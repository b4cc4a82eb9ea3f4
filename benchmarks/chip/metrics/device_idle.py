"""1 - the union of the device's busy intervals over the traced window."""


def read(rec):
    t = rec.trace
    if t is None or t.devices == 0:
        return None
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)
