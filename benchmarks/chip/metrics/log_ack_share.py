"""Share of the measured interval spent inside the response append and its
receipt's ``wait()`` (spans of the harness's proxy around the log)."""


def read(rec):
    if rec.interval <= 0:
        return None
    lo, hi = rec.window
    return 100.0 * rec.spans.seconds({"log_append", "log_wait"}, lo, hi) / rec.interval
