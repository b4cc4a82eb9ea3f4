"""Backend compiles and persistent-cache loads that start between the
opening of the window and the end of its last batch, drain included."""


def read(rec):
    return float(len(rec.compiles.started_in(rec.window[0], rec.served_until)))
