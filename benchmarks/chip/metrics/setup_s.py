"""Set-up: from the start of the process to the opening of the window,
with loading, weight generation, compilation and warm-up in it."""


def read(rec):
    return rec.setup_s
