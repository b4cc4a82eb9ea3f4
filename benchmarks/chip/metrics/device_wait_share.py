"""Share of the traced stretch of the measured interval inside the engine's
``serve.fetch`` spans: the host blocked on the device for the generated
tokens."""
from program_spans import share


def read(rec):
    return share(rec, {"serve.fetch"})
