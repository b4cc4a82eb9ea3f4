"""Share of the traced stretch of the measured interval inside the engine's
``serve.decode`` spans: the host dispatching the generated positions and
their argmax."""
from program_spans import share


def read(rec):
    return share(rec, {"serve.decode"})
