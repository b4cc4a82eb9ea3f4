"""Share of the traced stretch of the measured interval inside the engine's
``serve.prefill`` spans: the host dispatching the prompt positions."""
from program_spans import share


def read(rec):
    return share(rec, {"serve.prefill"})
