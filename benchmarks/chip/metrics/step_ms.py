"""Device time per execution of the decode-step program, from the trace."""
from harness import STEP_PROGRAM


def read(rec):
    if rec.trace is None:
        return None
    ex = rec.trace.executions(STEP_PROGRAM)
    return sum(m.end - m.start for m in ex) / len(ex) / 1e6 if ex else None
