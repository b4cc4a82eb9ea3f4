"""Least time of the traced decode steps over their device time. A step's
least time is the larger of its operations over the peak rate and its bytes
(weights, cached positions read, one written, logits) over HBM bandwidth."""
import work
from harness import traced_steps


def read(rec):
    if rec.trace is None:
        return None
    steps = traced_steps(rec)
    if not steps:
        return None
    least = sum(work.step_least_s(rec.dm, b, pos, rec.peak) for b, pos, _ in steps)
    return 100.0 * least / (sum(ns for _, _, ns in steps) / 1e9)
