"""The longest host time of one position (``host_max_s``) among the traced
``serve.prefill`` and ``serve.decode`` spans."""
from program_spans import PHASES, batches


def read(rec):
    longest = [p.attrs["host_max_s"] for _, under in batches(rec)
               for name in PHASES for p in under.get(name, ())
               if "host_max_s" in p.attrs]
    return max(longest) * 1e3 if longest else None
