"""Requests admitted over batch slots offered, over the traced
``serve.batch`` spans (``size`` over ``batch_size``)."""
from program_spans import batches


def read(rec):
    sizes = [(b.attrs["size"], b.attrs["batch_size"]) for b, _ in batches(rec)]
    offered = sum(n for _, n in sizes)
    return 100.0 * sum(k for k, _ in sizes) / offered if offered else None
