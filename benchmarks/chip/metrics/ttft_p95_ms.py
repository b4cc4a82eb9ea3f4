"""95th percentile over every request due in the window of the time from
its due time to its first response token acknowledged on the log."""
from harness import percentile


def read(rec):
    v = rec.latencies["ttft"]
    return percentile(v, 95) * 1e3 if v else None
