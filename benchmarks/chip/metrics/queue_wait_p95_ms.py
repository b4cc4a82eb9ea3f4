"""95th percentile (nearest rank), over the requests the traced batches
admitted, of the time from a request's due time to the end of the poll that
admitted it.

A request's due time is its acknowledgement less its time to first token:
the harness stamps the acknowledgement as its receipt's ``wait()`` returns,
which is where the batch's last ``log.wait`` span ends. Request ``r<k>`` is
the k-th request of the open loop, whose time to first token is
``rec.latencies["ttft"][k]``."""
from harness import percentile
from program_spans import batches


def read(rec):
    ttft = rec.latencies["ttft"]
    waits = []
    for b, under in batches(rec):
        polls, acks = under.get("serve.poll"), under.get("log.wait")
        if not polls or not acks:
            continue
        service = acks[-1].end - polls[0].end
        for rid in b.attrs["ids"]:
            k = int(rid[1:]) if rid[1:].isdigit() else len(ttft)
            if k < len(ttft):
                waits.append(ttft[k] - service)
    return percentile(waits, 95) * 1e3 if waits else None
