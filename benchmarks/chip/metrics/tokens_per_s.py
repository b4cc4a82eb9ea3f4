"""Output tokens acknowledged by the batches that started inside the window,
over the time from the first one's start to the last one's acknowledgement."""


def read(rec):
    if not rec.batches or rec.interval <= 0:
        return None
    return sum(b.size for b in rec.batches) * rec.mix["gen_tokens"] / rec.interval
