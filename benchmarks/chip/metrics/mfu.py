"""Model operations of the useful tokens (prompt and generated, padding
excluded) of the batches counted in the measured interval, over the
interval times the chips' bf16 peak."""
import work


def read(rec):
    if not rec.batches or rec.interval <= 0:
        return None
    gen = rec.mix["gen_tokens"]
    flops = sum(work.request_flops(rec.dm, n, gen)
                for b in rec.batches for n in b.prompt_lens)
    return 100.0 * flops / (rec.interval * rec.chips * rec.peak["flops"])
