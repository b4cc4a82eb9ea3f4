"""What decides ``correct``: the log's read-back and the served tokens' logits.

Log. Every record the engine's append receipts acknowledged is read back
through a client subscription, exactly once per ``(id, seq)`` and once per
EOS, with the same token; every request attempted has its EOS, after exactly
``gen_tokens`` tokens. These counts are compared exactly (limit 0).

Model. Once the window has closed and the program's state is freed, a sample
of the answered requests, drawn from the seed with the longest prompt in it,
goes through the plain float32 reference over its prompt and served tokens.
At each served position the gap is the reference's best logit less its logit
of the served token; the widest gap over the sample is compared with the
configuration's limit (``check.logit_gap_limit``), which ``PERF.md`` derives
from readings of sound runs and of the fp8 control.
"""

from __future__ import annotations

import math
from collections import Counter, defaultdict
from typing import Dict, Iterable, List, Tuple

import numpy as np

CHECK_TOKENS = 1024     # served tokens in the reference's sample, at least


def record_key(rec: dict) -> tuple:
    return (rec["id"], "eos") if rec.get("eos") else (rec["id"], rec["seq"])


def log_counts(acked: Dict[tuple, dict], readback: List[dict],
               attempted: Iterable[str], gen_tokens: int,
               ack_duplicates: int) -> Dict[str, int]:
    """Exact counts of what the log lost, duplicated or changed."""
    seen = Counter(record_key(r) for r in readback)
    back = {record_key(r): r for r in readback}
    lost = sum(1 for k in acked if k not in back)
    changed = sum(1 for k, r in back.items() if acked.get(k) != r)
    toks: Dict[str, int] = defaultdict(int)
    for k in back:
        if k[1] != "eos":
            toks[k[0]] += 1
    wrong_eos = 0
    for rid in attempted:
        eos = back.get((rid, "eos"))
        if eos is None or eos["n"] != gen_tokens or toks[rid] != gen_tokens:
            wrong_eos += 1
    return {"log_lost": lost,
            "log_duplicated": sum(n - 1 for n in seen.values()) + ack_duplicates,
            "log_changed": changed,
            "requests_unanswered": wrong_eos}


def served_tokens(readback: List[dict]) -> Dict[str, List[int]]:
    by_id: Dict[str, Dict[int, int]] = defaultdict(dict)
    for r in readback:
        if not r.get("eos"):
            by_id[r["id"]][r["seq"]] = r["tok"]
    return {rid: [s[i] for i in sorted(s)] for rid, s in by_id.items()}


def sample(answered: List[str], prompts: Dict[str, List[int]],
           gen_tokens: int, seed: int) -> List[str]:
    """Requests for the reference, drawn from the seed, with the longest
    prompt among them, until they hold ``CHECK_TOKENS`` served tokens."""
    if not answered:
        return []
    k = min(len(answered), math.ceil(CHECK_TOKENS / gen_tokens))
    longest = max(answered, key=lambda r: len(prompts[r]))
    rest = [r for r in answered if r != longest]
    rng = np.random.default_rng([seed, 4])
    picked = rng.choice(len(rest), size=k - 1, replace=False) if k > 1 else []
    return [longest] + [rest[i] for i in sorted(picked)]


def gaps(reference, conf: Dict, seed: int, rows: List[Tuple[List[int], List[int]]],
         quant=None) -> Tuple[np.ndarray, np.ndarray]:
    """Reference gaps of ``rows`` of (prompt, served tokens), grouped by
    length. Returns (gap of each served token, gap of the token that the
    ``quant`` control puts first) -- the second only with ``quant``."""
    served_gap: List[np.ndarray] = []
    control_gap: List[np.ndarray] = []
    by_len: Dict[Tuple[int, int], List[int]] = defaultdict(list)
    for i, (p, s) in enumerate(rows):
        by_len[(len(p), len(s))].append(i)
    for (plen, glen), idx in sorted(by_len.items()):
        toks = np.asarray([rows[i][0] + rows[i][1][:-1] for i in idx], np.int32)
        served = np.asarray([rows[i][1] for i in idx], np.int32)
        ref = reference.logits(conf, seed, toks, plen - 1)
        best = ref.max(-1)
        served_gap.append(best - np.take_along_axis(ref, served[..., None], -1)[..., 0])
        if quant is not None:
            low = reference.logits(conf, seed, toks, plen - 1, quant=quant)
            pick = low.argmax(-1)
            control_gap.append(best - np.take_along_axis(ref, pick[..., None], -1)[..., 0])
    cat = lambda xs: np.concatenate([x.ravel() for x in xs]) if xs else np.zeros(0)
    return cat(served_gap), cat(control_gap)
