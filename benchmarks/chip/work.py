"""The yardstick: the chip's peaks, and the work a decode step needs.

Operations and bytes come from a configuration's shapes, not from the
compiled program, so a roofline share reads the same work whatever
implements the step. The counts start from ``serve/costs.py``'s analytic
``decode_cost`` and differ from it where it does not count the work a step
needs (``test_work.py`` pins each difference):

* the output head's matmul is counted in the operations (``decode_cost``
  counts only the layers' parameters);
* attention's score and value products over the context are counted;
* an untied embedding table is not read whole: a step gathers one row per
  sequence, while the head is read whole;
* a step at position ``t`` reads the ``t`` cached positions before it and
  writes one, not the whole cache that the program allocates.

Norm gains (a few kB) are left out of the bytes, as in ``decode_cost``.
"""

from __future__ import annotations

from typing import Dict

BF16 = 2

# Published peaks of one chip, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def layer_params(dm: Dict) -> int:
    """Matmul parameters of one layer: attention and MLP projections."""
    d, h, kh, dh, f = dm["d"], dm["h"], dm["kh"], dm["dh"], dm["f"]
    attn = d * h * dh + 2 * d * kh * dh + h * dh * d
    return attn + (3 if dm["gated"] else 2) * d * f


def kv_bytes_per_token(dm: Dict) -> int:
    return dm["layers"] * 2 * dm["kh"] * dm["dh"] * BF16


def flops_per_token(dm: Dict, ctx: int) -> float:
    """Model operations to run one token at a position that attends to
    ``ctx`` positions (itself included): every projection, the head, and
    attention's two products."""
    matmul = dm["layers"] * layer_params(dm) + dm["d"] * dm["v"]
    return 2.0 * matmul + 4.0 * dm["layers"] * dm["h"] * dm["dh"] * ctx


def step_flops(dm: Dict, batch: int, pos: int) -> float:
    """One decode step of ``batch`` sequences at position ``pos`` (0-based)."""
    return batch * flops_per_token(dm, pos + 1)


def step_bytes(dm: Dict, batch: int, pos: int) -> float:
    """HBM bytes one decode step needs at position ``pos``: every layer's
    weights and the head once, one embedding row per sequence, the ``pos``
    cached positions read, one position written, the logits written."""
    weights = dm["layers"] * layer_params(dm) + dm["d"] * dm["v"]
    kv = kv_bytes_per_token(dm)
    return (weights * BF16 + batch * dm["d"] * BF16
            + batch * pos * kv + batch * kv + batch * dm["v"] * BF16)


def step_least_s(dm: Dict, batch: int, pos: int, peak: Dict[str, float]) -> float:
    """The least time of one step: the larger of its operations over the
    peak rate and its bytes over the HBM bandwidth."""
    return max(step_flops(dm, batch, pos) / peak["flops"],
               step_bytes(dm, batch, pos) / peak["hbm_bytes_per_s"])


def request_flops(dm: Dict, prompt: int, gen: int) -> float:
    """Model operations of one request's useful tokens: the prompt and the
    generated tokens fed back (``prompt + gen - 1`` positions), padding
    excluded, each at its own position."""
    return sum(flops_per_token(dm, t + 1) for t in range(prompt + gen - 1))
