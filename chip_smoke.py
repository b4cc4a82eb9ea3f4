#!/usr/bin/env python3
"""Bring-up smoke: the log-fed serving path of smollm-135m on one TPU chip.

    python chip_smoke.py

Everything runs in this one process, in order:

  serve        A BoltSystem with a ``requests`` and a ``responses`` topic.
               ServeEngine reads 4 requests (32-token prompts) from its log
               subscription, decodes 16 tokens each at smollm-135m's
               published widths, and appends them back with an EOS per
               request. The tokens are checked against one teacher-forced
               ``forward`` over prompt + response.
  speculative  SpeculativeDecoder (target smollm-135m, draft its SMOKE
               config, k=4) writes 8 tokens through ``speculate()``
               sessions; the tokens and the log's view of them must equal
               sequential greedy decode.
  kernels      flash_attention and mlstm_chunk compiled for the chip
               (interpret=False) at one real shape each, checked against
               ``kernels/ref.py`` within the tolerances of
               ``tests/test_kernels.py``.

Weights come from ``init_params`` with a fixed seed; nothing is downloaded.
The lines before the last are bring-up readings, not benchmark numbers. The
last line is one JSON object, printed only when every phase passed. Off a
TPU the script exits non-zero without it: there is no CPU fallback.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core import BoltSystem  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.mlstm_chunk import mlstm_chunk  # noqa: E402
from repro.kernels.ref import flash_attention_ref, mlstm_ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models.lm import forward, init_params  # noqa: E402
from repro.serve import (ModelDraft, ModelTarget, ServeEngine,  # noqa: E402
                         SpeculativeDecoder, decode_response,
                         sequential_decode)
from repro.streams import Producer, Topic  # noqa: E402
from repro.streams.records import decode_record  # noqa: E402

SEED = 0
ARCH = "smollm-135m"
BATCH, PROMPT, GEN = 4, 32, 16
SPEC_K, SPEC_GEN = 4, 8
# Served logits are bf16, 8 significant bits: a logit in [2^e, 2^(e+1)) is
# held to a step of 2^(e-7). Cached decode (one position per step) and the
# full forward (chunked online softmax) sum in different orders through 30
# layers; on CPU at these widths they differ by at most one such step. A
# served token may differ from the forward's argmax only where the forward's
# top logit beats the served token's by at most BF16_STEPS steps of the
# row's largest |logit| — a near-tie that rounding can flip.
BF16_STEPS = 4
FLASH_SHAPE = (4, 9, 3, 1024, 64)   # B, H, KH, S, Dh: smollm-135m heads
MLSTM_SHAPE = (1, 4, 256, 1024)     # B, H, S, Dh: xlstm-1.3b mLSTM heads
MLSTM_CHUNK = 64
FLASH_TOL, MLSTM_TOL = 2e-2, 3e-4   # tests/test_kernels.py (bf16, f32)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# one program per config: run eagerly, init_params compiles each of its ops
init = jax.jit(init_params, static_argnums=0)


def reading(msg: str) -> None:
    print(f"bring-up reading (not a benchmark): {msg}", flush=True)


class CompileLog:
    """Compile-or-load seconds per jitted function, and persistent-cache
    hits and writes, from JAX's monitoring events."""

    def __init__(self) -> None:
        self.events = []
        self.hits = self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((kw.get("fun_name"), seconds))

    def _event(self, event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def first(self, fun_name: str) -> float:
        return next(s for name, s in self.events
                    if name == f"jit({fun_name})")


def serve_phase(cfg, params, system, rng, compiles: CompileLog) -> None:
    requests = Topic.create(system, "requests")
    responses = Topic.create(system, "responses")
    prod = Producer(requests)
    prompts = {f"req-{i}": [int(t) for t in rng.integers(2, cfg.vocab_size,
                                                          PROMPT)]
               for i in range(BATCH)}
    for rid, prompt in prompts.items():
        prod.produce({"id": rid, "prompt": prompt})
    prod.flush()

    eng = ServeEngine(cfg, params, requests, responses, batch_size=BATCH)
    assert eng.poll_and_serve(gen_tokens=GEN) == BATCH
    reading(f"first decode step compile s={compiles.first('decode_step')!r}")
    assert eng.poll_and_serve(gen_tokens=GEN) == 0   # durable cursor moved

    raw = responses.log.read(0, responses.log.visible_tail)
    eos = {r["id"]: r["n"] for r in map(decode_record, raw) if r.get("eos")}
    assert eos == {rid: GEN for rid in prompts}, eos
    out = decode_response(raw)
    assert set(out) == set(prompts)
    assert all(len(toks) == GEN for toks in out.values()), out

    # one teacher-forced forward over prompt + response: position P-1+j
    # predicts served token j
    ids = sorted(prompts)
    seq = np.asarray([prompts[r] + out[r] for r in ids], np.int32)
    logits = jax.jit(lambda p, t: forward(cfg, p, {"tokens": t})[0])(
        params, jnp.asarray(seq))
    pred = np.asarray(logits[:, PROMPT - 1:PROMPT + GEN - 1, :cfg.vocab_size]
                      .astype(jnp.float32))
    served = seq[:, PROMPT:]
    top = pred.argmax(-1)
    gap = pred.max(-1) - np.take_along_axis(pred, served[..., None], -1)[..., 0]
    step = 2.0 ** (np.floor(np.log2(np.abs(pred).max(-1))) - 7)
    near_tie = (top != served) & (gap <= BF16_STEPS * step)
    wrong = (top != served) & ~near_tie
    assert not wrong.any(), (
        f"served tokens disagree with the forward's argmax at "
        f"{np.argwhere(wrong).tolist()} (gaps {gap[wrong].tolist()})")
    top2 = np.sort(pred, -1)[..., -2:]
    reading(f"serve check: {int((top == served).sum())}/{served.size} tokens "
            f"equal the forward argmax, {int(near_tie.sum())} near-ties; "
            f"smallest top-two margin {float((top2[..., 1] - top2[..., 0]).min())!r}")


def speculative_phase(cfg, params, system, rng) -> None:
    dcfg = get_smoke_config(ARCH)
    stats = system.serve_stats
    target = ModelTarget(cfg, params, stats=stats)
    draft = ModelDraft(dcfg, init(dcfg, jax.random.key(SEED + 1)),
                       stats=stats)
    spec_log = system.create_log("spec-responses")
    dec = SpeculativeDecoder(target, draft, k=SPEC_K, stats=stats)
    prompt = [int(t) for t in rng.integers(2, cfg.vocab_size, PROMPT)]
    ref = sequential_decode(target, prompt, SPEC_GEN)
    res = dec.decode_request(spec_log, "spec-0", prompt, SPEC_GEN)
    assert res.tokens == ref, (res.tokens, ref)
    view = decode_response(spec_log.read(0, spec_log.visible_tail))
    assert view == {"spec-0": ref}, view
    reading(f"speculative: {len(res.tokens)} tokens in {len(res.rollouts)} "
            f"speculate() sessions, acceptance {res.acceptance!r}")


def kernel_phase() -> None:
    ks = jax.random.split(jax.random.key(SEED + 2), 8)
    B, H, KH, S, Dh = FLASH_SHAPE
    q = jax.random.normal(ks[0], (B, H, S, Dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, KH, S, Dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, KH, S, Dh), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=False)
    with jax.default_matmul_precision("highest"):
        ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=FLASH_TOL, atol=FLASH_TOL)

    B, H, S, Dh = MLSTM_SHAPE
    q = jax.random.normal(ks[3], (B, H, S, Dh), jnp.float32)
    k = jax.random.normal(ks[4], (B, H, S, Dh), jnp.float32) * Dh ** -0.5
    v = jax.random.normal(ks[5], (B, H, S, Dh), jnp.float32)
    li = jax.random.normal(ks[6], (B, H, S), jnp.float32)
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[7], (B, H, S)) + 2.0)
    out = mlstm_chunk(q, k, v, li, lf, chunk=MLSTM_CHUNK, interpret=False)
    with jax.default_matmul_precision("highest"):
        ref, _ = mlstm_ref(q, k, v, li, lf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=MLSTM_TOL, atol=MLSTM_TOL)
    reading(f"kernels: flash_attention {FLASH_SHAPE} bf16 and mlstm_chunk "
            f"{MLSTM_SHAPE} f32 match kernels/ref.py")


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    reading(f"device {dev.device_kind!r} x{len(devices)}")
    reading(f"compile cache dir {cache_dir}")

    cfg = get_config(ARCH)
    rng = np.random.default_rng(SEED)
    system = BoltSystem(n_brokers=4)
    t0 = time.perf_counter()
    params = init(cfg, jax.random.key(SEED))
    jax.block_until_ready(params)
    reading(f"init_params s={time.perf_counter() - t0!r}")
    phases = [("serve", lambda: serve_phase(cfg, params, system, rng,
                                            compiles)),
              ("speculative", lambda: speculative_phase(cfg, params, system,
                                                        rng)),
              ("kernels", kernel_phase)]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        reading(f"phase {name} wall s={time.perf_counter() - t0!r}")
    compile_s = sum(s for _, s in compiles.events)
    reading(f"compile-or-load s={compile_s!r} over {len(compiles.events)} "
            f"programs; persistent cache {compiles.hits} hits, "
            f"{compiles.writes} writes")
    stats = dev.memory_stats() or {}
    reading(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
