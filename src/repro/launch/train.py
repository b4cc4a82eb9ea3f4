"""End-to-end training driver: streaming-log data plane -> JAX train loop.

The full production story on one box: documents are ingested into an AgileLog
topic; the training job consumes exactly-resumable host-sharded batches; a
synthetic-data agent can inject validated curriculum via a promotable cFork;
checkpoints (params + optimizer + data cursor) commit atomically to the same
object store; crash/restart resumes the identical batch stream.

Usage (CPU-scale, examples/train_e2e.py wraps this):
    PYTHONPATH=src python -m repro.launch.train --steps 200 --d-model 256
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import BoltSystem
from ..data import LogDataPipeline, TokenStreamWriter, synthetic_token_docs
from ..models.config import ModelConfig
from ..models.lm import init_params
from ..streams import Topic
from ..train.checkpoint import CheckpointManager
from ..train.optimizer import AdamWConfig, adamw_init
from ..train.step import make_train_step
from .cache import enable_compile_cache


def small_config(d_model: int, n_layers: int, vocab: int) -> ModelConfig:
    return ModelConfig(
        name=f"train-e2e-{d_model}", n_layers=n_layers, d_model=d_model,
        n_heads=max(2, d_model // 64), n_kv_heads=max(1, d_model // 128),
        d_ff=d_model * 4, vocab_size=vocab, tie_embeddings=True,
        remat="none", attn_chunk=128)


def run(steps: int = 100, d_model: int = 128, n_layers: int = 4,
        batch: int = 4, seq: int = 128, vocab: int = 2048,
        resume: bool = False, store=None, system=None, log_every: int = 20,
        ckpt_every: int = 50, seed: int = 0):
    cfg = small_config(d_model, n_layers, vocab)
    total, _ = cfg.count_params()
    print(f"model: {cfg.name} ({total/1e6:.1f}M params)")

    # ---- data plane: the forkable shared log --------------------------------
    # The log is a durable shared SERVICE; the training job is a client.
    # Crash/resume means the job re-attaches to the same BoltSystem (pass
    # `system=`), finds its token stream and checkpoint catalog by name, and
    # resumes — checkpoints are log forks now (DESIGN.md §17), so their
    # lineage lives in the log's metadata, not in ad-hoc store keys.
    system = system if system is not None else BoltSystem(n_brokers=4,
                                                          store=store)
    existing = system.find_log("train-tokens")
    if existing is None:
        topic = Topic.create(system, "train-tokens")
        writer = TokenStreamWriter(topic, batch_docs=64)
        for doc in synthetic_token_docs(4000, vocab=vocab, min_len=64,
                                        max_len=512, seed=seed):
            writer.write_doc(doc)
        writer.flush()
    else:
        topic = Topic("train-tokens", existing)
    pipe = LogDataPipeline(topic, batch_size=batch, seq_len=seq)

    # ---- model + optimizer ----------------------------------------------------
    opt_cfg = AdamWConfig(lr=1e-3, weight_decay=0.01)
    ckpt = CheckpointManager(system, prefix="ckpt")
    start_step = 0
    if resume and ckpt.latest_step() is not None:
        orphans = ckpt.recover()    # reclaim forks a crashed save left behind
        if orphans:
            print(f"recovered {len(orphans)} orphaned checkpoint fork(s)")
        start_step, params, opt_state, extra = ckpt.restore()
        pipe.restore(tuple(extra["cursor"]))
        print(f"resumed from step {start_step}, cursor {extra['cursor']}")
    else:
        params = init_params(cfg, jax.random.key(seed))
        opt_state = adamw_init(params, opt_cfg)

    step_fn = jax.jit(make_train_step(cfg, opt_cfg, accum=1),
                      donate_argnums=(0, 1))

    losses = []
    t0 = time.time()
    for step in range(start_step, steps):
        block = next(pipe)
        batch_dict = {"tokens": jnp.asarray(block[:, :-1]),
                      "labels": jnp.asarray(block[:, 1:])}
        params, opt_state, metrics = step_fn(params, opt_state, batch_dict)
        losses.append(float(metrics["loss"]))
        if (step + 1) % log_every == 0:
            tput = batch * seq * log_every / (time.time() - t0)
            print(f"step {step + 1:5d} loss {np.mean(losses[-log_every:]):.4f} "
                  f"({tput:.0f} tok/s)")
            t0 = time.time()
        if (step + 1) % ckpt_every == 0:
            ckpt.save(step + 1, params, opt_state,
                      extra={"cursor": list(pipe.cursor())})
    return losses, params, system


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    losses, _, _ = run(steps=args.steps, d_model=args.d_model,
                       n_layers=args.layers, batch=args.batch, seq=args.seq,
                       resume=args.resume)
    print(f"final loss: {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
