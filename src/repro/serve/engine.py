"""Batched serving engine: request topic -> prefill -> decode -> response topic.

Reworked for DESIGN.md §17: both sides of the engine now ride the §12
session API end-to-end. Requests arrive through a tailing
``log.subscribe()`` (held by the offset-tracking :class:`Consumer`, whose
cursor is a durable resume token), and every response token batch is
appended with an :class:`AppendReceipt` the engine waits on before
committing its request cursor — a crash between the two replays the batch
rather than losing it. Per-token response records are ``(id, seq)``-keyed so
clients demux the shared response stream from their own subscription.

The production-shape decode step (sequence-sharded KV cache, flash-decoding
combine) is what the dry-run compiles per (arch × decode shape); this engine
is the same step driven end-to-end at host scale. :class:`ModelTarget` /
:class:`ModelDraft` adapt that step to the JAX-free speculative driver
(``serve/speculative.py``), which maps each draft rollout onto a
``log.speculate()`` session.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.sim import ServeStats
from ..models.config import ModelConfig
from ..models.lm import decode_step, init_caches
from ..streams.topics import Consumer, Topic
from .speculative import encode_eos, encode_token


# one jitted step, shared by every engine and adapter (cfg is static)
_decode_step = jax.jit(decode_step, static_argnums=0)


class _JaxStepper:
    """Greedy decode over the repo's ``decode_step``, recomputed from the
    prefix each call. O(T) steps per call is the honest trade for test-scale
    configs: no per-request cache registry to invalidate when a speculative
    branch is squashed — the log IS the state, the model is a pure function
    of it (the §17 mapping taken literally)."""

    def __init__(self, cfg: ModelConfig, params,
                 stats: Optional[ServeStats] = None) -> None:
        self.cfg = cfg
        self.params = params
        self.stats = stats
        self._step = functools.partial(_decode_step, cfg)

    def _greedy(self, logits: jax.Array) -> int:
        return int(jnp.argmax(logits[0, -1, :self.cfg.vocab_size]))

    def _run(self, tokens: List[int], extra: int) -> tuple:
        """Feed ``tokens`` one position at a time; returns (logits, caches)
        after the last token, with cache room for ``extra`` more."""
        caches = init_caches(self.cfg, 1, len(tokens) + extra)
        arr = jnp.asarray(np.asarray(tokens, np.int32)[None, :])
        logits = None
        for t in range(len(tokens)):
            logits, caches = self._step(self.params, caches,
                                        arr[:, t:t + 1],
                                        jnp.asarray(t, jnp.int32))
        return logits, caches


class ModelTarget(_JaxStepper):
    """TargetModel adapter: ``verify(prefix, draft)`` returns the k+1 greedy
    tokens (position i conditioned on ``prefix + draft[:i]``) — one logical
    forward pass over the draft window."""

    def verify(self, prefix: List[int], draft: List[int]) -> List[int]:
        logits, caches = self._run(list(prefix), extra=len(draft) + 1)
        if self.stats is not None:
            self.stats.model_steps += 1
        out = [self._greedy(logits)]
        base = len(prefix)
        for i, tok in enumerate(draft):
            arr = jnp.asarray([[tok]], jnp.int32)
            logits, caches = self._step(self.params, caches, arr,
                                        jnp.asarray(base + i, jnp.int32))
            out.append(self._greedy(logits))
        return out


class ModelDraft(_JaxStepper):
    """DraftModel adapter: k greedy tokens from the (smaller) draft model."""

    def propose(self, prefix: List[int], k: int) -> List[int]:
        logits, caches = self._run(list(prefix), extra=k)
        if self.stats is not None:
            self.stats.draft_steps += k
        out = []
        base = len(prefix)
        for i in range(k):
            tok = self._greedy(logits)
            out.append(tok)
            if i + 1 < k:
                arr = jnp.asarray([[tok]], jnp.int32)
                logits, caches = self._step(self.params, caches, arr,
                                            jnp.asarray(base + i, jnp.int32))
        return out


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, requests: Topic,
                 responses: Topic, batch_size: int = 4,
                 max_len: int = 64, group: str = "serve") -> None:
        self.cfg = cfg
        self.params = params
        # subscription-backed consumer (§12): restore() makes the request
        # cursor a durable resume token, so a restarted engine re-serves
        # exactly the uncommitted suffix
        self.consumer = Consumer.restore(requests, group=group)
        self.responses = responses
        self.batch_size = batch_size
        self.max_len = max_len
        self.stats: ServeStats = requests.log.system.serve_stats
        self.tracer = requests.log.system.tracer
        self._step = functools.partial(_decode_step, cfg)
        self.served = 0

    def _greedy(self, logits: jax.Array) -> jax.Array:
        return jnp.argmax(logits[:, -1, :self.cfg.vocab_size], axis=-1)

    def poll_and_serve(self, gen_tokens: int = 16) -> int:
        """Serve one batch of requests from the subscription; returns
        #served. Response tokens are appended as ``(id, seq)`` records plus
        an EOS marker per request; the receipt is waited before the request
        cursor commits (at-least-once across a crash, deduped by key).

        While the system's tracer is on, a batch that admits requests
        records a ``serve.batch`` span holding one span per phase, in
        order: ``serve.poll``, ``serve.prefill``, ``serve.decode``,
        ``serve.fetch``, ``serve.encode``, ``serve.append``, ``serve.ack``,
        ``serve.commit``. The tracer follows JAX's profiler: it records,
        into the profile too, while a profiler session runs."""
        tracer = self.tracer
        tracer.follow(jax.profiler.TraceAnnotation.is_enabled(),
                      jax.profiler.TraceAnnotation)
        with tracer.span("serve.batch") as batch:
            with tracer.span("serve.poll"):
                reqs = self.consumer.poll(self.batch_size)
            if not reqs:
                batch.drop()
                return 0
            B = len(reqs)
            self.stats.requests += B
            prompts = [r["prompt"] for r in reqs]
            plen = max(len(p) for p in prompts)
            if batch.on:
                batch.set(ids=[r["id"] for r in reqs], size=B,
                          batch_size=self.batch_size)
            # each position's host time is a lap of its phase's span
            with tracer.span("serve.prefill") as phase:
                timing = phase.on
                toks = np.full((B, plen), 1, np.int32)
                for i, p_ in enumerate(prompts):
                    toks[i, plen - len(p_):] = p_   # left-pad
                tokens = jnp.asarray(toks)
                caches = init_caches(self.cfg, B, plen + gen_tokens)
                logits = None
                for t in range(plen):   # teacher-forced prefill, decode path
                    logits, caches = self._step(self.params, caches,
                                                tokens[:, t:t + 1],
                                                jnp.asarray(t, jnp.int32))
                    self.stats.model_steps += 1
                    if timing:
                        phase.lap(t)
            with tracer.span("serve.decode") as phase:
                timing = phase.on
                outs = [self._greedy(logits)]
                for t in range(plen, plen + gen_tokens - 1):
                    logits, caches = self._step(self.params, caches,
                                                outs[-1][:, None],
                                                jnp.asarray(t, jnp.int32))
                    outs.append(self._greedy(logits))
                    self.stats.model_steps += 1
                    if timing:
                        phase.lap(t)
            with tracer.span("serve.fetch"):
                gen = np.asarray(jnp.stack(outs, axis=1))
            with tracer.span("serve.encode"):
                records: List[bytes] = []
                for i, r in enumerate(reqs):
                    records.extend(encode_token(r["id"], j, int(tok))
                                   for j, tok in enumerate(gen[i]))
                    records.append(encode_eos(r["id"], int(gen.shape[1])))
            with tracer.span("serve.append"):
                receipt = self.responses.log.append_batch(records)
            with tracer.span("serve.ack"):
                receipt.wait()      # durable before the request cursor moves
            with tracer.span("serve.commit"):
                self.consumer.commit()
        self.stats.tokens_out += int(gen.size)
        self.stats.responses += B
        self.served += B
        return B
