"""Plain float32 reference of the dense decoders, one layer at a time.

It follows the published block of both configurations: pre-norm
attention and MLP sublayers on a residual stream; grouped-query attention
with rotary embeddings (HF ``rotate_half`` layout) and a causal softmax
scaled by ``1/sqrt(head_dim)``; a SwiGLU MLP (``silu``, SmolLM/Llama layout)
or a ``gelu_pytorch_tanh`` MLP (StarCoder2); a final norm and a tied or untied
head. It imports nothing of the program and takes nothing it made: the
weights come again from the seed (:mod:`dense_decoder_weights`), one layer
at a time, upcast to float32, with every matmul at ``highest`` precision.

Departures from the published equations, each taken because the program
computes it so, and listed in the configuration files:

* the embedding is multiplied by ``sqrt(hidden_size)`` before the first layer;
* norms are RMSNorm with gain ``1 + g`` and no bias, also where StarCoder2
  publishes LayerNorm; its linear layers have no biases;
* StarCoder2's 4096-token sliding window is not applied (no sequence here
  reaches 4096 positions, so it would change nothing).

``quant="fp8"`` is the benchmark's control: the same forward with both
operands of every projection and of the head rounded to float8 e4m3, each
tensor scaled to the format's range — what a change that served the model in
fp8 would compute. It has to fail the comparison that the program passes.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import dense_decoder_weights as W

F8_MAX = 448.0   # largest finite float8_e4m3fn


def _fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, x, w, quant: Optional[str]):
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """x: (N, S, heads, dh); positions 0..S-1."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(dm: Dict, eps: float, theta: float, quant, x, w):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    n, s, _ = x.shape
    group = dm["h"] // dm["kh"]
    h = _rms(x, w["ln1"], eps)
    q = _rope(_mm("nsd,dhe->nshe", h, w["wq"], quant), theta)
    k = _rope(_mm("nsd,dhe->nshe", h, w["wk"], quant), theta)
    v = _mm("nsd,dhe->nshe", h, w["wv"], quant)
    k = jnp.repeat(k, group, axis=2)             # query head j reads kv head j // group
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("nqhe,nkhe->nhqk", q, k) / math.sqrt(dm["dh"])
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("nhqk,nkhe->nqhe", jax.nn.softmax(scores, -1), v)
    x = x + _mm("nshe,hed->nsd", att, w["wo"], quant)
    h = _rms(x, w["ln2"], eps)
    up = _mm("nsd,df->nsf", h, w["w_in"], quant)
    if dm["gated"]:
        up = jax.nn.silu(_mm("nsd,df->nsf", h, w["w_gate"], quant)) * up
    else:
        up = jax.nn.gelu(up, approximate=True)
    return x + _mm("nsf,fd->nsd", up, w["w_out"], quant)


def logits(conf: Dict, seed: int, tokens: np.ndarray, first: int,
           quant: Optional[str] = None) -> np.ndarray:
    """Float32 logits ``(N, S - first, vocab)`` at positions ``first..S-1``
    of the token rows ``tokens`` ``(N, S)``; row ``n``, column ``j`` predicts
    ``tokens[n, first + j + 1]``."""
    dm = W.dims(conf)
    eps = conf.get("rms_norm_eps", conf.get("norm_epsilon"))
    key = W.root_key(seed)
    gen = jax.jit(partial(W.layer, dm))
    step = jax.jit(partial(_layer, dm, eps, float(conf["rope_theta"]), quant))
    with jax.default_matmul_precision("highest"):
        emb = W.embed(dm, key).astype(jnp.float32)
        x = emb[jnp.asarray(tokens)] * math.sqrt(dm["d"])
        for i in range(dm["layers"]):
            x = step(x, gen(key, jnp.int32(i)))
        x = _rms(x[:, first:], W.final_norm(dm, key).astype(jnp.float32), eps)
        head = emb.T if dm["tied"] else W.head(dm, key).astype(jnp.float32)
        del emb
        out = _mm("nsd,dv->nsv", x, head, quant)
        return np.asarray(jax.device_get(out))
