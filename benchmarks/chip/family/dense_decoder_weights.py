"""Seeded weights of a dense decoder, made on the device, one layer at a time.

The benchmark makes the weights itself, from ``--seed``, so that its plain
reference can make the very same numbers again without taking anything the
program has made. Layer ``i`` is a pure function of ``(key, i)``: the program's
stacked parameters are these layers mapped over ``i`` inside one jitted call,
and the reference calls :func:`layer` for one layer at a time, so the float32
reference of a 4.4 B-parameter stage never holds more than one layer.

Scales. Random weights at the usual 0.02 leave the residual stream dominated
by the input embedding, and a tied head then predicts the input token again
and again: greedy decoding collapses onto repeats and every logit comparison
becomes trivial. So each projection has variance 1/fan_in, queries and keys
twice that (attention scores of spread ~2, not uniform), the embedding is
scaled so that ``embed * sqrt(d)`` has an RMS of 0.5, and every layer adds
about as much again to the residual stream. Norm gains are ``1 + g`` with
``g ~ N(0, 0.1)``, so the gain path is exercised too.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

NORM_GAIN_STD = 0.1
EMBED_RMS = 0.5        # RMS of embed * sqrt(d_model), the residual stream's start


def dims(conf: Dict) -> Dict[str, int]:
    """The sizes the weights need, from a configuration file's keys."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {
        "d": d, "h": h, "kh": conf["num_key_value_heads"],
        "dh": conf.get("head_dim", d // h), "f": conf["intermediate_size"],
        "v": conf["vocab_size"], "layers": conf["num_hidden_layers"],
        "gated": conf["hidden_act"] == "silu",
        "tied": bool(conf.get("tie_word_embeddings", False)),
    }


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, also one wider than 32 bits."""
    words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    key = jax.random.key(int(words[0] >> 1))
    return jax.random.fold_in(key, int(words[1] >> 1))


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer(dm: Dict, key: jax.Array, i, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Weights of layer ``i`` (a Python int or a traced index)."""
    d, h, kh, dh, f = dm["d"], dm["h"], dm["kh"], dm["dh"], dm["f"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1), i), 9)
    w = {
        "ln1": _normal(ks[0], (d,), NORM_GAIN_STD, dtype),
        "wq": _normal(ks[1], (d, h, dh), math.sqrt(2.0 / d), dtype),
        "wk": _normal(ks[2], (d, kh, dh), math.sqrt(2.0 / d), dtype),
        "wv": _normal(ks[3], (d, kh, dh), math.sqrt(1.0 / d), dtype),
        "wo": _normal(ks[4], (h, dh, d), math.sqrt(1.0 / (h * dh)), dtype),
        "ln2": _normal(ks[5], (d,), NORM_GAIN_STD, dtype),
        "w_in": _normal(ks[6], (d, f), math.sqrt(1.0 / d), dtype),
        "w_out": _normal(ks[8], (f, d), math.sqrt(1.0 / f), dtype),
    }
    if dm["gated"]:
        w["w_gate"] = _normal(ks[7], (d, f), math.sqrt(1.0 / d), dtype)
    return w


def embed(dm: Dict, key: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Token embedding, ``(vocab, d)``; also the head where it is tied."""
    return _normal(jax.random.fold_in(key, 2), (dm["v"], dm["d"]),
                   EMBED_RMS / math.sqrt(dm["d"]), dtype)


def head(dm: Dict, key: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Untied output head, ``(d, vocab)``: logits of unit spread."""
    return _normal(jax.random.fold_in(key, 3), (dm["d"], dm["v"]),
                   math.sqrt(1.0 / dm["d"]), dtype)


def final_norm(dm: Dict, key: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return _normal(jax.random.fold_in(key, 4), (dm["d"],), NORM_GAIN_STD, dtype)
