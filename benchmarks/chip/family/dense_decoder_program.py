"""The program's side of a dense decoder: its config object and its weights.

A configuration file of the benchmark holds the published keys
(``hidden_size``, ``num_hidden_layers`` ...). This module turns them into the
program's ``ModelConfig`` and lays the benchmark's seeded weights
(:mod:`dense_decoder_weights`) out in the program's parameter tree, in one
jitted call on the device. The tree is checked against the program's own
``init_params`` shapes, so a change of the program's layout fails here and
not as a wrong answer.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

import dense_decoder_weights as W
from repro.models.config import ModelConfig
from repro.models.lm import init_params


def program_config(conf: Dict) -> ModelConfig:
    dm = W.dims(conf)
    return ModelConfig(
        name=conf["name"], n_layers=dm["layers"], d_model=dm["d"],
        n_heads=dm["h"], n_kv_heads=dm["kh"], d_ff=dm["f"],
        vocab_size=dm["v"], head_dim=dm["dh"], rope_theta=conf["rope_theta"],
        norm_eps=conf.get("rms_norm_eps", conf.get("norm_epsilon")), tie_embeddings=dm["tied"],
        mlp_gated=dm["gated"], dtype=conf["serve_dtype"])


def _params(dm: Dict, padded_vocab: int, key: jax.Array) -> Dict:
    dtype = jnp.bfloat16
    stacked = jax.lax.map(lambda i: W.layer(dm, key, i, dtype),
                          jnp.arange(dm["layers"]))
    block = {"ln1": stacked["ln1"], "ln2": stacked["ln2"],
             "attn": {k: stacked[k] for k in ("wq", "wk", "wv", "wo")},
             "mlp": {k: stacked[k] for k in ("w_in", "w_out", "w_gate")
                     if k in stacked}}
    pad = padded_vocab - dm["v"]
    emb = jnp.pad(W.embed(dm, key, dtype), ((0, pad), (0, 0)))
    params = {"embed": emb, "final_norm": W.final_norm(dm, key, dtype),
              "groups": (block,)}
    if not dm["tied"]:
        params["head"] = jnp.pad(W.head(dm, key, dtype), ((0, 0), (0, pad)))
    return params


def make_params(conf: Dict, cfg: ModelConfig, seed: int) -> Dict:
    """The program's parameters for ``seed``, made on the device."""
    dm = W.dims(conf)
    fn = jax.jit(partial(_params, dm, cfg.padded_vocab))
    key = W.root_key(seed)
    want = jax.eval_shape(partial(init_params, cfg), key)
    got = jax.eval_shape(fn, key)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.map(lambda a: (a.shape, a.dtype), want)
            != jax.tree.map(lambda a: (a.shape, a.dtype), got)):
        raise RuntimeError("the program's parameter tree changed: "
                           f"{jax.tree.map(lambda a: a.shape, want)}")
    return fn(key)


dims = W.dims
