"""Attention: chunked flash (train/prefill), sharded flash-decode, GQA, MLA.

TPU-native choices (DESIGN.md §3):
  * train/prefill attention is an online-softmax scan over KV chunks — the
    XLA-level flash formulation (fp32 accumulators, chunk sized for VMEM). The
    Pallas kernel (kernels/flash_attention.py) computes the same thing but is
    not called from here.
  * decode shards the KV cache's *sequence* dim over the 'model' axis and
    combines per-shard partial attention with a log-sum-exp reduction — flash-
    decoding expressed in pure SPMD (the cross-shard combine lowers to small
    all-reduces over ICI).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.context import shard
from .config import ModelConfig
from .layers import apply_rope, dense_init, pdtype, rms_norm


# ------------------------------------------------------------------ init
def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Dict:
    D, Dh = cfg.d_model, cfg.head_dim_
    H, KH = cfg.n_heads_padded, cfg.n_kv_heads   # params store ORIGINAL kv heads
    ks = jax.random.split(key, 6)
    dt = pdtype(cfg)
    p = {
        "wq": dense_init(ks[0], (D, H, Dh), dtype=dt),
        "wk": dense_init(ks[1], (D, KH, Dh), dtype=dt),
        "wv": dense_init(ks[2], (D, KH, Dh), dtype=dt),
        "wo": dense_init(ks[3], (H, Dh, D),
                         std=0.02 / (2 * cfg.n_layers) ** 0.5, dtype=dt),
    }
    if cfg.n_heads_padded > cfg.n_heads:
        # pad q-slots are the LAST slots of each kv superblock, so real head j
        # keeps its original kv group (permutation-equivalent, exact geometry);
        # their wo rows are zeroed so they cannot affect the output
        sb = H // KH                       # slots per original kv head
        real = cfg.n_heads // KH           # real q heads per kv head
        mask = ((jnp.arange(H) % sb) < real).astype(dt)[:, None, None]
        p["wo"] = p["wo"] * mask
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((Dh,), dt)
        p["k_norm"] = jnp.zeros((Dh,), dt)
    return p


def init_mla(key, cfg: ModelConfig) -> Dict:
    m = cfg.mla
    D, Dh, H = cfg.d_model, cfg.head_dim_, cfg.n_heads_padded
    ks = jax.random.split(key, 5)
    dt = pdtype(cfg)
    return {
        "w_dkv": dense_init(ks[0], (D, m.kv_lora_rank + m.rope_head_dim), dtype=dt),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dt),
        "w_uk": dense_init(ks[1], (m.kv_lora_rank, H, Dh), dtype=dt),
        "w_uv": dense_init(ks[2], (m.kv_lora_rank, H, Dh), dtype=dt),
        "wq": dense_init(ks[3], (D, H, Dh + m.rope_head_dim), dtype=dt),
        "wo": dense_init(ks[4], (H, Dh, D),
                         std=0.02 / (2 * cfg.n_layers) ** 0.5, dtype=dt),
    }


# ------------------------------------------------------------- flash (train)
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool, chunk: int,
                    q_offset: int = 0) -> jax.Array:
    """q: (B, H, Sq, Dhk); k: (B, KH, Sk, Dhk); v: (B, KH, Sk, Dhv) with
    H = KH * G (Dhk may exceed Dhv, e.g. MLA rope-extended keys).
    Online-softmax scan over KV chunks; fp32 accumulators."""
    B, H, Sq, Dh = q.shape
    KH, Sk = k.shape[1], k.shape[2]
    Dhv = v.shape[-1]
    G = H // KH
    qg = q.reshape(B, KH, G, Sq, Dh)
    scale = Dh ** -0.5
    chunk = min(chunk, Sk)
    kv_len = Sk
    pad = (-Sk) % chunk
    if pad:  # non-divisible kv length (e.g. whisper's 1500 frames): pad + mask
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        Sk = Sk + pad
    n_chunks = Sk // chunk
    kc = jnp.moveaxis(k.reshape(B, KH, n_chunks, chunk, Dh), 2, 0)
    vc = jnp.moveaxis(v.reshape(B, KH, n_chunks, chunk, Dhv), 2, 0)
    q_pos = jnp.arange(Sq) + q_offset

    def body(carry, inp):
        m, l, acc = carry
        kci, vci, ci = inp
        s = jnp.einsum("bkgqd,bkcd->bkgqc", qg, kci,
                       preferred_element_type=jnp.float32) * scale
        kv_pos = ci * chunk + jnp.arange(chunk)
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]
            s = jnp.where(mask, s, -jnp.inf)
        if pad:
            s = jnp.where(kv_pos[None, :] < kv_len, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - jnp.where(jnp.isfinite(m_new), m_new, 0.0)[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m),
                         jnp.exp(m - jnp.where(jnp.isfinite(m_new), m_new, 0.0)),
                         0.0)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bkcd->bkgqd", p.astype(vci.dtype), vci,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KH, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KH, G, Sq, Dhv), jnp.float32)
    body = jax.checkpoint(body)  # flash backward: recompute p per chunk
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, Sq, Dhv).astype(q.dtype)


# ------------------------------------------------------- flash-decode (serve)
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, n_shards: int) -> jax.Array:
    """q: (B, H, 1, Dh); caches: (B, KH, L, Dh) with L sharded over 'model'
    as `n_shards` blocks. Per-shard partials + LSE combine (pure SPMD)."""
    B, H, _, Dh = q.shape
    KH, L = k_cache.shape[1], k_cache.shape[2]
    Dhv = v_cache.shape[-1]
    G = H // KH
    pad = (-L) % n_shards
    if pad:  # non-divisible cache length (e.g. whisper's 1500-frame cross kv):
        # zero-pad; padded positions sit beyond cache_len and are masked out
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        L = L + pad
    Lc = L // n_shards
    qg = q.reshape(B, KH, G, Dh)
    kb = shard(k_cache.reshape(B, KH, n_shards, Lc, Dh),
               "data", None, "model", None, None)
    vb = shard(v_cache.reshape(B, KH, n_shards, Lc, Dhv),
               "data", None, "model", None, None)
    scale = Dh ** -0.5
    s = jnp.einsum("bkgd,bknld->bkngl", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    pos = (jnp.arange(n_shards) * Lc)[:, None] + jnp.arange(Lc)[None, :]
    s = jnp.where(pos[None, None, :, None, :] < cache_len, s, -jnp.inf)
    m_i = s.max(-1)                                          # (B,KH,n,G)
    p = jnp.exp(s - m_i[..., None])
    p = jnp.where(jnp.isfinite(m_i)[..., None], p, 0.0)
    l_i = p.sum(-1)
    o_i = jnp.einsum("bkngl,bknld->bkngd", p.astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32)
    m_g = m_i.max(2, keepdims=True)
    w = jnp.exp(m_i - m_g)
    l_g = (l_i * w).sum(2)
    o_g = (o_i * w[..., None]).sum(2) / jnp.maximum(l_g, 1e-30)[..., None]
    return o_g.reshape(B, H, 1, Dhv).astype(q.dtype)


# ------------------------------------------------------------------ GQA block
def _project_q(p, x, cfg):
    q = jnp.einsum("bsd,dhq->bhsq", x, p["wq"])
    return q


def _kv_repeat(kv: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Compute-time kv-head replication (kv stays exact: repeated heads are
    tied copies). Makes KH_eff divisible by the TP axis."""
    if cfg.kv_repeat == 1:
        return kv
    return jnp.repeat(kv, cfg.kv_repeat, axis=1)


def attention_block(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                    causal: bool = True,
                    positions: Optional[jax.Array] = None,
                    kv_x: Optional[jax.Array] = None,
                    kv_positions: Optional[jax.Array] = None,
                    cache: Optional[Dict] = None,
                    cache_pos: Optional[jax.Array] = None,
                    use_rope: bool = True,
                    want_cache: bool = False,
                    cross: bool = False) -> Tuple[jax.Array, Optional[Dict]]:
    """Full attention over x (self) or kv_x (cross). With `cache` (arrays-only
    dict, scan-friendly), runs one decode step: x is (B, 1, D) and k/v are
    appended at `cache_pos`."""
    B, S, D = x.shape
    src = x if kv_x is None else kv_x
    if positions is None:
        positions = jnp.arange(S)
    q = _project_q(p, x, cfg)                                 # (B,H,S,Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
    q = shard(q, "data", "model", None, None)

    if cache is not None and cross:
        # cross-attention decode: kv precomputed at prefill
        out = decode_attention(q, cache["k"], cache["v"], cache_pos,
                               cfg.decode_seq_shards)
        new_cache = None
    elif cache is not None:
        # self-attention decode: append new kv, attend over the cache.
        # The cache stores the ORIGINAL kv heads (no kv_repeat): the repeat
        # only exists so training-time kv projections TP-shard; decode shards
        # the cache on the sequence dim, and GQA math needs only KH | H —
        # storing repeated heads would double cache bytes (§Perf decode).
        k_new = jnp.einsum("bsd,dhq->bhsq", x, p["wk"])
        v_new = jnp.einsum("bsd,dhq->bhsq", x, p["wv"])
        if cfg.qk_norm:
            k_new = rms_norm(k_new, p["k_norm"], cfg.norm_eps)
        if use_rope:
            k_new = apply_rope(k_new, positions, cfg.rope_theta)
        pos = cache_pos
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, 0, pos, 0))
        out = decode_attention(q, k_cache, v_cache, pos + S,
                               cfg.decode_seq_shards)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        k_raw = jnp.einsum("bsd,dhq->bhsq", src, p["wk"])
        v_raw = jnp.einsum("bsd,dhq->bhsq", src, p["wv"])
        k = _kv_repeat(k_raw, cfg)
        v = _kv_repeat(v_raw, cfg)
        if cfg.qk_norm:
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        if use_rope:
            kp = kv_positions if kv_positions is not None else jnp.arange(src.shape[1])
            k = apply_rope(k, kp, cfg.rope_theta)
        k = shard(k, "data", "model", None, None)
        v = shard(v, "data", "model", None, None)
        out = flash_attention(q, k, v, causal=causal and kv_x is None,
                              chunk=cfg.attn_chunk)
        new_cache = {"k": k_raw, "v": v_raw} if want_cache else None
    y = jnp.einsum("bhsq,hqd->bsd", out, p["wo"])
    return shard(y, "data", None, None), new_cache


# ------------------------------------------------------------------ MLA block
def mla_block(p: Dict, x: jax.Array, cfg: ModelConfig, *,
              positions: Optional[jax.Array] = None,
              cache: Optional[Dict] = None,
              cache_pos: Optional[jax.Array] = None,
              want_cache: bool = False) -> Tuple[jax.Array, Optional[Dict]]:
    """Multi-head Latent Attention (deepseek-v2). Cache stores the compressed
    c_kv (r) + rope key (rope_dim) per position — the whole point of MLA."""
    m = cfg.mla
    B, S, D = x.shape
    Dh, H = cfg.head_dim_, cfg.n_heads_padded
    if positions is None:
        positions = jnp.arange(S)
    qfull = jnp.einsum("bsd,dhq->bhsq", x, p["wq"])          # (B,H,S,Dh+rope)
    q_nope, q_rope = qfull[..., :Dh], qfull[..., Dh:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])           # (B,S,r+rope)
    c_kv = rms_norm(dkv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(dkv[..., None, m.kv_lora_rank:].swapaxes(1, 2),
                        positions, cfg.rope_theta)            # (B,1,S,rope)

    if cache is not None:
        pos = cache_pos
        ckv_c = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, pos, 0))
        krope_c = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope[:, 0].astype(cache["k_rope"].dtype), (0, pos, 0))
        # absorbed decode: q_nope' = q_nope @ w_uk  -> scores in latent space
        q_lat = jnp.einsum("bhsq,rhq->bhsr", q_nope, p["w_uk"])  # (B,H,1,r)
        q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)        # (B,H,1,r+rope)
        k_cat = jnp.concatenate([ckv_c, krope_c], axis=-1)[:, None]  # (B,1,L,r+rope)
        out_lat = decode_attention(q_cat, k_cat, ckv_c[:, None],
                                   pos + S, cfg.decode_seq_shards)  # (B,H,1,r)
        out = jnp.einsum("bhsr,rhq->bhsq", out_lat, p["w_uv"])
        new_cache = {"c_kv": ckv_c, "k_rope": krope_c}
    else:
        k_nope = jnp.einsum("bsr,rhq->bhsq", c_kv, p["w_uk"])
        v = jnp.einsum("bsr,rhq->bhsq", c_kv, p["w_uv"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope, (B, H, S, m.rope_head_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        q = shard(q, "data", "model", None, None)
        k = shard(k, "data", "model", None, None)
        out = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
        new_cache = ({"c_kv": c_kv, "k_rope": k_rope[:, 0]}
                     if want_cache else None)
    y = jnp.einsum("bhsq,hqd->bsd", out, p["wo"])
    return shard(y, "data", None, None), new_cache


def init_self_attn_cache(cfg: ModelConfig, batch: int, max_len: int,
                         stack: int = 0) -> Dict:
    """Abstract-friendly cache init (works under jax.eval_shape).
    Caches store original (unrepeated) kv heads — see attention_block."""
    Dh, KH = cfg.head_dim_, cfg.n_kv_heads
    dt = pdtype(cfg)
    shp = (batch, KH, max_len, Dh)
    if stack:
        shp = (stack,) + shp
    if cfg.mla is not None:
        m = cfg.mla
        base = (batch, max_len)
        if stack:
            base = (stack,) + base
        return {"c_kv": jnp.zeros(base + (m.kv_lora_rank,), dt),
                "k_rope": jnp.zeros(base + (m.rope_head_dim,), dt)}
    return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}
