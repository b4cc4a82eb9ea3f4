"""Causal GQA flash attention as a Pallas TPU kernel.

TPU-native design (DESIGN.md §3/§7): the grid is (batch*q_heads, q_blocks,
kv_blocks); the kv_blocks dimension is sequential, carrying the online-softmax
state (m, l, acc) in VMEM scratch so score blocks never touch HBM — the
fix for the score-materialization memory-boundedness the dry-run shows for the
pure-XLA path. Block shapes are MXU-aligned (multiples of 128 on the block
dims); fp32 accumulation; GQA is handled in the kv index_map (q head h reads
kv head h // G), so kv blocks are reused across the q-head group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_kv_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def compute():
        q = q_ref[0].astype(jnp.float32)                     # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                     # (bk, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            # finite mask: a row whose every key in this block is masked gets
            # a finite m_new, and its bogus p = 1 terms are zeroed by the
            # correction exp(MASK - m) once the row meets its real keys
            s = jnp.where(q_pos >= k_pos, s, MASK_VALUE)
        m_prev = m_ref[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                       # 0 on first block
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    if causal:
        # skip fully-masked kv blocks (top-right of the causal band)
        pl.when(kj * block_k <= qi * block_q + block_q - 1)(compute)
    else:
        compute()

    @pl.when(kj == n_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, S, Dh); k, v: (B, KH, S, Dh); H = KH * G. Returns (B,H,S,Dh)."""
    B, H, S, Dh = q.shape
    KH = k.shape[1]
    G = H // KH
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k
    qf = q.reshape(B * H, S, Dh)
    kf = k.reshape(B * KH, S, Dh)
    vf = v.reshape(B * KH, S, Dh)

    kernel = functools.partial(
        _flash_kernel, scale=Dh ** -0.5, causal=causal,
        block_q=block_q, block_k=block_k, n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda b, i, j, G=G: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
        scratch_shapes=[               # VMEM state carried across kv steps
            pltpu.VMEM((block_q, 1), jnp.float32),    # m: running row max
            pltpu.VMEM((block_q, 1), jnp.float32),    # l: running row sum
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, Dh)
