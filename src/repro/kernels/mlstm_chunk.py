"""Chunkwise mLSTM as a Pallas TPU kernel.

Grid: (B*H, n_chunks); the chunk dimension is sequential and carries the
matrix-memory state (C, n, m) in VMEM scratch. Each chunk does three
MXU matmuls (intra-chunk scores, value combine, state outer-product) plus
cheap vector work on the cumulative gates — the TPU-friendly factorization of
xLSTM's recurrence (the per-step recurrent form is pure VPU and ~Dh x slower).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, h_ref,
                  C_ref, n_ref, m_ref, *, chunk: int):
    """Gates arrive as (1, L) rows; every per-step vector is kept 2-D, as a
    (1, L) row or an (L, 1) column, and the prefix sums and maxima are masked
    (L, L) reductions, so no 1-D value or scan reaches the TPU compiler. The
    chunk-level scalars (m0, F_L, m_L) are true scalars, m in SMEM."""
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[0, 0] = jnp.float32(-1e30)

    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    q = q_ref[0].astype(jnp.float32)          # (L, Dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    li_r = li_ref[...].astype(jnp.float32)    # (1, L)
    lf_r = lf_ref[...].astype(jnp.float32)
    m0 = m_ref[0, 0]

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = t_idx >= s_idx
    eye = t_idx == s_idx
    li_c = jnp.sum(jnp.where(eye, li_r, 0.0), axis=1, keepdims=True)     # row -> col
    F_c = jnp.sum(jnp.where(causal, lf_r, 0.0), axis=1, keepdims=True)   # inclusive
    F_r = jnp.sum(jnp.where(eye, F_c, 0.0), axis=0, keepdims=True)       # col -> row
    g_r = li_r - F_r
    run = jnp.maximum(m0, jnp.max(jnp.where(causal, g_r, -jnp.inf),
                                  axis=1, keepdims=True))
    m = F_c + run                             # (L, 1) stabilizer per step
    W = jnp.where(causal, jnp.exp((F_c - m) + g_r), 0.0)   # t rows, s cols
    scores = dot(q, k, (((1,), (1,)), ((), ()))) * W
    h_num = dot(scores, v, (((1,), (0,)), ((), ())))
    n_intra = dot(W, k, (((1,), (0,)), ((), ())))
    w_state = jnp.exp(F_c + m0 - m)           # (L, 1)
    h_num = h_num + w_state * dot(q, C_ref[...], (((1,), (0,)), ((), ())))
    n_t = n_intra + w_state * n_ref[...]
    den = jnp.abs(jnp.sum(q * n_t, axis=1, keepdims=True))
    h_ref[0] = (h_num / jnp.maximum(den, jnp.exp(-m))).astype(h_ref.dtype)

    F_L = jnp.sum(lf_r)
    m_L = F_L + jnp.maximum(m0, jnp.max(g_r))
    kw = k * jnp.exp((F_L - F_c) + li_c - m_L)                    # (L, Dh)
    # exp on a (1, Dh) row: the scalar unit has no exp, and a (1, 1) value
    # cannot broadcast over sublanes and lanes at once
    decay = jnp.exp(jnp.full((1, C_ref.shape[1]), F_L + m0 - m_L))
    C_ref[...] = decay * C_ref[...] + dot(kw, v, (((0,), (0,)), ((), ())))
    n_ref[...] = decay * n_ref[...] + jnp.sum(kw, axis=0, keepdims=True)
    m_ref[0, 0] = m_L


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                li: jax.Array, lf: jax.Array, *,
                chunk: int = DEFAULT_CHUNK,
                interpret: bool = False) -> jax.Array:
    """q,k,v: (B,H,S,Dh) (k pre-scaled); li,lf: (B,H,S). Returns h (B,H,S,Dh)."""
    B, H, S, Dh = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    qf = q.reshape(B * H, S, Dh)
    kf = k.reshape(B * H, S, Dh)
    vf = v.reshape(B * H, S, Dh)
    # one (1, chunk) row per grid step: the block's last two dims equal the
    # array's, which is what the TPU tiling rule asks of a sub-128 chunk
    lif = li.reshape(B * H, nc, 1, chunk)
    lff = lf.reshape(B * H, nc, 1, chunk)
    gate_spec = pl.BlockSpec((None, None, 1, chunk), lambda b, j: (b, j, 0, 0))

    out = pl.pallas_call(
        functools.partial(_mlstm_kernel, chunk=chunk),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, Dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, Dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, Dh), lambda b, j: (b, j, 0)),
            gate_spec,
            gate_spec,
        ],
        out_specs=pl.BlockSpec((1, chunk, Dh), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Dh, Dh), jnp.float32),   # C state
            pltpu.VMEM((1, Dh), jnp.float32),    # n state
            pltpu.SMEM((1, 1), jnp.float32),     # m stabilizer
        ],
        interpret=interpret,
    )(qf, kf, vf, lif, lff)
    return out.reshape(B, H, S, Dh)
