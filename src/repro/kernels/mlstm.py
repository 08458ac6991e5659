"""Chunkwise-parallel mLSTM kernel (Pallas TPU).

The mLSTM matrix memory ``C_t = f_t C_{t-1} + i_t v_t k_t^T`` is a linear
recurrence over (D, D) states with exponential gating and a max
stabilizer ``m``.  Sequential scan is VPU-serial; the chunkwise form
closes a chunk of ``C`` timesteps with dense (C,C)/(C,D) matmuls and
carries only (C_mat, n, m) between chunks — MXU-friendly, the same trick
flash attention plays with online softmax.

Grid = (B*H, S/chunk), sequential over chunks; carries live in VMEM
scratch: C_mat (D, D) f32, n (8, D) f32 (row-broadcast), m (8, 128) f32.

Stabilized chunk math (l <= j within the chunk; b = cumsum(f_log)):

    w_jl      = b_j - b_l + g_l
    m_intra_j = max_l w_jl ;  m_inter_j = m_prev + b_j
    m_j       = max(m_intra_j, m_inter_j)
    num_j     = e^{m_inter_j - m_j} (C_prev q_j)
                + sum_l e^{w_jl - m_j} (k_l . q_j) v_l
    n_j       = e^{m_inter_j - m_j} n_prev + sum_l e^{w_jl - m_j} k_l
    h_j       = num_j / max(|n_j . q_j|, 1)

Chunk-end carry uses the same formulas at j = C with stabilizer
``m_next = max(m_prev + b_C, max_l (b_C - b_l + g_l))``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, g_ref, f_ref, o_ref, cmat_scr, n_scr, m_scr, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        cmat_scr[...] = jnp.zeros_like(cmat_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    q = q_ref[0].astype(jnp.float32)  # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    g = g_ref[0, :, 0].astype(jnp.float32)  # (C,) log input gate
    f = f_ref[0, :, 0].astype(jnp.float32)  # (C,) log forget gate

    b = jnp.cumsum(f)  # (C,)
    m_prev = m_scr[0, 0]
    c_prev = cmat_scr[...]
    n_prev = n_scr[0]

    # intra-chunk decay matrix
    w = b[:, None] - b[None, :] + g[None, :]  # (C, C)
    ltri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) <= jax.lax.broadcasted_iota(
        jnp.int32, (chunk, chunk), 0
    )
    w = jnp.where(ltri, w, NEG_INF)
    m_intra = jnp.max(w, axis=1)  # (C,)
    m_inter = m_prev + b
    m_j = jnp.maximum(m_intra, m_inter)

    d_mat = jnp.exp(w - m_j[:, None])  # (C, C) masked decays
    inter_scale = jnp.exp(jnp.clip(m_inter - m_j, None, 0.0))  # (C,)
    # m_prev == -inf (first chunk): inter contribution is exactly zero
    inter_scale = jnp.where(jnp.isinf(m_prev), 0.0, inter_scale)

    s_qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    # cmat layout is (Dk, Dv) — contract q's key dim against cmat dim 0.
    num = inter_scale[:, None] * jax.lax.dot_general(
        q, c_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        s_qk * d_mat, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    n_j = inter_scale[:, None] * n_prev[None, :] + jax.lax.dot_general(
        d_mat, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    denom = jnp.maximum(jnp.abs(jnp.sum(n_j * q, axis=1)), 1.0)
    o_ref[0] = (num / denom[:, None]).astype(o_ref.dtype)

    # ---- chunk-end carry ----
    btot = b[-1]
    wc = btot - b + g  # (C,)
    m_next = jnp.maximum(jnp.where(jnp.isinf(m_prev), NEG_INF, m_prev + btot), jnp.max(wc))
    carry_scale = jnp.where(jnp.isinf(m_prev), 0.0, jnp.exp(m_prev + btot - m_next))
    kw = jnp.exp(wc - m_next)[:, None] * k  # (C, D) weighted keys
    cmat_scr[...] = carry_scale * c_prev + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    n_new = carry_scale * n_prev + jnp.sum(kw, axis=0)
    n_scr[...] = jnp.broadcast_to(n_new, n_scr.shape)
    m_scr[...] = jnp.full_like(m_scr, m_next)


def mlstm_chunkwise_fwd(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,
    v: jax.Array,
    i_pre: jax.Array,  # (B, H, S) log input gate pre-activation
    f_log: jax.Array,  # (B, H, S) log-sigmoid forget gate
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, s, d = q.shape
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"S={s} must be a multiple of chunk={chunk}")
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)
    # gates as (BH, S, 1) so BlockSpec stays rank-3
    gf = i_pre.reshape(bh, s, 1)
    ff = f_log.reshape(bh, s, 1)

    grid = (bh, s // chunk)
    out = pl.pallas_call(
        functools.partial(_mlstm_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, d), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, d), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda ib, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), lambda ib, ic: (ib, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),
            pltpu.VMEM((8, d), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, gf, ff)
    return out.reshape(b, h, s, d)
