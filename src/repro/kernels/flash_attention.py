"""Flash attention (Pallas TPU): causal / sliding-window / GQA.

Tiling: grid = (B*H, S/block_q, T/block_k); the key axis is the
sequential ("arbitrary") dimension so the online-softmax running state
(m, l, acc) lives in VMEM scratch across key tiles.  Blocks:

    q   (1, block_q, D)  VMEM     o (1, block_q, D) VMEM (written at last tile)
    k,v (1, block_k, D)  VMEM     scratch: acc (bq, D) f32, m/l (bq, 128) f32

MXU alignment: block_q/block_k default 128; D is the head dim (128/256
for the assigned archs).  Fully-masked key tiles are skipped via
``pl.when`` on scalar tile bounds — with causal masking this halves the
compute; with a sliding window only O(window/block_k) tiles run per row
(the sub-quadratic path used by gemma3/recurrentgemma).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_scr,
    m_scr,
    l_scr,
    *,
    sm_scale: float,
    causal: bool,
    window: int,
    logit_softcap: float,
    block_q: int,
    block_k: int,
    s_real: int,
    t_real: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q0 = iq * block_q
    k0 = ik * block_k
    offset = t_real - s_real  # right-aligned causality when T > S

    needed = k0 < t_real
    if causal:
        needed &= k0 <= q0 + offset + block_q - 1
    if window > 0:
        needed &= k0 + block_k - 1 > q0 + offset - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        s = s * sm_scale
        if logit_softcap > 0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + offset
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < t_real
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, KV, T, D)
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    logit_softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, s, d = q.shape
    _, kv, t, _ = k.shape
    if h % kv:
        raise ValueError(f"H={h} not a multiple of KV={kv}")
    g = h // kv

    block_q = min(block_q, max(s, 8))
    block_k = min(block_k, max(t, 8))
    s_pad = -(-s // block_q) * block_q
    t_pad = -(-t // block_k) * block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad - s), (0, 0))) if s_pad != s else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, t_pad - t), (0, 0))) if t_pad != t else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, t_pad - t), (0, 0))) if t_pad != t else v

    qf = qp.reshape(b * h, s_pad, d)
    kf = kp.reshape(b * kv, t_pad, d)
    vf = vp.reshape(b * kv, t_pad, d)

    grid = (b * h, s_pad // block_q, t_pad // block_k)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=1.0 / (d**0.5),
        causal=causal,
        window=window,
        logit_softcap=logit_softcap,
        block_q=block_q,
        block_k=block_k,
        s_real=s,
        t_real=t,
    )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik, g=g, kv=kv, h=h: (bh // h * kv + (bh % h) // g, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik, g=g, kv=kv, h=h: (bh // h * kv + (bh % h) // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf)

    return out.reshape(b, h, s_pad, d)[:, :, :s, :]
