"""Two-level decode attention (Pallas TPU) — the paper's tiered read path
materialized at the VMEM/HBM level (DESIGN.md §2, row L3).

Decode attention is memory-bound: every step streams the whole KV cache
through the chip.  The paper's insight — put a small fast tier in front
of the big slow tier and blend reads (Eq. 7) — maps onto TPU decode as:

    hot tier  = the last ``W`` tokens' KV, kept VMEM-resident across the
                whole kernel (BlockSpec index constant in the streaming
                axis -> fetched once, like Tachyon's RAM blocks);
    cold tier = the paged history, streamed tile-by-tile from HBM
                (the OrangeFS analogue).

The kernel is **ring-aware** and **length-dynamic**:

* The hot tier is consumed as the raw ring buffer — no caller-side
  chronological gather.  Decode softmax is permutation-invariant over
  valid keys, so the ring rotation reduces to position arithmetic: slot
  ``j`` has age ``(newest_slot - j) mod W`` and is valid iff
  ``age < hot_len``.  A caller with a plain chronological buffer passes
  ``newest_slot = hot_len - 1`` and gets the old ``j < hot_len`` mask.
* ``hot_len`` / ``cold_len`` / ``newest_slot`` arrive via scalar
  prefetch (SMEM), not as trace-time constants — one compiled kernel
  serves every decode step instead of retracing as the history grows.
* The cold tier is a paged buffer whose capacity is a ``block_k``
  multiple; the trailing partial page is masked by ``cold_len``.  The
  caller never ``jnp.pad``s the history per call — blocks past
  ``cold_len`` are skipped via ``pl.when`` on the prefetched scalar.

The kernel merges both tiers with one online softmax.  The effective
read time follows the paper's harmonic model with
``f = hot_len / (hot_len + cold_len)`` and rates (VMEM bw, HBM bw) — the
benchmark in ``benchmarks/fig5_crossover.py`` reuses Eq. 7 with TPU
constants for exactly this kernel.

Layout: q (B, H, 1, D) — a decode step; cold (B, KV, C, D) HBM-streamed
paged capacity buffer; hot (B, KV, W, D) VMEM-pinned ring.  Key order is
[cold ; hot] (softmax-order irrelevant, kept for the docs' mental model).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
SUBLANES = 8


def _tiered_kernel(
    lens_ref,  # SMEM (3,): [hot_len, cold_len, newest_slot]
    q_ref,
    hot_k_ref,
    hot_v_ref,
    cold_k_ref,
    cold_v_ref,
    o_ref,
    acc_scr,
    m_scr,
    l_scr,
    *,
    sm_scale: float,
    block_k: int,
    w_max: int,
):
    ik = pl.program_id(1)
    n_k = pl.num_programs(1)
    hot_len = lens_ref[0]
    cold_len = lens_ref[1]
    newest = lens_ref[2]

    q = q_ref[0].astype(jnp.float32)  # (SUBLANES, D) row-broadcast query

    @pl.when(ik == 0)
    def _hot():
        # Fast tier first — the paper's 'nearest available copy' priority.
        hk = hot_k_ref[0].astype(jnp.float32)  # (W, D)
        hv = hot_v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, hk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = s * sm_scale  # (SUBLANES, W)
        # Ring validity by age: slot j holds the (newest - j mod W)-th most
        # recent token; the shift keeps the rem argument non-negative.
        slot = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, w_max), 1)
        age = jax.lax.rem(newest - slot + w_max, w_max)
        s = jnp.where(age < hot_len, s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        p = jnp.where(age < hot_len, p, 0.0)  # exact zero when fully masked
        acc_scr[...] = jax.lax.dot_general(
            p, hv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        l_scr[...] = jnp.broadcast_to(jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m, m_scr.shape)

    k0 = ik * block_k

    @pl.when(k0 < cold_len)
    def _cold():
        ck = cold_k_ref[0].astype(jnp.float32)  # (bk, D)
        cv = cold_v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, ck, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = s * sm_scale
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, block_k), 1)
        s = jnp.where(kpos < cold_len, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(kpos < cold_len, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True), l_scr.shape
        )
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, cv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


def tiered_decode_attention_fwd(
    q: jax.Array,  # (B, H, 1, D)
    hot_k: jax.Array,  # (B, KV, W, D) fast tier (ring buffer of recent keys)
    hot_v: jax.Array,
    cold_k: jax.Array,  # (B, KV, C, D) cold tier paged capacity buffer
    cold_v: jax.Array,
    lens: jax.Array,  # (3,) int32: [hot_len, cold_len, newest_slot]
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, h, one, d = q.shape
    _, kv, w_max, _ = hot_k.shape
    t = cold_k.shape[2]
    g = h // kv
    block_k = min(block_k, t)
    if t % block_k:
        # Fallback for ad-hoc callers; the paged serving cache always hands
        # over a block-multiple capacity buffer, so serving never pads.
        pad = -(-t // block_k) * block_k - t
        cold_k = jnp.pad(cold_k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cold_v = jnp.pad(cold_v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        t = cold_k.shape[2]

    # Broadcast the single query row across sublanes for layout friendliness.
    qf = jnp.broadcast_to(q.reshape(b * h, 1, d), (b * h, SUBLANES, d))

    grid = (b * h, t // block_k)
    kvmap = lambda bh, ik, lens, kv=kv, h=h, g=g: (bh // h * kv + (bh % h) // g, 0, 0)
    kvmap_cold = lambda bh, ik, lens, kv=kv, h=h, g=g: (bh // h * kv + (bh % h) // g, ik, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, SUBLANES, d), lambda bh, ik, lens: (bh, 0, 0)),
            # hot tier: block index constant across the streaming axis ->
            # fetched into VMEM once per (b, h) program (the fast tier).
            pl.BlockSpec((1, w_max, d), kvmap),
            pl.BlockSpec((1, w_max, d), kvmap),
            pl.BlockSpec((1, block_k, d), kvmap_cold),
            pl.BlockSpec((1, block_k, d), kvmap_cold),
        ],
        out_specs=pl.BlockSpec((1, SUBLANES, d), lambda bh, ik, lens: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, d), jnp.float32),
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _tiered_kernel,
            sm_scale=1.0 / (d**0.5),
            block_k=block_k,
            w_max=w_max,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, SUBLANES, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens.astype(jnp.int32), qf,
      hot_k.reshape(b * kv, w_max, d), hot_v.reshape(b * kv, w_max, d),
      cold_k.reshape(b * kv, t, d), cold_v.reshape(b * kv, t, d))

    return out[:, :1, :].reshape(b, h, 1, d)
