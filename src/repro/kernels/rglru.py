"""Blocked gated-linear-recurrence kernel (RG-LRU core, Pallas TPU).

Computes ``h_t = a_t * h_{t-1} + x_t`` over the sequence axis.

Tiling: grid = (B, W/block_w, S/block_s); the sequence axis is the
sequential grid dimension, carrying ``h`` in VMEM scratch between tiles.
Within a (block_s, block_w) tile the recurrence closes in log2(block_s)
Hillis-Steele passes — each pass is a full-width vector op, so the MXU/VPU
stays busy instead of serializing one timestep at a time; the carry-in
folds as ``h_t += A_cum_t * h0``.

This is the HBM-bandwidth-bound op of the hybrid archs: the roofline
memory term is ~3 streams (a, x, h) x S x W bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, x_ref, o_ref, h_scr, *, block_s: int):
    is_ = pl.program_id(2)

    @pl.when(is_ == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)  # (bs, bw)
    x = x_ref[0].astype(jnp.float32)

    # Hillis-Steele inclusive scan of the affine maps (a, x):
    #   (a2, x2) o (a1, x1) = (a1*a2, a2*x1 + x2)
    acc_a, acc_x = a, x
    shift = 1
    while shift < block_s:
        a_sh = jnp.pad(acc_a, ((shift, 0), (0, 0)), constant_values=1.0)[:block_s]
        x_sh = jnp.pad(acc_x, ((shift, 0), (0, 0)), constant_values=0.0)[:block_s]
        acc_x = acc_x + acc_a * x_sh
        acc_a = acc_a * a_sh
        shift *= 2

    h0 = h_scr[0]  # (bw,) carry from previous sequence tile
    h_all = acc_x + acc_a * h0[None, :]
    o_ref[0] = h_all.astype(o_ref.dtype)
    h_scr[...] = jnp.broadcast_to(h_all[-1], h_scr.shape)


def rglru_scan_fwd(
    a: jax.Array,  # (B, S, W) decay in (0,1]
    x: jax.Array,  # (B, S, W) gated input
    block_s: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, s, w = a.shape
    block_s = min(block_s, s)
    block_w = min(block_w, w)
    if s % block_s or w % block_w:
        s_pad = -(-s // block_s) * block_s
        w_pad = -(-w // block_w) * block_w
        # pad a with 1s would corrupt carry; pad with 0 decay + 0 input: the
        # padded steps write h=0 but only padded rows read them -> safe, and
        # padded width lanes are sliced off.
        a = jnp.pad(a, ((0, 0), (0, s_pad - s), (0, w_pad - w)))
        x = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, w_pad - w)))
        s2, w2 = s_pad, w_pad
    else:
        s2, w2 = s, w

    grid = (b, w2 // block_w, s2 // block_s)
    out = pl.pallas_call(
        functools.partial(_rglru_kernel, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda ib, iw, is_: (ib, is_, iw)),
            pl.BlockSpec((1, block_s, block_w), lambda ib, iw, is_: (ib, is_, iw)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w), lambda ib, iw, is_: (ib, is_, iw)),
        out_shape=jax.ShapeDtypeStruct((b, s2, w2), x.dtype),
        scratch_shapes=[pltpu.VMEM((8, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, x)
    return out[:, :s, :w]
