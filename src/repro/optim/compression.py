"""Top-k gradient compression with error feedback (distributed-opt trick).

At 1000+ nodes the DP all-reduce of dense grads dominates the collective
term for small models; top-k sparsification with an error-feedback (EF)
residual keeps convergence while shrinking the payload ~``1/ratio``.

Integration point: on a real multi-host mesh this wraps the per-bucket
``psum`` inside a ``shard_map`` (sparse indices+values all-gather).  The
transform itself is jit-compatible; correctness (EF accumulation ->
unbiased long-run updates) is property-tested in
``tests/test_compression.py``, and the collective-byte saving is entered
as a modeled term in DESIGN.md §13 alongside the block-codec accounting.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any


def _topk_mask(x: jax.Array, k: int) -> jax.Array:
    flat = jnp.abs(x.reshape(-1))
    if k >= flat.size:
        return jnp.ones_like(x, bool)
    thresh = jax.lax.top_k(flat, k)[0][-1]
    return (jnp.abs(x) >= thresh) & (jnp.abs(x) > 0)


def topk_compress_with_ef(
    grads: PyTree,
    ef_state: PyTree | None,
    ratio: float = 0.01,
) -> tuple[PyTree, PyTree, dict]:
    """Sparsify grads to the top ``ratio`` fraction per leaf, with EF.

    Returns (sparse_grads, new_ef_state, stats).  ``sparse_grads`` has the
    same (dense) structure but is zero outside the mask — the sparse
    payload for a real wire format is (indices, values) of the mask.
    """
    if ef_state is None:
        ef_state = jax.tree_util.tree_map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def one(g, e):
        acc = g.astype(jnp.float32) + e
        k = max(1, int(acc.size * ratio))
        mask = _topk_mask(acc, k)
        sent = jnp.where(mask, acc, 0.0)
        residual = acc - sent
        return sent.astype(g.dtype), residual

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(ef_state)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    sparse = treedef.unflatten([o[0] for o in outs])
    new_ef = treedef.unflatten([o[1] for o in outs])
    total = sum(g.size for g in flat_g)
    sent = sum(max(1, int(g.size * ratio)) for g in flat_g)
    stats = {"ratio": sent / max(total, 1), "elements_sent": sent, "elements_total": total}
    return sparse, new_ef, stats
