"""Step builders: loss, train_step, prefill/serve steps, and sharding
resolution for states and batches.

Everything here is mesh-agnostic until ``*_shardings`` binds a Mesh via
the shard-if-divisible rules (``repro.nn.module``) — this is what lets a
single code path lower on 1 CPU device or a multi-chip mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.launch.mesh import dp_axis_names
from repro.nn.module import logical_to_pspec
from repro.optim.adamw import AdamW, apply_updates

PyTree = Any

MOE_AUX_WEIGHT = 0.01
MTP_WEIGHT = 0.3
Z_LOSS_WEIGHT = 1e-4
IGNORE_INDEX = -100


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, labels: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Mean CE over non-ignored positions + z-loss. logits fp32 (B,S,V)."""
    mask = (labels != IGNORE_INDEX).astype(jnp.float32)
    safe = jnp.where(labels == IGNORE_INDEX, 0, labels)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = (logz - gold) * mask
    n = jnp.maximum(mask.sum(), 1.0)
    loss = ce.sum() / n
    zloss = Z_LOSS_WEIGHT * ((logz * mask) ** 2).sum() / n
    return loss + zloss, loss


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def make_loss_fn(model, cfg: ArchConfig) -> Callable:
    def loss_fn(params: PyTree, batch: dict) -> tuple[jax.Array, dict]:
        if cfg.encdec is not None:
            logits, aux = model.train_logits(params, batch["frames"], batch["inputs"])
        elif cfg.vlm is not None:
            logits, aux = model.train_logits(params, batch["inputs"], batch["patches"])
        elif cfg.mtp:
            hidden, aux = model.train_hidden(params, batch["inputs"])
            from repro.nn import layers as L  # local to avoid cycle

            x = L.norm_apply(params["final_norm"], hidden, cfg)
            logits = L.logits_apply(params["embed"], params.get("head"), x, cfg)
        else:
            logits, aux = model.train_logits(params, batch["inputs"])

        total, ce = cross_entropy(logits, batch["labels"])
        metrics = {"ce": ce}
        if cfg.moe is not None:
            total = total + MOE_AUX_WEIGHT * aux
            metrics["moe_aux"] = aux
        if cfg.mtp and cfg.encdec is None and cfg.vlm is None:
            # Predict t+2: inputs shifted by one feed the MTP head.
            mtp_logits = model.mtp_logits(params, batch["inputs"][:, 1:], hidden[:, :-1])
            mtp_total, mtp_ce = cross_entropy(mtp_logits, batch["labels"][:, 1:])
            total = total + MTP_WEIGHT * mtp_total
            metrics["mtp_ce"] = mtp_ce
        return total, metrics

    return loss_fn


def make_train_step(model, cfg: ArchConfig, optimizer: AdamW, accum_steps: int = 1) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    ``accum_steps > 1`` scans over microbatches, accumulating grads in
    fp32 — the standard way to hold the global batch while bounding
    activation memory (and a §Perf lever).
    """
    loss_fn = make_loss_fn(model, cfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        params = state["params"]
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:]), batch
            )

            def body(acc, mb):
                (loss, metrics), grads = grad_fn(params, mb)
                acc_g, acc_l = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32) / accum_steps, acc_g, grads
                )
                return (acc_g, acc_l + loss / accum_steps), metrics

            zero_g = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), mstack = jax.lax.scan(body, (zero_g, 0.0), micro)
            metrics = jax.tree_util.tree_map(lambda m: m[-1], mstack)

        updates, opt_state, opt_metrics = optimizer.update(grads, state["opt"], params)
        new_params = apply_updates(params, updates)
        new_state = {"params": new_params, "opt": opt_state, "step": state["step"] + 1}
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return new_state, metrics

    return train_step


def init_state(model, cfg: ArchConfig, optimizer: AdamW, rng: jax.Array, abstract: bool = False):
    """(state, axes) — axes only covers params; opt m/v share them."""
    from repro.nn.module import init_with_axes

    params, axes = init_with_axes(model.init, rng, abstract=abstract, dtype=jnp.dtype(cfg.param_dtype))
    if abstract:
        opt = jax.eval_shape(optimizer.init, params)
        step = jax.ShapeDtypeStruct((), jnp.int32)
    else:
        opt = optimizer.init(params)
        step = jnp.zeros((), jnp.int32)
    return {"params": params, "opt": opt, "step": step}, axes


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------


def make_prefill_step(model, cfg: ArchConfig) -> Callable:
    if cfg.encdec is not None:
        def prefill_step(params, batch, caches):
            logits, caches = model.prefill(params, batch["frames"], batch["inputs"], caches)
            return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), caches
    elif cfg.vlm is not None:
        def prefill_step(params, batch, caches):
            logits, caches = model.prefill(params, batch["inputs"], caches, patches=batch["patches"])
            return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), caches
    else:
        def prefill_step(params, batch, caches):
            logits, caches = model.prefill(params, batch["inputs"], caches)
            return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), caches

    return prefill_step


def make_serve_step(model, cfg: ArchConfig) -> Callable:
    def serve_step(params, token: jax.Array, caches) -> tuple[jax.Array, PyTree]:
        logits, caches = model.decode_step(params, token, caches)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], caches

    return serve_step


# ---------------------------------------------------------------------------
# Tiered (two-level KV) serving — DESIGN.md §2a
# ---------------------------------------------------------------------------


def make_tiered_caches(
    model, cfg: ArchConfig, batch: int, max_len: int, window: int, page: int | None,
    dtype=jnp.bfloat16, store=None, store_prefix: str = "serving/kv", pages=None,
) -> dict:
    """Caches for the two-level serving backend: every full-attention GQA
    layer gets a ``TieredKVCache`` (device hot ring + paged host cold tier);
    windowed/recurrent/MLA layers keep their standard O(window)/O(1) caches.

    ``store`` (a :class:`~repro.core.store.TwoLevelStore`, e.g. one host
    shard of a :class:`~repro.core.dstore.DistributedStore`) adds the
    third level: completed cold pages persist under
    ``<store_prefix>/prefix_<i>/`` so KV history survives host DRAM loss
    (``restore_cold_from_store``).  ``pages`` (a
    :class:`~repro.serving.SharedPageRegistry`) routes completed pages
    through the content-addressed refcounted table instead, so sessions
    sharing a prompt prefix store each shared page once.

    Requires an unrolled stack (``cfg.scan_layers=False``) — the cold tier
    is host state, which cannot ride a ``lax.scan`` carry.
    """
    from repro.models.lm import make_layer_cache  # local to avoid cycle
    from repro.serving import TieredKVCache

    if model.n_periods:
        raise ValueError("tiered serving needs an unrolled stack (cfg.scan_layers=False)")
    hd = cfg.resolved_head_dim
    caches: dict[str, Any] = {}
    for i, spec in enumerate(model.prefix):
        if spec.mixer == "gqa" and spec.window == 0:
            caches[f"prefix_{i}"] = TieredKVCache(
                batch, cfg.n_kv_heads, hd, window=window, max_len=max_len,
                dtype=dtype, page=page,
                store=store, store_prefix=store_prefix, name=f"prefix_{i}",
                pages=pages,
            )
        else:
            caches[f"prefix_{i}"] = make_layer_cache(spec, cfg, batch, max_len, dtype)
    return caches


def tiered_serve_loop(
    model,
    cfg: ArchConfig,
    params: PyTree,
    prompts: jax.Array,  # (B, S) int32
    tokens: int,
    window: int,
    page: int | None = None,
    dtype=jnp.bfloat16,
    store=None,
    store_prefix: str = "serving/kv",
) -> tuple[jax.Array, float, float, dict]:
    """Batched prefill + greedy decode routed through the two-level KV
    cache.  Runs eagerly (the cold tier is host memory; pages are staged
    to device between steps).  Returns (generated, prefill_s, decode_s,
    caches) — read per-layer ``TieredKVStats`` off the caches.
    """
    import time

    batch, prompt_len = prompts.shape
    max_len = prompt_len + tokens + 1
    caches = make_tiered_caches(
        model, cfg, batch, max_len, window, page, dtype,
        store=store, store_prefix=store_prefix,
    )

    t0 = time.perf_counter()
    logits, caches = model.prefill(params, prompts, caches)
    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    jax.block_until_ready(tok)
    prefill_s = time.perf_counter() - t0

    out = [tok]
    t0 = time.perf_counter()
    for _ in range(tokens):
        logits, caches = model.decode_step(params, tok, caches)
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    decode_s = time.perf_counter() - t0
    return jnp.concatenate(out, axis=1), prefill_s, decode_s, caches


def tiered_cache_stats(caches: dict) -> dict:
    """Aggregate ``TieredKVStats`` across the tiered layers of a cache dict
    (hot fraction, staged H2D bytes, write-through flushes)."""
    from repro.serving import TieredKVCache

    tiered = [c for c in caches.values() if isinstance(c, TieredKVCache)]
    if not tiered:
        return {"layers": 0}
    return {
        "layers": len(tiered),
        "length": tiered[0].length,
        "window": tiered[0].window,
        "page": tiered[0].page,
        "hot_fraction": sum(c.stats.hot_fraction() for c in tiered) / len(tiered),
        "bytes_staged": sum(c.stats.bytes_staged for c in tiered),
        "pages_staged": sum(c.stats.pages_staged for c in tiered),
        "bytes_written_through": sum(c.stats.bytes_written_through for c in tiered),
        "d2h_flushes": sum(c.stats.d2h_flushes for c in tiered),
        "hot_device_bytes": sum(c.hot_device_bytes() for c in tiered),
        "host_bytes": sum(c.host_bytes() for c in tiered),
    }


# ---------------------------------------------------------------------------
# Sharding resolution
# ---------------------------------------------------------------------------


def _shard_if(dim: int, axes: tuple[str, ...], mesh: Mesh):
    import numpy as np

    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if size > 1 and dim % size == 0:
        return axes if len(axes) > 1 else axes[0]
    return None


def state_shardings(state_shapes: PyTree, axes: PyTree, mesh: Mesh, rules=None) -> PyTree:
    """NamedShardings for {params, opt, step} from the params axes tree."""
    pspecs = logical_to_pspec(axes, state_shapes["params"], mesh, rules)
    ns = lambda spec: NamedSharding(mesh, spec)
    params_sh = jax.tree_util.tree_map(ns, pspecs, is_leaf=lambda x: isinstance(x, P))
    return {
        "params": params_sh,
        "opt": {
            "m": params_sh,
            "v": params_sh,
            "count": NamedSharding(mesh, P()),
        },
        "step": NamedSharding(mesh, P()),
    }


def batch_shardings(batch_shapes: PyTree, mesh: Mesh) -> PyTree:
    """Shard the leading batch dim over (pod, data); replicate the rest."""
    dp = dp_axis_names(mesh)

    def one(leaf):
        lead = _shard_if(leaf.shape[0], dp, mesh)
        return NamedSharding(mesh, P(lead, *([None] * (len(leaf.shape) - 1))))

    return jax.tree_util.tree_map(one, batch_shapes)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
