"""Param scopes with logical sharding axes — the framework's module system.

No flax dependency: a ``Scope`` threads an RNG key through ``init``
functions and records, for every parameter, a tuple of **logical axis
names** (``("embed", "ff")`` etc.).  One init pass yields two parallel
pytrees — params and axes — from a single source of truth.

Logical axes resolve to mesh ``PartitionSpec``s through a rules table
(``DEFAULT_RULES``) with a **shard-if-divisible** guard: a dim whose size
does not divide its mesh axis is replicated instead (required for e.g.
InternVL's 14 heads on a 16-way model axis).  This guarantee is what makes
every (arch × mesh) combination lower and compile.

``init_with_axes(fn, key, ...)`` runs an init function under
``jax.eval_shape`` when abstract=True, so 671 B-parameter models cost no
memory to describe.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

PyTree = Any

# logical axis -> mesh axis (None = replicate). The "data" axes appear only
# on activations, never on params.
DEFAULT_RULES: dict[str, str | None] = {
    "vocab": "model",
    "embed": None,
    "ff": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "experts": "model",
    "dispatch": ("pod", "data"),  # MoE group-local dispatch (one group/DP shard)
    # expert_ff ALSO maps to model: resolve_axes claims each mesh axis once
    # per tensor, so when the expert axis shards (deepseek, 256%16==0) the
    # ff dim replicates, and when it cannot (grok, 8%16!=0) the ff dim
    # shards instead of replicating the whole expert stack on every chip
    # (§Perf iteration 1: 16x compute-term reduction on grok train_4k).
    "expert_ff": "model",
    "q_lora": None,
    "kv_lora": None,
    "rnn": "model",
    "conv": None,
    "batch": ("pod", "data"),
    "seq": None,
    "residual_seq": "model",  # sequence-parallel residual stream
    "act_embed": None,
    "act_heads": "model",
    "act_ff": "model",
    "cache_seq": None,
    "layers": None,
    "scalar": None,
}

# FSDP/ZeRO-style variant: weight d_model dims additionally shard over the
# data axis (2D "hybrid" sharding). XLA all-gathers weight shards per layer
# (FSDP) instead of all-reducing activations per block — a large win for
# dense TP-bound cells (§Perf iteration 7).
FSDP_RULES: dict[str, str | None] = dict(DEFAULT_RULES, embed="data")

RULE_SETS: dict[str, dict[str, str | None]] = {
    "default": DEFAULT_RULES,
    "fsdp": FSDP_RULES,
}


class Scope:
    """Threads RNG + path through init; collects params and logical axes."""

    def __init__(self, key: jax.Array, path: str = "", store: dict | None = None, axes: dict | None = None, dtype=jnp.float32):
        self._key = key
        self._path = path
        self._dtype = dtype
        self.params: dict = store if store is not None else {}
        self.axes: dict = axes if axes is not None else {}

    def child(self, name: str) -> "Scope":
        self._key, sub = jax.random.split(self._key)
        self.params.setdefault(name, {})
        self.axes.setdefault(name, {})
        return Scope(sub, f"{self._path}/{name}", self.params[name], self.axes[name], self._dtype)

    def next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def param(
        self,
        name: str,
        shape: tuple[int, ...],
        axes: tuple[str | None, ...],
        init: str = "normal",
        scale: float | None = None,
        dtype=None,
    ) -> jax.Array:
        if len(shape) != len(axes):
            raise ValueError(f"{self._path}/{name}: shape {shape} vs axes {axes} length mismatch")
        if name in self.params:
            raise ValueError(f"duplicate param {self._path}/{name}")
        dtype = dtype or self._dtype
        key = self.next_key()
        if init == "normal":
            s = scale if scale is not None else 0.02
            val = jax.random.normal(key, shape, dtype) * jnp.asarray(s, dtype)
        elif init == "fan_in":
            fan_in = shape[0] if len(shape) >= 1 else 1
            s = scale if scale is not None else 1.0
            val = jax.random.normal(key, shape, dtype) * jnp.asarray(s / math.sqrt(max(fan_in, 1)), dtype)
        elif init == "zeros":
            val = jnp.zeros(shape, dtype)
        elif init == "ones":
            val = jnp.ones(shape, dtype)
        elif init == "uniform":
            s = scale if scale is not None else 1.0
            val = jax.random.uniform(key, shape, dtype, -s, s)
        else:
            raise ValueError(f"unknown init {init!r}")
        self.params[name] = val
        self.axes[name] = tuple(axes)
        return val


def init_with_axes(
    init_fn: Callable[[Scope], None],
    key: jax.Array,
    abstract: bool = False,
    dtype=jnp.float32,
) -> tuple[PyTree, PyTree]:
    """Run ``init_fn`` under a fresh Scope; return (params, axes) trees.

    ``abstract=True`` runs under ``jax.eval_shape`` — no memory is
    allocated; params come back as ShapeDtypeStructs (compile-only path).
    """
    axes_box: dict = {}

    def run(k):
        scope = Scope(k, dtype=dtype)
        init_fn(scope)
        axes_box.clear()
        axes_box.update(scope.axes)
        return scope.params

    if abstract:
        params = jax.eval_shape(run, key)
    else:
        params = jax.jit(run)(key)
    return params, axes_box


def stacked_init(scope: Scope, name: str, n: int, init_fn: Callable[[Scope], None]) -> None:
    """Initialize ``n`` copies of a subtree with leading dim ``n`` per leaf.

    The substrate for scan-over-layers: the stacked params feed
    ``jax.lax.scan``, keeping HLO size and compile time O(1) in depth
    (61-layer compiles would be intractable unrolled).  Axes gain a
    leading "layers" logical axis (never sharded by DEFAULT_RULES).
    """
    keys = jax.random.split(scope.next_key(), n)
    probe = Scope(keys[0], dtype=scope._dtype)
    init_fn(probe)
    axes = jax.tree_util.tree_map(
        lambda a: ("layers", *a), probe.axes, is_leaf=lambda x: isinstance(x, tuple)
    )

    def one(k):
        s = Scope(k, dtype=scope._dtype)
        init_fn(s)
        return s.params

    scope.params[name] = jax.vmap(one)(keys)
    scope.axes[name] = axes


# ---------------------------------------------------------------------------
# Logical axes -> PartitionSpec resolution
# ---------------------------------------------------------------------------


def _mesh_axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def resolve_axes(
    logical: tuple[str | None, ...],
    shape: tuple[int, ...],
    mesh: Mesh,
    rules: dict[str, str | None] | None = None,
) -> PartitionSpec:
    """Logical axes -> PartitionSpec with the shard-if-divisible guard."""
    rules = rules or DEFAULT_RULES
    spec: list = []
    used: set = set()
    for dim, name in zip(shape, logical):
        mesh_axis = rules.get(name) if name is not None else None
        if mesh_axis is None:
            spec.append(None)
            continue
        flat = tuple(mesh_axis) if isinstance(mesh_axis, (tuple, list)) else (mesh_axis,)
        flat = tuple(a for a in flat if a in mesh.shape)
        if not flat or any(a in used for a in flat):
            spec.append(None)
            continue
        size = int(np.prod([mesh.shape[a] for a in flat]))
        if size <= 1 or dim % size != 0:
            spec.append(None)  # shard-if-divisible: replicate instead
            continue
        used.update(flat)
        spec.append(flat[0] if len(flat) == 1 else flat)
    return PartitionSpec(*spec)


def logical_to_pspec(axes_tree: PyTree, shapes_tree: PyTree, mesh: Mesh, rules=None) -> PyTree:
    """Map the (axes, shapes) trees to a PartitionSpec tree."""

    def leaf(axes, shaped):
        return resolve_axes(tuple(axes), tuple(shaped.shape), mesh, rules)

    return jax.tree_util.tree_map(
        leaf, axes_tree, shapes_tree, is_leaf=lambda x: isinstance(x, tuple)
    )


def named_shardings(axes_tree: PyTree, shapes_tree: PyTree, mesh: Mesh, rules=None) -> PyTree:
    specs = logical_to_pspec(axes_tree, shapes_tree, mesh, rules)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )


# Explicit context for activation constraints: `with mesh:` alone does not
# expose an abstract mesh to traced code in this JAX version, so launch code
# wraps tracing in `axis_rules(mesh)` and `constrain` reads the stack.
_AXIS_CTX: list[tuple[Mesh, dict]] = []


class axis_rules:
    """Context manager registering (mesh, rules) for ``constrain``."""

    def __init__(self, mesh: Mesh, rules: dict[str, str | None] | None = None):
        self.entry = (mesh, rules or DEFAULT_RULES)

    def __enter__(self):
        _AXIS_CTX.append(self.entry)
        return self

    def __exit__(self, *exc):
        _AXIS_CTX.pop()


def current_dp_groups() -> int:
    """Data-parallel group count from the active axis_rules mesh (1 off-mesh).

    Used by the MoE group-local dispatch: routing/sort/scatter stay inside
    one DP shard so token gathers never cross the data axis (§Perf
    iteration 4 — kills the (T*k, d)-sized dispatch all-reduces).
    """
    if not _AXIS_CTX:
        return 1
    mesh, _ = _AXIS_CTX[-1]
    out = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            out *= mesh.shape[a]
    return max(out, 1)


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    """Activation sharding constraint via logical names (no-op off-mesh)."""
    if not _AXIS_CTX:
        return x
    mesh, rules = _AXIS_CTX[-1]
    if len(logical) != x.ndim:
        raise ValueError(f"constrain: {len(logical)} axes for rank-{x.ndim} value")
    spec = resolve_axes(tuple(logical), tuple(x.shape), mesh, rules)
    return jax.lax.with_sharding_constraint(x, spec)
