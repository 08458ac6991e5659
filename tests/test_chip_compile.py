"""Compile the main path's TPU programs for a described v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (tiling,
fast-memory limits, HBM overflow).  Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  All chip compiles stay in this one file for the same reason.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: One v5e chip's HBM as its compiler reports it.
V5E_HBM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A described-chip compile cannot be read back from the persistent
    # cache without the chip; keep the cache out of it.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
            try:
                topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("cold,block_k", [(2048, 128), (4096, 512)])
def test_tiered_decode_kernel_compiles(one_chip, cold, block_k):
    from repro.kernels.tiered_decode import tiered_decode_attention_fwd

    b, h, kv, d, w = 4, 32, 8, 128, 256
    args = (
        _sds((b, h, 1, d), jnp.bfloat16, one_chip),
        _sds((b, kv, w, d), jnp.bfloat16, one_chip),
        _sds((b, kv, w, d), jnp.bfloat16, one_chip),
        _sds((b, kv, cold, d), jnp.bfloat16, one_chip),
        _sds((b, kv, cold, d), jnp.bfloat16, one_chip),
        _sds((3,), jnp.int32, one_chip),
    )
    fn = functools.partial(tiered_decode_attention_fwd, block_k=block_k)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention_fwd

    s, h, kv, d = 2048, 32, 8, 128
    q = _sds((1, h, s, d), jnp.bfloat16, one_chip)
    k = _sds((1, kv, s, d), jnp.bfloat16, one_chip)
    compiled = jax.jit(flash_attention_fwd).lower(q, k, k).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_donated_train_step_fits_one_chip(one_chip):
    """The step ``run_training`` compiles, at the one-chip training share of
    qwen3-8b (published widths, 2 layers, vocab 151,936 / 8, 4 x 2048).
    Donated it needs about 13.1 GiB; without donation about 16.1 GiB,
    more than the chip has."""
    from repro.configs import get_config, make_model
    from repro.launch.steps import init_state
    from repro.launch.train import jit_train_step
    from repro.optim.adamw import AdamW

    cfg = dataclasses.replace(get_config("qwen3_8b"), n_layers=2, vocab=18_992)
    model = make_model(cfg)
    optimizer = AdamW()
    state, _ = init_state(model, cfg, optimizer, jax.random.PRNGKey(0), abstract=True)
    state = jax.tree_util.tree_map(lambda s: _sds(s.shape, s.dtype, one_chip), state)
    batch = {k: _sds((4, 2048), jnp.int32, one_chip) for k in ("inputs", "labels")}
    compiled = jit_train_step(model, cfg, optimizer).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree_util.tree_leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes  # the new state reuses the old
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak <= V5E_HBM_BYTES
