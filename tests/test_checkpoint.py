"""Two-level checkpointing: atomic commits, async durability, GC, reshard."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.checkpoint import CheckpointManager


def tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": rng.normal(size=(16, 8)).astype(np.float32), "b": np.zeros(8, np.float32)},
        "opt": {"m": np.zeros((16, 8), np.float32), "count": np.int32(3)},
        "step": np.int64(7),
    }


class TestSaveRestore:
    def test_roundtrip_exact(self, store):
        cm = CheckpointManager(store, tag="t")
        state = tree()
        cm.save(10, state)
        step, got = cm.restore(state)
        assert step == 10
        jax.tree_util.tree_map(np.testing.assert_array_equal, got, state)

    def test_latest_wins(self, store):
        cm = CheckpointManager(store, tag="t")
        s1, s2 = tree(1), tree(2)
        cm.save(1, s1)
        cm.save(2, s2)
        step, got = cm.restore(s1)
        assert step == 2
        np.testing.assert_array_equal(got["params"]["w"], s2["params"]["w"])

    def test_restore_specific_step(self, store):
        cm = CheckpointManager(store, tag="t", keep_last=5)
        s1, s2 = tree(1), tree(2)
        cm.save(1, s1)
        cm.save(2, s2)
        step, got = cm.restore(s1, step=1)
        assert step == 1
        np.testing.assert_array_equal(got["params"]["w"], s1["params"]["w"])

    def test_empty_raises(self, store):
        cm = CheckpointManager(store, tag="none")
        with pytest.raises(FileNotFoundError):
            cm.restore(tree())

    def test_shape_mismatch_raises(self, store):
        cm = CheckpointManager(store, tag="t")
        cm.save(1, tree())
        bad = tree()
        bad["params"]["w"] = np.zeros((4, 4), np.float32)
        with pytest.raises(ValueError, match="shape mismatch"):
            cm.restore(bad)

    def test_structure_mismatch_raises(self, store):
        cm = CheckpointManager(store, tag="t")
        cm.save(1, tree())
        bad = tree()
        bad["params"]["extra"] = np.zeros(3, np.float32)
        with pytest.raises(KeyError):
            cm.restore(bad)


class TestDurabilityAndGC:
    def test_async_mode_durable_after_barrier(self, store):
        cm = CheckpointManager(store, tag="t", mode="async")
        cm.save(5, tree())
        cm.wait_until_durable()
        # wipe the memory tier: restore must come from the PFS tier
        store.mem.clear()
        step, _ = cm.restore(tree())
        assert step == 5

    def test_memory_only_mode_is_volatile(self, store):
        cm = CheckpointManager(store, tag="t", mode="memory_only")
        cm.save(5, tree())
        assert cm.steps() == [5]
        store.mem.clear()
        # metadata may linger, but the blocks died with the fast tier
        with pytest.raises(Exception):
            cm.restore(tree())

    def test_keep_last_gc(self, store):
        cm = CheckpointManager(store, tag="t", keep_last=2)
        for s in (1, 2, 3, 4):
            cm.save(s, tree())
        assert cm.steps() == [3, 4]

    def test_uncommitted_save_invisible(self, store):
        cm = CheckpointManager(store, tag="t")
        state = tree()
        cm.save(1, state)
        # simulate a crash mid-save: data without COMMIT
        prefix = cm._prefix(2)
        store.put(f"{prefix}/leaves", b"partial")
        store.put(f"{prefix}/manifest", b"{}")
        assert cm.steps() == [1]
        step, _ = cm.restore(state)
        assert step == 1


class TestElasticRestore:
    def test_restore_sharded_places_on_device(self, store):
        cm = CheckpointManager(store, tag="t")
        state = tree()
        cm.save(1, state)
        shardings = jax.tree_util.tree_map(
            lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]), state
        )
        step, placed = cm.restore_sharded(state, shardings)
        assert step == 1
        leaf = placed["params"]["w"]
        assert isinstance(leaf, jax.Array)
        np.testing.assert_array_equal(np.asarray(leaf), state["params"]["w"])

    def test_jax_arrays_serializable(self, store):
        cm = CheckpointManager(store, tag="t")
        state = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)}
        cm.save(1, state)
        _, got = cm.restore(state)
        np.testing.assert_array_equal(got["w"], np.asarray(state["w"]))


class TestChunkedLayout:
    def test_chunks_and_manifest_files_exist(self, store):
        import json

        cm = CheckpointManager(store, tag="t", chunk_bytes=256)  # force many chunks
        cm.save(3, tree())
        names = [n for n in store.list_files() if n.startswith("ckpt/t/step_00000003/")]
        chunk_names = [n for n in names if "/chunk_" in n]
        assert len(chunk_names) >= 2  # leaves split across chunks
        assert any(n.endswith("/manifest") for n in names)
        assert any(n.endswith("/COMMIT") for n in names)
        man = json.loads(store.get("ckpt/t/step_00000003/manifest").decode())
        assert len(man["chunks"]) == len(chunk_names)
        # every leaf lands whole inside one chunk
        for meta in man["leaves"].values():
            assert meta["offset"] + meta["size"] <= man["chunks"][meta["chunk"]]

    def test_gc_removes_chunk_files(self, store):
        cm = CheckpointManager(store, tag="t", keep_last=1, chunk_bytes=256)
        cm.save(1, tree())
        cm.save(2, tree())
        leftover = [n for n in store.list_files() if n.startswith("ckpt/t/step_00000001/")]
        assert leftover == []

    def test_steps_ignores_debris(self, store):
        cm = CheckpointManager(store, tag="t")
        cm.save(4, tree())
        # stray non-conforming files under ckpt/<tag>/ must not break steps()
        store.put("ckpt/t/step_garbage/COMMIT", b"x")
        store.put("ckpt/t/step_12xy/leaves", b"x")
        store.put("ckpt/t/notes/README", b"x")
        assert cm.steps() == [4]
        assert cm.latest_step() == 4

    def test_restore_uses_ranged_reads_for_partial_chunks(self, store):
        """A template needing one leaf out of a packed chunk must not read
        the other leaves' bytes."""
        cm = CheckpointManager(store, tag="t", chunk_bytes=1 << 30)  # one big chunk
        state = tree()
        cm.save(1, state)
        store.mem.clear()  # force PFS reads so byte accounting is visible
        sub = {"opt": {"count": np.int32(0)}}
        before = store.pfs.stats.bytes_read
        _, got = cm.restore(sub)
        assert int(got["opt"]["count"]) == int(state["opt"]["count"])
        total = sum(np.asarray(v).nbytes for v in jax.tree_util.tree_leaves(state))
        assert store.pfs.stats.bytes_read - before < total

    def test_async_save_overlaps_and_commits_in_order(self, store):
        cm = CheckpointManager(store, tag="t", mode="async", keep_last=10)
        for s in (1, 2, 3):
            cm.save(s, tree(s))
        cm.wait_until_durable()
        assert cm.steps() == [1, 2, 3]
        step, got = cm.restore(tree())
        assert step == 3
        np.testing.assert_array_equal(got["params"]["w"], tree(3)["params"]["w"])



# Chunk size of the zero-copy pack tests: leaves named ``big_*`` are larger
# and get a chunk each (handed to the store as views); the ``small_*`` run
# shares chunks (joined, a copy); ``big_transposed`` is non-contiguous, so
# it is made contiguous (a copy) before it fills its chunk alone.
PACK_CHUNK = 4096


def mixed_state(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "big_f32": rng.normal(size=(32, 48)).astype(np.float32),
        "big_bf16": jnp.asarray(rng.normal(size=(40, 64)), dtype=jnp.bfloat16),
        "big_int32": rng.integers(-(2**31), 2**31 - 1, size=(1500,), dtype=np.int32),
        "big_transposed": rng.normal(size=(24, 80)).astype(np.float32).T,
        "small_a_scalar": np.float32(rng.normal()),
        "small_b_bf16": rng.normal(size=(7, 5)).astype(jnp.bfloat16),
        "small_c_int32": rng.integers(0, 100, size=(13,), dtype=np.int32),
        "small_d_f32": rng.normal(size=(3, 9)).astype(np.float32),
        "small_e_int64": np.int64(rng.integers(0, 2**40)),
    }


def reference_layout(state, chunk_bytes):
    """The checkpoint a copying pack writes: each leaf's ``tobytes`` packed
    greedily, in leaf order, into chunks of about ``chunk_bytes``."""
    leaves, chunks, parts, filled = {}, [], [], 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        arr = np.asarray(leaf)
        raw = np.ascontiguousarray(arr).tobytes()
        if filled and filled + len(raw) > chunk_bytes:
            chunks.append(b"".join(parts))
            parts, filled = [], 0
        leaves[jax.tree_util.keystr(path)] = {
            "shape": list(arr.shape), "dtype": str(arr.dtype),
            "chunk": len(chunks), "offset": filled, "size": len(raw),
        }
        parts.append(raw)
        filled += len(raw)
        if filled >= chunk_bytes:
            chunks.append(b"".join(parts))
            parts, filled = [], 0
    if parts:
        chunks.append(b"".join(parts))
    return {"chunks": [len(c) for c in chunks], "leaves": leaves}, chunks


def assert_bit_exact(got, want):
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)
    ):
        g, w = np.asarray(g), np.asarray(w)
        assert (g.dtype, g.shape) == (w.dtype, w.shape), jax.tree_util.keystr(path)
        assert g.tobytes() == w.tobytes(), jax.tree_util.keystr(path)


def leaf_bytes(state, prefix):
    return sum(np.asarray(v).nbytes for k, v in state.items() if k.startswith(prefix))


class TestZeroCopyPack:
    def test_reference_layout_gives_big_leaves_their_own_chunks(self):
        man, _ = reference_layout(mixed_state(), PACK_CHUNK)
        by_chunk = {}
        for name, meta in man["leaves"].items():
            by_chunk.setdefault(meta["chunk"], []).append(name)
        alone = {names[0] for names in by_chunk.values() if len(names) == 1}
        assert alone == {f"['{k}']" for k in mixed_state() if k.startswith("big_")}

    @pytest.mark.parametrize("mode", ["sync", "async", "memory_only"])
    def test_store_holds_the_copying_pack_bytes(self, store, mode):
        import json

        cm = CheckpointManager(store, tag="z", mode=mode, chunk_bytes=PACK_CHUNK)
        state = mixed_state()
        cm.save(5, state)
        cm.wait_until_durable()
        want_manifest, want_chunks = reference_layout(state, PACK_CHUNK)
        prefix = "ckpt/z/step_00000005"
        assert json.loads(store.get(f"{prefix}/manifest").decode()) == want_manifest
        names = sorted(n for n in store.list_files() if n.startswith(f"{prefix}/chunk_"))
        assert names == [f"{prefix}/chunk_{i:04d}" for i in range(len(want_chunks))]
        for name, want in zip(names, want_chunks):
            assert store.get(name) == want, name
        assert store.get(f"{prefix}/COMMIT") == str(len(want_chunks)).encode()

    @pytest.mark.parametrize("mode", ["sync", "async", "memory_only"])
    def test_restore_is_bit_exact(self, store, mode):
        cm = CheckpointManager(store, tag="z", mode=mode, chunk_bytes=PACK_CHUNK)
        state = mixed_state(1)
        cm.save(2, state)
        step, got = cm.restore(state)
        assert step == 2
        assert_bit_exact(got, state)

    @pytest.mark.parametrize("mode", ["sync", "async", "memory_only"])
    def test_only_small_and_noncontiguous_leaves_are_copied(self, store, mode):
        cm = CheckpointManager(store, tag="z", mode=mode, chunk_bytes=PACK_CHUNK)
        state = mixed_state(2)
        copied = leaf_bytes(state, "small_") + state["big_transposed"].nbytes
        viewed = leaf_bytes(state, "big_") - state["big_transposed"].nbytes
        cm.save(1, state)
        cm.wait_until_durable()
        assert (cm.pack_copied_bytes, cm.pack_view_bytes) == (copied, viewed)
        cm.save(2, state)  # the counters are cumulative
        cm.wait_until_durable()
        assert (cm.pack_copied_bytes, cm.pack_view_bytes) == (2 * copied, 2 * viewed)

    def test_checkpoint_from_the_copying_pack_restores(self, store):
        """A checkpoint written by the copying pack (same format) restores."""
        import json

        state = mixed_state(3)
        manifest, chunks = reference_layout(state, PACK_CHUNK)
        prefix = "ckpt/z/step_00000011"
        batch = {f"{prefix}/chunk_{i:04d}": c for i, c in enumerate(chunks)}
        batch[f"{prefix}/manifest"] = json.dumps(manifest).encode()
        store.put_many(batch)
        store.put(f"{prefix}/COMMIT", str(len(chunks)).encode())
        step, got = CheckpointManager(store, tag="z", chunk_bytes=PACK_CHUNK).restore(state)
        assert step == 11
        assert_bit_exact(got, state)

    def test_async_save_owns_its_snapshot(self, store):
        """The lane reads the snapshot after save() returns: a donating step
        on the device leaves and in-place writes to the caller's host leaves
        made meanwhile must not reach the checkpoint."""
        import threading

        rng = np.random.default_rng(4)
        params = {
            "w": jnp.asarray(rng.normal(size=(64, 32)), dtype=jnp.float32),
            "e": jnp.asarray(rng.normal(size=(48, 64)), dtype=jnp.bfloat16),
        }
        host = {
            "cursor": np.arange(3, dtype=np.int64),
            "table": rng.normal(size=(40, 40)).astype(np.float32),
        }
        saved = jax.tree_util.tree_map(np.array, {"params": params, "host": host})
        step_fn = jax.jit(
            lambda p: jax.tree_util.tree_map(lambda x: x * 3 + 1, p), donate_argnums=0
        )
        cm = CheckpointManager(store, tag="z", mode="async", chunk_bytes=PACK_CHUNK)
        gate = threading.Event()
        cm._bg.submit(gate.wait)  # hold the lane until the writes below are done
        cm.save(1, {"params": params, "host": host})
        params = jax.block_until_ready(step_fn(params))
        host["cursor"] += 100
        host["table"][:] = -1.0
        gate.set()
        cm.wait_until_durable()
        _, got = cm.restore(saved)
        assert_bit_exact(got, saved)
        assert not np.array_equal(np.asarray(params["w"]), saved["params"]["w"])

ELASTIC_SUBPROCESS_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys, tempfile
import jax, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import TwoLevelStore
from repro.runtime.checkpoint import CheckpointManager

rng = np.random.default_rng(0)
state = {
    "w": rng.normal(size=(16, 8)).astype(np.float32),
    "b": rng.normal(size=(8,)).astype(np.float32),
}
out = {"ok": True}

def shardings_for(n_dev):
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(n_dev), ("data",))
    return {
        "w": NamedSharding(mesh, P("data", None)),
        "b": NamedSharding(mesh, P()),
    }

with tempfile.TemporaryDirectory() as d:
    with TwoLevelStore(d + "/pfs", mem_capacity_bytes=32 * 2**20) as store:
        cm = CheckpointManager(store, tag="t")
        # save from a 1-device placement
        placed1 = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, jax.devices()[0]), state
        )
        cm.save(1, placed1)
        # elastic restore onto 2- and 4-device meshes
        for n_dev in (2, 4):
            step, placed = cm.restore_sharded(state, shardings_for(n_dev), step=1)
            assert step == 1
            for k in state:
                np.testing.assert_array_equal(np.asarray(placed[k]), state[k])
            nsh = len({str(s.index) for s in placed["w"].addressable_shards})
            assert nsh == n_dev, f"w not sharded {n_dev}-way: {nsh}"
            # save from the bigger mesh and restore back onto 1 device
            cm.save(n_dev, placed)
            step2, back = cm.restore_sharded(
                state,
                jax.tree_util.tree_map(
                    lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]), state
                ),
                step=n_dev,
            )
            for k in state:
                np.testing.assert_array_equal(np.asarray(back[k]), state[k])
            assert len(back["w"].addressable_shards) == 1
print(json.dumps(out))
"""


def test_elastic_restore_across_mesh_sizes():
    """Save on 1 device; restore_sharded onto 2/4-device meshes and back —
    leaf equality and sharding placement both asserted (8 forced CPU
    devices in a subprocess, like test_sharding)."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", ELASTIC_SUBPROCESS_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"]


def test_restore_legacy_monolithic_format(store):
    """Checkpoints written by the pre-chunked layout (one `leaves` blob +
    flat manifest) on a surviving PFS root must still restore."""
    import json

    state = tree()
    manifest = {}
    parts = []
    offset = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        arr = np.asarray(leaf)
        raw = np.ascontiguousarray(arr).tobytes()
        manifest[jax.tree_util.keystr(path)] = {
            "shape": list(arr.shape), "dtype": str(arr.dtype),
            "offset": offset, "size": len(raw),
        }
        parts.append(raw)
        offset += len(raw)
    prefix = "ckpt/t/step_00000009"
    store.put(f"{prefix}/leaves", b"".join(parts))
    store.put(f"{prefix}/manifest", json.dumps(manifest).encode())
    store.put(f"{prefix}/COMMIT", str(offset).encode())

    cm = CheckpointManager(store, tag="t")
    step, got = cm.restore(state)
    assert step == 9
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, state)
