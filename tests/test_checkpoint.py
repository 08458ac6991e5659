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
