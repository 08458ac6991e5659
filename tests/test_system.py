"""End-to-end behaviour: training through the two-level store with
checkpoint/restart, failure injection, and exact recovery."""

import dataclasses
import threading

import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import ReadMode, TwoLevelStore, WriteMode
from repro.core import trace
from repro.launch.train import run_training
from repro.runtime.failure import FailureInjector


def small_cfg():
    return dataclasses.replace(get_reduced("starcoder2_3b"), n_layers=2, d_model=32, d_ff=64,
                               n_heads=4, n_kv_heads=2, vocab=256)


@pytest.fixture()
def big_store(tmp_path):
    with TwoLevelStore(
        str(tmp_path / "pfs"), mem_capacity_bytes=64 * 2**20, block_bytes=2**20
    ) as st:
        yield st


class TestEndToEnd:
    def test_train_completes_and_checkpoints(self, big_store):
        res = run_training(small_cfg(), big_store, total_steps=8, ckpt_every=4)
        assert res.steps_run == 8
        assert res.restarts == 0
        assert np.isfinite(res.losses).all()
        # checkpoints live in BOTH tiers (write mode c / async writeback)
        names = big_store.list_files()
        assert any(n.startswith("ckpt/") for n in names)
        assert any(n.startswith("corpus/") for n in names)

    def test_failure_recovery_reaches_target(self, big_store):
        inj = FailureInjector([6])
        res = run_training(small_cfg(), big_store, total_steps=10, ckpt_every=5, injector=inj)
        assert res.restarts == 1
        assert len(inj.injected) == 1
        assert int(res.state["step"]) == 10

    def test_recovery_is_exact(self, tmp_path):
        """Failure + restore must yield the SAME final params as an
        uninterrupted run (deterministic pipeline + committed cursor)."""
        cfg = small_cfg()
        with TwoLevelStore(str(tmp_path / "a"), mem_capacity_bytes=64 * 2**20) as st_a:
            clean = run_training(cfg, st_a, total_steps=10, ckpt_every=5, ckpt_mode="sync")
        with TwoLevelStore(str(tmp_path / "b"), mem_capacity_bytes=64 * 2**20) as st_b:
            failed = run_training(
                cfg, st_b, total_steps=10, ckpt_every=5, ckpt_mode="sync",
                injector=FailureInjector([7]),
            )
        assert failed.restarts == 1
        wa = clean.state["params"]["embed"]["table"]
        wb = failed.state["params"]["embed"]["table"]
        np.testing.assert_allclose(np.asarray(wa), np.asarray(wb), rtol=1e-5, atol=1e-6)

    def test_cold_cluster_restart_resumes(self, tmp_path):
        """Process death: a NEW store (empty memory tier) resumes from the
        PFS tier — the paper's fault-tolerance argument for the TLS."""
        cfg = small_cfg()
        with TwoLevelStore(str(tmp_path / "pfs"), mem_capacity_bytes=64 * 2**20) as st1:
            run_training(cfg, st1, total_steps=5, ckpt_every=5, ckpt_mode="sync")
        # new store object = lost RAM; PFS directory survives
        with TwoLevelStore(str(tmp_path / "pfs"), mem_capacity_bytes=64 * 2**20) as st2:
            second = run_training(cfg, st2, total_steps=10, ckpt_every=5, ckpt_mode="sync")
            assert int(second.state["step"]) == 10
            assert second.steps_run == 5  # only the remaining steps
            # and the resume actually read checkpoint blocks from the PFS tier
            assert st2.stats.mem_misses > 0

    def test_elastic_batch_change_via_restore(self, big_store):
        """Restore the same checkpoint into a run with a different global
        batch (elastic rescale: N hosts -> M hosts)."""
        cfg = small_cfg()
        run_training(cfg, big_store, total_steps=5, ckpt_every=5, global_batch=8, ckpt_mode="sync")
        res = run_training(cfg, big_store, total_steps=8, ckpt_every=4, global_batch=4)
        assert int(res.state["step"]) == 8


def _spans_after(fn):
    """Run ``fn``; return its result and the spans recorded meanwhile."""
    recs = trace.records()
    start = recs[-1].seq if recs else -1
    out = fn()
    return out, [r for r in trace.records() if r.seq > start]


def _inside(inner, outer) -> bool:
    return inner.thread == outer.thread and outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


class TestSpans:
    def test_each_step_and_save_records_its_spans(self, big_store):
        res, recs = _spans_after(lambda: run_training(
            small_cfg(), big_store, total_steps=4, ckpt_every=2, ckpt_mode="async"))
        assert res.steps_run == 4
        loop = {r.thread for r in recs if r.name == "train.dispatch"}
        assert loop == {threading.current_thread().name}
        for name in ("train.data_wait", "train.dispatch", "train.result_wait"):
            assert [r.step for r in recs if r.name == name] == [0, 1, 2, 3], name
        for saved in (2, 4):
            (outer,) = [r for r in recs if r.name == "train.ckpt" and r.step == saved]
            (save,) = [r for r in recs if r.name == "ckpt.save" and r.step == saved]
            (snap,) = [r for r in recs if r.name == "ckpt.snapshot" and r.step == saved]
            (pack,) = [r for r in recs if r.name == "ckpt.pack" and r.step == saved]
            assert _inside(save, outer) and _inside(snap, save)
            assert pack.thread.startswith("ckpt-save") and pack.t0 >= snap.t1
        # the stall totals are the spans' sums
        total = lambda name: sum(r.seconds for r in recs if r.name == name)
        assert res.stalls == {
            "data_stall_total_s": pytest.approx(total("train.data_wait"), abs=1e-12),
            "ckpt_stall_total_s": pytest.approx(total("train.ckpt"), abs=1e-12),
            "ckpt_save_critical_s": pytest.approx(total("ckpt.save"), abs=1e-12),
        }

    @pytest.mark.parametrize("depth,waits", [(1, True), (64, False)])
    def test_a_full_write_back_queue_records_the_wait(self, tmp_path, depth, waits):
        """One flusher held on its first block: with room for one queued
        block the third put waits for the queue; with 64 none does."""
        release = threading.Event()
        with TwoLevelStore(str(tmp_path / "pfs"), mem_capacity_bytes=64 * 2**20,
                           block_bytes=2**20, async_queue_depth=depth,
                           flush_workers=1) as st:
            flush = st._claim_and_flush

            def held_flush(bkey):
                assert release.wait(timeout=30)
                flush(bkey)

            st._claim_and_flush = held_flush
            timer = threading.Timer(0.5, release.set)
            timer.start()
            try:
                _, recs = _spans_after(lambda: st.put(
                    "wb/file", bytes(8 * 2**20), mode=WriteMode.ASYNC_WRITEBACK))
            finally:
                release.set()
                timer.cancel()
            st.drain()
            assert st.get("wb/file") == bytes(8 * 2**20)
        waited = [r for r in recs if r.name == "store.writeback_wait"]
        assert bool(waited) == waits
        for r in waited:
            assert r.thread == threading.current_thread().name and r.step is None
        if waits:
            assert sum(r.seconds for r in waited) >= 0.1
