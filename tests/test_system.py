"""End-to-end behaviour: training through the two-level store with
checkpoint/restart, failure injection, and exact recovery."""

import dataclasses
import threading
import time

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


def _hooked_run(store, **kw):
    """``run_training`` with an ``on_step`` that records each call's step,
    loss and time; returns the result, the calls and the loop's spans."""
    calls = []
    hook = lambda s, m: calls.append((s, float(m["loss"]), time.perf_counter()))
    res, recs = _spans_after(lambda: run_training(small_cfg(), store, on_step=hook, **kw))
    by = lambda name: {r.step: r for r in recs if r.name == name}
    return res, calls, by


class TestOverlappedHostTurn:
    """The next step's batch is taken and uploaded while the step before it
    runs; each step's loss is read before the next dispatch, and the batch
    after a save step is taken once the save is done."""

    def test_the_next_batch_is_taken_before_the_result_is_read(self, big_store):
        res, _, by = _hooked_run(big_store, total_steps=5, ckpt_every=100)
        data, dispatch, result = by("train.data_wait"), by("train.dispatch"), by("train.result_wait")
        assert res.steps_run == 5 and sorted(result) == sorted(data) == [0, 1, 2, 3, 4]
        for n in range(4):
            assert dispatch[n].t1 <= data[n + 1].t0 and data[n + 1].t1 <= result[n].t0, n
            assert result[n].t1 <= dispatch[n + 1].t0, n

    def test_a_save_step_is_finished_before_its_save(self, big_store):
        res, calls, by = _hooked_run(big_store, total_steps=6, ckpt_every=3,
                                     ckpt_mode="async")
        data, dispatch, result, ckpt = (by(n) for n in (
            "train.data_wait", "train.dispatch", "train.result_wait", "train.ckpt"))
        hooked = {s: t for s, _, t in calls}
        assert sorted(ckpt) == [3, 6]
        # step 2 is saved as 3: its result and hook come first; step 3's
        # batch is taken, and step 3 dispatched, once the save is done
        assert result[2].t1 <= hooked[2] <= ckpt[3].t0
        assert ckpt[3].t1 <= data[3].t0 and data[3].t1 <= dispatch[3].t0
        # the overlap resumes after the save: step 4's batch before step 3's result
        assert data[4].t1 <= result[3].t0
        # the last step is finished, its hook called, before the run ends
        assert result[5].t1 <= hooked[5] <= ckpt[6].t0
        assert res.steps_run == 6 and int(res.state["step"]) == 6

    def test_each_step_is_handed_to_the_hook_once_in_order(self, big_store):
        res, calls, _ = _hooked_run(big_store, total_steps=7, ckpt_every=3)
        assert [s for s, _, _ in calls] == list(range(7))
        assert [loss for _, loss, _ in calls] == res.losses
        assert res.steps_run == len(res.losses) == 7

    def test_a_failure_replays_from_the_saved_cursor(self, tmp_path):
        """Failing before step 7's dispatch, with its batch already taken:
        the run restores step 5 and the loader's cursor there, and replays
        5 and 6 with the first run's losses, bit for bit."""
        kw = dict(total_steps=10, ckpt_every=5, ckpt_mode="sync")
        with TwoLevelStore(str(tmp_path / "a"), mem_capacity_bytes=64 * 2**20) as st:
            clean, clean_calls, _ = _hooked_run(st, **kw)
        with TwoLevelStore(str(tmp_path / "b"), mem_capacity_bytes=64 * 2**20) as st:
            failed, calls, _ = _hooked_run(st, injector=FailureInjector([7]), **kw)
        assert failed.restarts == 1 and failed.steps_run == 12
        assert [s for s, _, _ in calls] == [0, 1, 2, 3, 4, 5, 6, 5, 6, 7, 8, 9]
        assert [loss for _, loss, _ in calls] == failed.losses
        assert failed.losses == clean.losses[:7] + clean.losses[5:]
        assert int(failed.state["step"]) == 10
