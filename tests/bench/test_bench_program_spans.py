"""The readers of the program's spans (bench/program_spans.py and its four
metrics), on synthetic span records and on a tiny run on the CPU."""

import sys

import pytest

import bench_tiny as bt
from bench import harness
from bench import program_spans as ps
from repro.core.trace import SpanRecord

LOOP, LANE = "MainThread", "ckpt-save_0"
READERS = ("train.host_gap_ms", "ckpt.snapshot_ms", "ckpt.pack_s", "store.writeback_wait_s.train")


class Ring:
    """Synthetic span records, in the order a run would end them."""

    def __init__(self):
        self.recs: list[SpanRecord] = []

    def add(self, name, t0, t1, step=None, thread=LOOP):
        self.recs.append(SpanRecord(len(self.recs), name, thread, t0, t1, step))

    def sorted(self):
        out = sorted(self.recs, key=lambda r: r.t1)
        return [r._replace(seq=i) for i, r in enumerate(out)]


SAVES = {1: (0.9, 0.8), 2: (0.4, 0.5)}


def run(steps=6, warm=3, gap=0.004, warm_gap=0.05, saves=SAVES, waits_after=2):
    """Steps 0..steps-1; each step's host gap is ``gap`` from step ``warm``
    on and ``warm_gap`` before, whatever its save adds.  After step k in
    ``saves`` the state of step k+1 is saved, with the snapshot and pack
    seconds given.  Around the save after step ``waits_after`` the store's
    puts wait on its queue 0.25 s before its pack (the corpus) and 0.2 +
    0.1 s after it."""
    ring = Ring()
    t = 0.0
    for k in range(steps):
        g = gap if k >= warm else warm_gap
        ring.add("train.data_wait", t, t + g / 4, k)
        ring.add("train.dispatch", t + g / 2, t + g, k)
        ring.add("train.result_wait", t + g, t + g + 0.25, k)
        t += g + 0.25
        if k in saves:
            snap, pack = saves[k]
            ring.add("ckpt.snapshot", t, t + snap, k + 1)
            ring.add("ckpt.save", t, t + snap + 0.01, k + 1)
            ring.add("train.ckpt", t, t + snap + 0.02, k + 1)
            ring.add("ckpt.pack", t + snap + 0.03, t + snap + 0.03 + pack, k + 1, LANE)
            if k == waits_after:
                ring.add("store.writeback_wait", t - 0.5, t - 0.25)
                end = t + snap + 0.03 + pack
                ring.add("store.writeback_wait", end + 0.1, end + 0.3, thread=LANE)
                ring.add("store.writeback_wait", end + 0.4, end + 0.5, thread=LANE)
            t += snap + 0.02
    return ring.sorted()


def test_the_window_leaves_out_warm_up_and_subtracts_the_save():
    w = ps.window(run(), 3)
    assert w.host_gaps == pytest.approx([0.004] * 3)  # warm-up gaps are 0.05
    assert w.snapshots == pytest.approx([0.4]) and w.packs == pytest.approx([0.5])
    assert w.writeback_wait_s == pytest.approx(0.3)


def test_the_window_save_is_picked_by_its_step():
    # with four window steps the save of step 2 is the window's too
    w = ps.window(run(warm=2, saves={1: (0.9, 0.8)}), 4)
    assert w.snapshots == pytest.approx([0.9]) and w.packs == pytest.approx([0.8])
    assert w.writeback_wait_s == 0.0
    assert w.host_gaps == pytest.approx([0.004] * 4)
    # a window that starts after the last save has none
    w = ps.window(run(saves={1: (0.9, 0.8)}), 3)
    assert w.snapshots == [] and w.packs == [] and w.writeback_wait_s is None
    assert w.host_gaps == pytest.approx([0.004] * 3)


def test_a_ring_without_the_first_window_step_gives_nothing():
    recs = run()
    assert ps.window(recs, 6) is None  # step 0 has no step before it
    first = next(i for i, r in enumerate(recs) if r.name == "train.dispatch" and r.step == 3)
    assert ps.window(recs[first + 1:], 3) is None  # the first window step is gone
    prev = next(i for i, r in enumerate(recs) if r.name == "train.result_wait" and r.step == 2)
    assert ps.window(recs[prev + 1:], 3) is None  # and the end of the step before it
    assert ps.window(recs[prev:], 3) is not None
    assert ps.window([], 3) is None and ps.window(None, 3) is None


def _rec(steps):
    return harness.RunRecord("train.ckpt", {}, {}, {}, 1.0, [], {"train.steps": steps}, None)


def test_the_readers_read_the_window(monkeypatch):
    monkeypatch.setattr(ps, "program_records", lambda: run())
    got = {m: harness.reader_module(m).read(_rec(3)) for m in READERS}
    assert got == pytest.approx({"train.host_gap_ms": 4.0, "ckpt.snapshot_ms": 400.0,
                                 "ckpt.pack_s": 0.5, "store.writeback_wait_s.train": 0.3})
    monkeypatch.setattr(ps, "program_records", lambda: run(saves={}))
    got = {m: harness.reader_module(m).read(_rec(3)) for m in READERS}
    assert got["train.host_gap_ms"] == pytest.approx(4.0)
    assert [got[m] for m in READERS[1:]] == [None, None, None]


def test_a_program_without_the_tracer_gives_no_number(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)  # import fails
    assert ps.program_records() is None
    assert all(harness.reader_module(m).read(_rec(3)) is None for m in READERS)


def test_a_tiny_traced_run_reports_every_span_metric(tmp_path):
    res = bt.run_tiny(bt.tiny_cell("train.ckpt"), tmp_path, trace=True)
    assert res["correct"], res["checks"]
    got = {m: res["metrics"].get(m, {}).get("value") for m in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["train.host_gap_ms"] > 0 and got["ckpt.snapshot_ms"] > 0 and got["ckpt.pack_s"] > 0
    # the snapshot is part of the save the benchmark times around it
    assert got["ckpt.snapshot_ms"] <= res["metrics"]["ckpt.save_stall_ms"]["value"]
