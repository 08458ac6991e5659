"""Trace reduction on a small recorded trace (tests/bench/data)."""

import json
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import trace

DATA = json.loads((Path(__file__).parent / "data" / "trace_small.json").read_text())


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_events([tuple(e) for e in DATA["events"]], DATA["window_s"])


def test_busy_is_the_union_of_device_ops(summary):
    # [1.0, 4.0] ms, [6.0, 9.0] ms and [9.5, 9.75] ms: nested ops count once
    assert summary.chips == 1
    assert summary.busy_s == pytest.approx(6.25e-3)
    assert trace.idle_share_pct(summary) == pytest.approx(37.5)


def test_op_names_are_cut_from_the_instruction_text(summary):
    assert set(summary.op_seconds) == {"while.8", "fusion.12", "copy.5", "convolution.3", "fusion.40"}
    assert trace.op_family("convolution.3") == "convolution"


def test_ops_count_their_self_time(summary):
    # first loop [1.0, 3.0]: fusion.12 runs [1.2, 1.7], copy.5 [1.5, 2.0] over it;
    # the loop keeps [1.0, 1.2] and [2.0, 3.0], the later op the overlap
    assert summary.op_seconds["fusion.12"] == pytest.approx(0.3e-3)
    assert summary.op_seconds["copy.5"] == pytest.approx(0.5e-3)
    assert summary.op_seconds["while.8"] == pytest.approx(1.2e-3 + 2.0e-3)
    top = summary.breakdown()["device_ops"]
    assert top[0] == ["while", pytest.approx(3.2e-3)]
    assert len(top) <= trace.TOP


@pytest.mark.parametrize("ops", [
    [(0, 10, "a"), (1, 3, "b"), (2, 8, "c")],  # siblings overlap inside a parent
    [(0, 10, "a"), (8, 20, "b"), (10, 12, "c")],  # a child outlives its parent
    [(0, 10, "a"), (0, 10, "a2"), (4, 5, "b")],  # one op twice
    [(0, 4, "a"), (6, 9, "b"), (6, 9, "c")],  # a gap between ops
], ids=["overlapping_siblings", "outliving_child", "duplicate", "gap"])
def test_self_times_add_up_to_the_busy_union(ops):
    into: dict = {}
    trace._self_seconds(ops, into)
    union = sum(e - s for s, e in trace._union([(s, e) for s, e, _ in ops]))
    assert sum(into.values()) == pytest.approx(union * 1e-9)
    assert all(v > 0 for v in into.values())


def test_idle_gaps_go_to_the_innermost_host_span(summary):
    gaps = dict(summary.breakdown()["idle_gaps"])
    # 4.0-6.0 ms: the save covers 1.7 ms of it, the dispatch 0.2 ms
    assert gaps["bench.ckpt.save"] == pytest.approx(2e-3)
    # 9.0-9.5 ms: the durable wait covers all of it, the data wait in it 0.4 ms
    assert gaps["bench.ckpt.durable_wait"] == pytest.approx(0.5e-3)


def test_busy_time_past_the_window_shows_as_negative_idle(summary):
    import dataclasses

    short = dataclasses.replace(summary, window_s=5e-3)
    assert trace.idle_share_pct(short) == pytest.approx(-25.0)


def test_a_trace_without_device_ops_reads_nothing():
    host_only = [e for e in DATA["events"] if not e[0].startswith("/device")]
    s = trace.reduce_events([tuple(e) for e in host_only], DATA["window_s"])
    assert s.chips == 0 and trace.idle_share_pct(s) is None
