"""The program's span ring (repro.core.trace): order, fields, its bound,
and the spans' place in a JAX profile on the host plane."""

import threading
import time
from pathlib import Path

from repro.core import trace
from repro.core.trace import span


def _since(seq: int) -> list:
    return [r for r in trace.records() if r.seq > seq]


def _last_seq() -> int:
    recs = trace.records()
    return recs[-1].seq if recs else -1


def test_records_keep_order_seq_step_thread_and_seconds():
    start = _last_seq()
    with span("t.outer", step=7) as outer:
        with span("t.inner", step=7) as inner:
            time.sleep(0.01)
    with span("t.plain") as plain:
        pass

    def other():
        with span("t.thread", step=3):
            pass

    th = threading.Thread(target=other, name="t-worker")
    th.start()
    th.join(timeout=10)
    assert not th.is_alive()

    recs = _since(start)
    assert [r.name for r in recs] == ["t.inner", "t.outer", "t.plain", "t.thread"]
    seqs = [r.seq for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert [r.step for r in recs] == [7, 7, None, 3]
    assert [r.thread for r in recs] == ["MainThread"] * 3 + ["t-worker"]
    assert inner.seconds >= 0.01 and outer.seconds >= inner.seconds
    assert plain.seconds >= 0
    by_name = {r.name: r for r in recs}
    assert by_name["t.inner"].seconds == inner.seconds
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert (by_name["t.outer"].t0, by_name["t.outer"].t1) == (outer.t0, outer.t1)


def test_a_span_is_recorded_when_its_block_raises():
    start = _last_seq()
    try:
        with span("t.raises", step=1):
            raise KeyError("x")
    except KeyError:
        pass
    assert [r.name for r in _since(start)] == ["t.raises"]


def test_the_ring_drops_its_oldest_records_when_full():
    start = _last_seq()
    n = trace.RING + 10
    for i in range(n):
        with span("t.fill", step=i):
            pass
    recs = trace.records()
    assert len(recs) == trace.RING
    assert [r.step for r in recs] == list(range(10, n))
    assert recs[0].seq == start + 11 and recs[-1].seq == start + n


def test_spans_land_on_the_host_plane_of_a_profile(tmp_path):
    import jax
    from jax.profiler import ProfileData

    names = ["t.prof.main0", "t.prof.worker", "t.prof.main1"]

    def worker():
        with span(names[1], step=1):
            time.sleep(0.005)

    start = _last_seq()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span(names[0], step=0):
            time.sleep(0.005)
        th = threading.Thread(target=worker, name="t-prof-worker")
        th.start()
        th.join(timeout=10)
        with span(names[2], step=2):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    assert not th.is_alive()

    files = sorted(Path(tmp_path).glob("plugins/profile/*/*.xplane.pb"))
    assert files
    found = {}
    for plane in ProfileData.from_file(str(files[-1])).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in names:
                    found[ev.name] = (plane.name, i, ev.start_ns, ev.duration_ns)
    assert set(found) == set(names)
    assert all(plane.startswith("/host:") for plane, *_ in found.values())
    # the second thread's span is on a line of its own
    assert found[names[1]][1] != found[names[0]][1] == found[names[2]][1]
    recs = {r.name: r for r in _since(start)}
    by_clock = sorted(names, key=lambda n: recs[n].t0)
    by_profile = sorted(names, key=lambda n: found[n][2])
    assert by_clock == by_profile == names
    for n in names:  # the same span, timed twice on one clock
        assert abs(found[n][3] * 1e-9 - recs[n].seconds) < 2e-3
