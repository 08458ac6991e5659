"""Reduction of a profiler trace to device busy time, top device operations
and idle gaps attributed to the benchmark's host spans.

Events are plain ``(plane, line, name, start_ns, dur_ns)`` tuples, so the
reduction is tested on a small recorded trace without a chip.  Device
events are those on ``/device:TPU:<n>`` planes, on the ``XLA Ops`` line
(one event per HLO operation that ran); host spans are the ``bench.*``
annotations a :class:`bench.harness.RunContext` writes into the trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10


def load_events(trace_dir: Path) -> list[tuple[str, str, str, int, int]]:
    """Every event of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return []
    pd = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return out


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def op_name(event_name: str) -> str:
    """The HLO op's name from a trace event, which holds the whole
    instruction (``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_family(name: str) -> str:
    """An HLO op name without its numeric suffix (``fusion.123`` -> ``fusion``)."""
    return re.sub(r"[.\d]+$", "", name) or name


@dataclasses.dataclass
class TraceSummary:
    window_s: float  # host-clock length of the traced window
    busy_s: float  # device busy time, averaged over the chips traced
    chips: int
    op_seconds: dict[str, float]  # device self seconds per op name, all chips
    gap_seconds: dict[str, float]  # idle seconds by the host span over them

    def breakdown(self) -> dict:
        fam: dict[str, float] = {}
        for n, s in self.op_seconds.items():
            fam[op_family(n)] = fam.get(op_family(n), 0.0) + s
        top = sorted(fam.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gap_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in top], "idle_gaps": [[n, s] for n, s in gaps]}


def _self_seconds(ops: list[tuple[int, int, str]], into: dict[str, float]) -> None:
    """Add to each op the time in which it is the innermost op running: of
    the ops running at a moment, the one that started last (of equal starts,
    the shorter).  Each moment of device time goes to one op, so the ops'
    seconds add up to the busy union whether they nest (a ``while`` loop and
    its body) or overlap one another."""
    ops = sorted(ops)
    bounds = sorted({t for s, e, _ in ops for t in (s, e)})
    running: list[tuple[int, int, str]] = []  # (-start, end, name); innermost first
    i = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while i < len(ops) and ops[i][0] <= t0:
            s, e, name = ops[i]
            heapq.heappush(running, (-s, e, name))
            i += 1
        while running and running[0][1] <= t0:
            heapq.heappop(running)
        if running:
            name = running[0][2]
            into[name] = into.get(name, 0.0) + (t1 - t0) * 1e-9


def reduce_events(events, window_s: float) -> TraceSummary:
    """Busy union per chip, op self times, and idle gaps on chip 0 labelled
    by the innermost ``bench.*`` host span that covers most of each gap."""
    per_chip: dict[str, list[tuple[int, int, str]]] = {}
    ops: dict[str, float] = {}
    spans: list[tuple[int, int, str]] = []
    for plane, line, name, start, dur in events:
        if DEVICE_PLANE.match(plane) and line == OPS_LINE:
            per_chip.setdefault(plane, []).append((start, start + dur, op_name(name)))
        elif plane.startswith("/host:") and name.startswith(SPAN_PREFIX):
            spans.append((start, start + dur, name))
    if not per_chip:
        return TraceSummary(window_s, 0.0, 0, ops, {})
    for chip_ops in per_chip.values():
        _self_seconds(chip_ops, ops)
    unions = {p: _union([(s, e) for s, e, _ in iv]) for p, iv in per_chip.items()}
    busy = sum(sum(e - s for s, e in u) for u in unions.values()) * 1e-9 / len(unions)
    first = unions[min(unions, key=lambda p: int(DEVICE_PLANE.match(p).group(1)))]
    gaps: dict[str, float] = {}
    spans.sort()
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    for (_, a), (b, _) in zip(first, first[1:]):
        best, label = 0, "host: no bench span"
        # Spans that start before the gap ends, walked back to the longest
        # span's reach; of equal cover the later start (the inner span) wins.
        i = bisect.bisect_left(starts, b) - 1
        while i >= 0 and starts[i] >= a - longest:
            s, e, n = spans[i]
            cover = min(e, b) - max(s, a)
            if cover > best:
                best, label = cover, n
            i -= 1
        gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
    return TraceSummary(window_s, busy, len(unions), ops, gaps)


def reduce_dir(trace_dir: Path, traced: tuple[float, float]) -> TraceSummary:
    return reduce_events(load_events(trace_dir), traced[1] - traced[0])


def idle_share_pct(trace: TraceSummary | None) -> float | None:
    """Per-layer ``device.idle_share.*``: 1 - busy / window, in percent, as
    read: busy time past the window (a fault of the trace or of the
    reduction) shows as a share below 0, not as 0."""
    if trace is None or trace.chips == 0 or trace.window_s <= 0:
        return None
    return (1.0 - trace.busy_s / trace.window_s) * 100.0
