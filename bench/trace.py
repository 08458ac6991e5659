"""Reduction of a profiler trace to device busy time, top device operations,
device time by the program's named scopes, and idle gaps attributed to the
benchmark's host spans.

Events are plain ``(plane, line, name, start_ns, dur_ns[, module])`` tuples,
so the reduction is tested on a small recorded trace without a chip.  Device
events are those on ``/device:TPU:<n>`` planes, on the ``XLA Ops`` line
(one event per HLO operation that ran); host spans are the ``bench.*``
annotations a :class:`bench.harness.RunContext` writes into the trace.

An op's module is the ``XLA Modules`` event of its chip that covers it (the
TPU's trace), or its own ``hlo_module`` and ``program_id`` stats (the CPU's),
named ``<module>(<program id>)`` either way.  The trace's ``/host:metadata``
plane holds each module's optimised HLO under the stat ``Hlo Proto``; every
instruction's ``metadata.op_name`` there is the path of ``jax.named_scope``
names and transforms it was traced under, such as
``jit(step)/transpose(jvp(mlp))/while/body/dot_general``.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
SPAN_PREFIX = "bench."
TOP = 10
#: a transform's wrapper round a scope name, as ``transpose(jvp(mlp))``
WRAPPED = re.compile(r"[^()]*\((.*)\)")


def trace_file(trace_dir: Path) -> Path | None:
    """The newest ``.xplane.pb`` under ``trace_dir``, if any."""
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


def load_events(path: Path | None) -> list[tuple[str, str, str, int, int, str]]:
    """Every event of the trace file ``path``, with the module that a host
    event's own stats name (``hlo_module`` and ``program_id``: a CPU op's),
    else ''; a chip's ops take theirs from its ``XLA Modules`` line."""
    from jax.profiler import ProfileData

    if path is None:
        return []
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        device = DEVICE_PLANE.match(plane.name) is not None  # a chip's ops name no module
        for line in plane.lines:
            for ev in line.events:
                stats = {} if device else dict(ev.stats)
                module = (f"{stats['hlo_module']}({stats['program_id']})"
                          if "hlo_module" in stats and "program_id" in stats else "")
                out.append((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns), module))
    return out


# ------------------------------------------- op names from the HLO metadata


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf) -> list[tuple[int, object]]:
    """One protobuf message's ``(field number, value)`` pairs in wire order:
    a varint as an int, a length-delimited field as a memoryview, a fixed
    one as its raw bytes."""
    buf = memoryview(buf)
    out, i = [], 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        out.append((key >> 3, value))
    return out


def _field(msg, number: int, default=b""):
    """The last value of field ``number`` in a decoded message."""
    return next((v for n, v in reversed(msg) if n == number), default)


def _packed(values) -> list[int]:
    """A repeated int64 field's values, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def _op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``metadata.op_name`` of one ``xla.HloProto``
    (hlo_module 1 > computations 3 (id 5) > instructions 2 > name 1,
    opcode 2, metadata 7 > op_name 2, called_computation_ids 38).

    A fusion whose own metadata is empty (as XLA leaves a multi-output
    fusion's) takes the scopes its fused instructions share: their op_names'
    longest common scope path, with its opcode as the op.  An instruction
    with no op_name and nothing named inside it gets none."""
    comps: dict[int, list[tuple[str, str, str, list[int]]]] = {}
    for n, comp in _fields(_field(_fields(hlo_proto), 1)):
        if n != 3:
            continue
        comp = _fields(comp)
        comps[_field(comp, 5, 0)] = [
            (bytes(_field(f, 1)).decode(), bytes(_field(f, 2)).decode(),
             bytes(_field(_fields(_field(f, 7)), 2)).decode(), _packed(v for k, v in f if k == 38))
            for f in (_fields(v) for m, v in comp if m == 2)]

    def inner_path(opcode: str, called: list[int]) -> str:
        paths = [p for c in called for _, op, own, sub in comps.get(c, []) if (p := own or inner_path(op, sub))]
        if not paths:
            return ""
        common = paths[0].split("/")[:-1]
        for p in paths[1:]:
            parts = p.split("/")[:-1]
            k = 0
            while k < min(len(common), len(parts)) and common[k] == parts[k]:
                k += 1
            common = common[:k]
        return "/".join(common + [opcode])

    out = {}
    for instrs in comps.values():
        for name, opcode, own, called in instrs:
            if path := own or inner_path(opcode, called):
                out[name] = path
    return out


def load_op_paths(path: Path | None) -> dict[tuple[str, str], str]:
    """(module, op) -> ``op_name`` for every instruction of every module in
    the trace file's ``/host:metadata`` plane (xplane.proto: XSpace planes 1;
    XPlane name 2, event_metadata 4, stat_metadata 5; map entries key 1,
    value 2; XEventMetadata name 2, stats 5; XStat metadata_id 1,
    bytes_value 6).  A trace without the plane gives nothing."""
    if path is None:
        return {}
    out = {}
    for n, plane in _fields(Path(path).read_bytes()):
        if n != 1:
            continue
        plane = _fields(plane)
        if bytes(_field(plane, 2)).decode() != METADATA_PLANE:
            continue
        stat_names = {}
        for m, entry in plane:
            if m == 5:
                stat = _fields(_field(_fields(entry), 2))
                stat_names[_field(stat, 1, 0)] = bytes(_field(stat, 2)).decode()
        for m, entry in plane:
            if m != 4:
                continue
            meta = _fields(_field(_fields(entry), 2))
            module = bytes(_field(meta, 2)).decode()
            for k, stat in meta:
                stat = _fields(stat) if k == 5 else ()
                if stat and stat_names.get(_field(stat, 1, 0)) == HLO_PROTO_STAT:
                    for op, op_name in _op_names(_field(stat, 6)).items():
                        out[(module, op)] = op_name
    return out


def scopes(op_path: str) -> set[str]:
    """The scope names an ``op_name`` path holds: every component but the
    last (the op itself), each with its transforms' wrappers taken off
    (``transpose(jvp(mlp))`` -> ``mlp``), so forward, rematerialised and
    backward ops share their scope's name."""
    out = set()
    for part in op_path.split("/")[:-1]:
        while m := WRAPPED.fullmatch(part):
            part = m.group(1)
        if part:
            out.add(part)
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
    #: device self seconds per (module, op), all chips; module '' where unknown
    module_op_seconds: dict[tuple[str, str], float] = dataclasses.field(default_factory=dict)
    #: (module, op) -> its HLO ``metadata.op_name``, for the ops that have one
    op_paths: dict[tuple[str, str], str] = dataclasses.field(default_factory=dict)

    def scope_seconds(self, name: str) -> float:
        """Device self seconds of the ops traced under ``jax.named_scope(name)``
        (forward, rematerialised and backward alike; a scope holds the
        scopes inside it), averaged over the chips traced as ``busy_s`` is."""
        if self.chips == 0:
            return 0.0
        return sum(s for k, s in self.module_op_seconds.items()
                   if k in self.op_paths and name in scopes(self.op_paths[k])) / self.chips

    @property
    def scope_coverage(self) -> float:
        """The share of device op seconds whose op has an ``op_name``, so
        that named scopes can account for it (0 with no device ops)."""
        total = sum(self.module_op_seconds.values())
        covered = sum(s for k, s in self.module_op_seconds.items() if k in self.op_paths)
        return covered / total if total > 0 else 0.0

    def breakdown(self) -> dict:
        fam: dict[str, float] = {}
        for n, s in self.op_seconds.items():
            fam[op_family(n)] = fam.get(op_family(n), 0.0) + s
        top = sorted(fam.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gap_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in top], "idle_gaps": [[n, s] for n, s in gaps]}


def _self_seconds(ops: list[tuple[int, int, object]], into: dict) -> None:
    """Add to each op the time in which it is the innermost op running: of
    the ops running at a moment, the one that started last (of equal starts,
    the shorter).  Each moment of device time goes to one op, so the ops'
    seconds add up to the busy union whether they nest (a ``while`` loop and
    its body) or overlap one another."""
    ops = sorted(ops)
    bounds = sorted({t for s, e, _ in ops for t in (s, e)})
    running: list[tuple[int, int, object]] = []  # (-start, end, name); innermost first
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


def _module_at(modules: list[tuple[int, int, str]], starts: list[int], t: int) -> str:
    """The module whose run covers ``t`` ('' where none does)."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][2] if i >= 0 and t < modules[i][1] else ""


def reduce_events(events, window_s: float, op_paths: dict | None = None) -> TraceSummary:
    """Busy union per chip, op self times per (module, op), and idle gaps on
    chip 0 labelled by the innermost ``bench.*`` host span that covers most
    of each gap; ``op_paths`` is :func:`load_op_paths`'s."""
    per_chip: dict[str, list[tuple[int, int, str, str]]] = {}
    modules: dict[str, list[tuple[int, int, str]]] = {}
    spans: list[tuple[int, int, str]] = []
    for plane, line, name, start, dur, *module in events:
        if DEVICE_PLANE.match(plane) and line == OPS_LINE:
            per_chip.setdefault(plane, []).append((start, start + dur, op_name(name), module[0] if module else ""))
        elif DEVICE_PLANE.match(plane) and line == MODULES_LINE:
            modules.setdefault(plane, []).append((start, start + dur, name))
        elif plane.startswith("/host:") and name.startswith(SPAN_PREFIX):
            spans.append((start, start + dur, name))
    if not per_chip:
        return TraceSummary(window_s, 0.0, 0, {}, {})
    keyed: dict[tuple[str, str], float] = {}
    for plane, chip_ops in per_chip.items():
        runs = sorted(modules.get(plane, []))
        starts = [s for s, _, _ in runs]
        _self_seconds([(s, e, (m or _module_at(runs, starts, s), op)) for s, e, op, m in chip_ops], keyed)
    ops: dict[str, float] = {}
    for (_, op), sec in keyed.items():
        ops[op] = ops.get(op, 0.0) + sec
    unions = {p: _union([(s, e) for s, e, *_ in iv]) for p, iv in per_chip.items()}
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
    paths = {k: op_paths[k] for k in keyed if k in op_paths} if op_paths else {}
    return TraceSummary(window_s, busy, len(unions), ops, gaps, keyed, paths)


def reduce_dir(trace_dir: Path, traced: tuple[float, float]) -> TraceSummary:
    path = trace_file(trace_dir)
    return reduce_events(load_events(path), traced[1] - traced[0], load_op_paths(path))


def idle_share_pct(trace: TraceSummary | None) -> float | None:
    """Per-layer ``device.idle_share.*``: 1 - busy / window, in percent, as
    read: busy time past the window (a fault of the trace or of the
    reduction) shows as a share below 0, not as 0."""
    if trace is None or trace.chips == 0 or trace.window_s <= 0:
        return None
    return (1.0 - trace.busy_s / trace.window_s) * 100.0
