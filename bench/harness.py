"""The benchmark's harness: find a cell's files by name, check the chip,
drive the cell's kind, read its per-layer metrics, print the result line.

A kind module (``bench/kinds/<kind>.py``) exposes ``run(ctx) -> KindResult``.
It builds the system under test, warms every shape its traffic uses, calls
``ctx.open_window()`` when the measured window starts and
``ctx.close_window()`` when it ends, and then decides ``correct`` against its
plain reference.  A per-layer reader (``bench/metrics/<metric>.py``) exposes
``read(rec) -> float | None`` over the :class:`RunRecord` of a traced run;
``None`` means it found nothing to read, and the metric is left out.  A
reader of a kernel's device time takes ``rec.trace.scope_seconds(name)``, the
self seconds of the ops the program traced under ``jax.named_scope(name)``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: Fixed scratch directories inside the checkout (bench/.gitignore lists them).
CACHE = BENCH / ".cache"
STORE_ROOT = CACHE / "store"
TRACE_DIR = CACHE / "trace"


class BenchError(Exception):
    """A cell, file or device the benchmark cannot run with."""


# ---------------------------------------------------------------- files


def load_json(path: Path) -> Any:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


#: a configuration file's own blocks; every other top-level key of a file
#: with no ``hf`` block is a key of the published config, under its
#: published name (as a catalog model's file holds them)
CONFIG_BLOCKS = ("name", "source", "deployment", "program_arch", "published", "reduced", "assumed",
                 "architecture", "precision", "program", "init", "train", "store", "limits")


def load_config(path: Path) -> dict:
    """A configuration file, with its published keys as ``hf``: the file's
    ``hf`` block or, where it has none, its top-level keys but its own
    blocks."""
    conf = load_json(path)
    if "hf" not in conf:
        conf["hf"] = {k: v for k, v in conf.items() if k not in CONFIG_BLOCKS}
    return conf


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    name = "bench_file_" + re.sub(r"\W", "_", str(path.resolve().with_suffix("")))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    kind: str
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: dict | None = None, bench_dir: Path = BENCH) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics, all
    found by name under ``bench_dir``."""
    spec = spec if spec is not None else load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name!r} names unknown config {w['config']!r}")
    config = load_config(bench_dir.parent / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic, traffic["kind"], e2e, layer)


def kind_module(kind: str, bench_dir: Path = BENCH):
    return load_module(bench_dir / "kinds" / f"{kind}.py")


def reader_module(metric: str, bench_dir: Path = BENCH):
    return load_module(bench_dir / "metrics" / f"{metric}.py")


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# --------------------------------------------------------------- device


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``$JAX_COMPILATION_CACHE_DIR`` says); every compile is kept."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def require_program() -> None:
    """The system under test (``src/repro``) has to be in the checkout."""
    if importlib.util.find_spec("repro") is None:
        raise BenchError(f"the program under test is missing: no package 'repro' under {ROOT / 'src'}")


def require_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX found {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def warm_host_memory(nbytes: int, chunk: int = 1 << 30) -> None:
    """Write ``nbytes`` of host memory at once and free it again.

    The first process on a fresh machine pays for the first touch of every
    page of host memory it uses; the runs after it do not.  A traffic file
    that names ``warm_host_bytes`` has that paid in set-up, so a window that
    moves gigabytes on the host reads the same on a fresh machine as later.
    """
    import numpy as np

    held = []
    for _ in range(max(0, nbytes) // chunk):
        a = np.empty(chunk, np.uint8)
        a.fill(1)
        held.append(a)
    del held


def fresh_dir(path: Path) -> Path:
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    return path


# ---------------------------------------------------------- run context


@dataclasses.dataclass
class Check:
    """One number compared with its limit; the check passes at or below it."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class KindResult:
    metrics: dict[str, float]  # end-to-end metrics but setup_s
    attempted: int
    failed: int
    checks: list[Check]
    memory_peak_bytes: int
    counters: dict[str, float]  # window deltas and shapes, for the readers
    notes: list[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader sees of one run."""

    cell: str
    config: dict
    traffic: dict
    peaks: dict
    window_s: float
    spans: list[tuple[str, float, float]]
    counters: dict[str, float]
    trace: Any  # bench.trace.TraceSummary or None

    def spans_named(self, name: str) -> list[float]:
        """Durations (s) of this run's spans called ``name`` in the window."""
        return [e - s for n, s, e in self.spans if n == name]


class RunContext:
    """Handed to a kind: the cell, the seed, the window's length, the
    span recorder and the window/trace switches."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_process: float, store_root: Path = STORE_ROOT,
                 trace_dir: Path = TRACE_DIR):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.config, self.traffic = cell.config, cell.traffic
        self.t_process = t_process
        self.store_root = store_root
        self.trace_dir = trace_dir
        self.spans: list[tuple[str, float, float]] = []
        self.window: tuple[float, float] | None = None
        self.traced: tuple[float, float] | None = None
        self._t_open: float | None = None
        self._tracing = False
        self._compiles = 0
        self.compiles_in_window = 0
        self.compiled_in_window: list[str] = []
        _watch_compiles(self)

    # ----------------------------------------------------------- spans

    @contextlib.contextmanager
    def span(self, name: str):
        """Host span around a call into the program, kept in memory and, in
        a traced run, written into the profiler's trace on the same clock."""
        ann = None
        if self._tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    # ---------------------------------------------------------- window

    def open_window(self) -> float:
        """Start of the measured window (and, traced, of the trace)."""
        if self.trace:
            import jax

            fresh_dir(self.trace_dir)
            jax.profiler.start_trace(str(self.trace_dir))
            self._tracing = True
        self._t_open = time.perf_counter()
        self._compiles_at_open = self._compiles
        return self._t_open

    def close_window(self) -> float:
        t = time.perf_counter()
        self.window = (self._t_open, t)
        self.compiles_in_window = self._compiles - self._compiles_at_open
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False
            self.traced = (self._t_open, t)
        return t

    def window_spans(self) -> list[tuple[str, float, float]]:
        lo, hi = self.window
        return [s for s in self.spans if lo <= s[1] <= hi]


def _watch_compiles(ctx: RunContext) -> None:
    """Count XLA compiles (cache hits included) so a compile inside the
    window is reported, never hidden."""
    import jax

    def on_event(event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            ctx._compiles += 1
            if ctx._t_open is not None and ctx.window is None:
                ctx.compiled_in_window.append(str(kw.get("fun_name", "?")))

    jax.monitoring.register_event_duration_secs_listener(on_event)


# ------------------------------------------------------------------ run


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_process: float, bench_dir: Path = BENCH, **ctx_kw) -> dict:
    from bench import trace as trace_mod

    kind = kind_module(cell.kind, bench_dir)
    ctx = RunContext(cell, seed, seconds, trace, t_process, **ctx_kw)
    res: KindResult = kind.run(ctx)
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else {}
    t_open, t_close = ctx.window
    window_s = t_close - t_open
    out = {
        "correct": all(c.ok for c in res.checks),
        "attempted": int(res.attempted),
        "failed": int(res.failed),
        "metrics": {},
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(res.memory_peak_bytes),
        },
    }
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    notes = list(res.notes)
    notes.append(f"window {window_s:.3f}s; compiles in window {ctx.compiles_in_window} "
                 f"{sorted(set(ctx.compiled_in_window))}")
    if not trace:
        values = dict(res.metrics, setup_s=t_open - t_process)
        for m in cell.end_to_end:
            if m["name"] in values:
                out["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        summary = None
        if ctx.traced is not None:
            summary = trace_mod.reduce_dir(ctx.trace_dir, ctx.traced)
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        rec = RunRecord(cell.name, cell.config, cell.traffic, peaks, window_s,
                        ctx.window_spans(), res.counters, summary)
        for m in cell.per_layer:
            v = reader_module(m["name"], bench_dir).read(rec)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": units[m["name"]]}
        if summary is not None:
            out["device"]["busy_s"] = summary.busy_s
            out["device"]["window_s"] = summary.window_s
            out["breakdown"] = summary.breakdown()
            notes.append(f"device op seconds with an op_name (named scopes can count them): "
                         f"{summary.scope_coverage * 100:.3f}%")
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in res.checks}
    out["_notes"] = notes
    return out


def emit(result: dict) -> None:
    """Notes and every compared number beside its limit as the last lines
    on stderr; the result line as the last line on stdout."""
    notes = result.pop("_notes", [])
    for n in notes:
        print(f"bench: {n}", file=sys.stderr)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
