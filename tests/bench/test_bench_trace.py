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


# ------------------------------------------------- device time by named scope

TPU = json.loads((Path(__file__).parent / "data" / "trace_tpu_scopes.json").read_text())


def _layer(h, w):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("moe.experts"):
        h = jnp.tanh(h @ w)
    with jax.named_scope("mla.attention"):
        h = h @ w.T + h
    return h, None


def _loss(ws, x):
    """Two named scopes in a rematerialised scan under ``outer``: the toy
    that ``data/trace_tpu_scopes.json`` recorded on a TPU v5e at ws (2, 1024,
    1024) and x (2048, 1024), float32."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("outer"):
        h, _ = jax.lax.scan(jax.checkpoint(_layer), x, ws)
    return jnp.sum(h * h)


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """A live CPU profile of the jitted ``value_and_grad`` at two shapes (so
    two modules hold ops of one name), its op events put on the TPU plane's
    XLA Ops line, as the chip's trace has them: (events, op paths, file)."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(jax.value_and_grad(_loss))
    shapes = [((2, 64, 64), (32, 64)), ((2, 48, 48), (16, 48))]
    args = [(jnp.full(w, 0.01), jnp.ones(x)) for w, x in shapes]
    for a in args:
        step(*a)[1].block_until_ready()
    d = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(d))
    for _ in range(3):
        for a in args:
            step(*a)[1].block_until_ready()
    jax.profiler.stop_trace()
    path = trace.trace_file(d)
    events = [("/device:TPU:0", trace.OPS_LINE, *e[2:]) for e in trace.load_events(path) if e[5]]
    return events, trace.load_op_paths(path), path


@pytest.fixture(scope="module")
def scoped(cpu_profile):
    events, paths, _ = cpu_profile
    return trace.reduce_events(events, 1.0, paths)


def _tpu_summary():
    paths = {(m, o): p for m, o, p in TPU["op_paths"]}
    return trace.reduce_events([tuple(e) for e in TPU["events"]], TPU["window_s"], paths)


@pytest.mark.parametrize("source", ["cpu_profile", "tpu_recorded"])
@pytest.mark.parametrize("scope", ["moe.experts", "mla.attention", "outer"])  # outer: as jvp(outer)
def test_a_scope_counts_its_forward_and_backward_ops(scoped, source, scope):
    s = scoped if source == "cpu_profile" else _tpu_summary()
    assert s.scope_seconds(scope) > 0
    ops = {k: sec for k, sec in s.module_op_seconds.items()
           if k in s.op_paths and scope in trace.scopes(s.op_paths[k])}
    backward = sum(sec for k, sec in ops.items() if "transpose(" in s.op_paths[k])
    assert backward > 0 and sum(ops.values()) - backward > 0


@pytest.mark.parametrize("source", ["cpu_profile", "tpu_recorded"])
def test_scoped_seconds_add_up_to_no_more_than_busy(scoped, source):
    s = scoped if source == "cpu_profile" else _tpu_summary()
    parts = s.scope_seconds("moe.experts") + s.scope_seconds("mla.attention")
    assert parts <= s.scope_seconds("outer") * (1 + 1e-9)  # a scope holds the scopes inside it
    assert s.scope_seconds("outer") <= s.busy_s * (1 + 1e-9)
    assert 0 < s.scope_coverage <= 1
    assert sum(s.module_op_seconds.values()) == pytest.approx(s.busy_s * s.chips)


def test_the_chips_ops_take_their_module_from_the_modules_line():
    s = _tpu_summary()
    assert {m for m, _ in s.module_op_seconds} == {"jit_f(17446593598162206174)"}
    # the recorded step's async copies and slices carry no op_name
    assert 0.85 < s.scope_coverage < 0.95


def test_the_same_op_name_in_two_modules_stays_apart(scoped):
    modules = {m for m, _ in scoped.module_op_seconds}
    assert len(modules) == 2 and all(m.startswith("jit__loss(") for m in modules)
    twice = {op for m, op in scoped.module_op_seconds
             if all((n, op) in scoped.module_op_seconds for n in modules)}
    assert twice
    for op in twice:
        assert scoped.op_seconds[op] == pytest.approx(
            sum(sec for (m, o), sec in scoped.module_op_seconds.items() if o == op))


def _without_metadata_plane(path: Path) -> bytes:
    """The trace file with its ``/host:metadata`` plane left out."""
    out = bytearray()
    for n, value in trace._fields(path.read_bytes()):
        if n == 1 and bytes(trace._field(trace._fields(value), 2)).decode() == trace.METADATA_PLANE:
            continue
        out += _len_field(n, value)  # every field of an XSpace is length-delimited
    return bytes(out)


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _len_field(number: int, value) -> bytes:
    return _varint(number << 3 | 2) + _varint(len(value)) + bytes(value)


def test_a_trace_without_its_metadata_reads_no_scope(cpu_profile, tmp_path):
    events, paths, path = cpu_profile
    bare = tmp_path / "bare.xplane.pb"
    bare.write_bytes(_without_metadata_plane(path))
    assert paths and trace.load_op_paths(bare) == {}
    assert trace.load_op_paths(None) == {}
    s = trace.reduce_events(events, 1.0, trace.load_op_paths(bare))
    assert s.busy_s > 0 and s.scope_seconds("moe.experts") == 0 and s.scope_coverage == 0


def test_a_module_missing_from_the_metadata_puts_its_ops_in_no_scope(cpu_profile, scoped):
    events, paths, _ = cpu_profile
    gone = sorted({m for m, _ in scoped.module_op_seconds})[0]
    s = trace.reduce_events(events, 1.0, {k: p for k, p in paths.items() if k[0] != gone})
    assert 0 < s.scope_seconds("moe.experts") < scoped.scope_seconds("moe.experts")
    assert s.scope_coverage < scoped.scope_coverage


def _hlo_instruction(name: str, op_name: str = "", called: tuple = ()) -> bytes:
    meta = _len_field(2, op_name.encode()) if op_name else b""
    ids = b"".join(_varint(c) for c in called)
    return (_len_field(1, name.encode()) + _len_field(2, name.split(".")[0].encode())
            + _len_field(7, meta) + (_len_field(38, ids) if called else b""))


def test_a_fusion_without_metadata_takes_the_scopes_its_parts_share():
    fused = _varint(5 << 3) + _varint(1) + b"".join(_len_field(2, i) for i in (
        _hlo_instruction("param_0"),
        _hlo_instruction("mul.1", "jit(f)/transpose(jvp(a))/b/mul"),
        _hlo_instruction("sub.2", "jit(f)/transpose(jvp(a))/b/c/sub")))
    entry = _varint(5 << 3) + _varint(2) + b"".join(_len_field(2, i) for i in (
        _hlo_instruction("fusion.3", "", (1,)),
        _hlo_instruction("convert.4"),
        _hlo_instruction("dot.5", "jit(f)/a/dot_general")))
    proto = _len_field(1, _len_field(3, fused) + _len_field(3, entry))
    names = trace._op_names(proto)
    assert names["fusion.3"] == "jit(f)/transpose(jvp(a))/b/fusion"
    assert "convert.4" not in names and "param_0" not in names
    assert trace.scopes(names["fusion.3"]) == {"f", "a", "b"}
    assert trace.scopes(names["dot.5"]) == {"f", "a"}  # the op's own name is no scope
