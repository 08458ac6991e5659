"""Smoke run of the two-level store's clients on a TPU, at qwen3-8b widths.

    python chip_smoke.py              # one chip: phases 0-3
    python chip_smoke.py --chips 4    # four chips: the sharded train step only
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal, reduced sizes

Phases, in order; any failure raises and the script exits non-zero:

0. Device: the compile cache is set up, then the first device must be a
   TPU (there is no CPU fallback; ``--tiny`` alone runs anywhere).
1. Store + trainer: ``run_training`` on qwen3-8b widths cut to 2 layers
   and one chip's share (1/8) of the vocabulary, batch 4 x 2048, 4 steps,
   corpus streamed through a fresh ``TwoLevelStore``, one async
   checkpoint at step 4, restored bit-identically from a reopened store.
2. Serving plane: ``SessionScheduler`` over 8 prefix-sharing sessions at
   4 layers, with HBM/host budgets tight enough that demotions,
   evictions and resumes all fire; tokens must equal an unbounded control.
3. Kernel: the Pallas ``tiered_decode`` kernel, compiled natively, against
   the XLA path on a cache built by the single-batch tiered loop.

``--chips 4`` runs the phase-1 train step on a 1x4 mesh against the same
steps on one chip, then restores checkpoints across the two layouts.

The last stdout line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: Fixed scratch root inside the checkout, wiped at start (never resumed).
STORE_ROOT = ROOT / ".smoke_store"
#: bf16 tolerance the kernel tests use (tests/test_kv_offload.py).
KERNEL_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    train_batch: int
    train_seq: int
    sessions: int
    prompt: int
    shared: int
    new_tokens: int
    window: int
    page: int
    max_batch: int
    kernel_batch: int
    kernel_tokens: int


FULL = Sizes(train_batch=4, train_seq=2048, sessions=8, prompt=1024, shared=512,
             new_tokens=32, window=256, page=128, max_batch=4,
             kernel_batch=2, kernel_tokens=8)
TINY = Sizes(train_batch=2, train_seq=64, sessions=8, prompt=48, shared=24,
             new_tokens=8, window=16, page=8, max_batch=4,
             kernel_batch=2, kernel_tokens=4)


def log(msg: str) -> None:
    print(msg, flush=True)


def train_config(tiny: bool):
    from repro.configs import get_config, get_reduced

    if tiny:
        return get_reduced("qwen3_8b")
    # Published widths; depth 2 because 4 layers do not fit one chip's HBM
    # even with the state donated, and the vocabulary is one chip's share of
    # a vocabulary split over 8 chips (151,936 / 8).
    return dataclasses.replace(get_config("qwen3_8b"), n_layers=2, vocab=18_992)


def serve_config(tiny: bool):
    from repro.configs import get_config, get_reduced

    base = get_reduced("qwen3_8b") if tiny else get_config("qwen3_8b")
    # float32 end to end: the control comparison is exact token equality.
    return dataclasses.replace(base, n_layers=4, scan_layers=False, dtype="float32")


def fresh_dir(path: Path) -> str:
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    return str(path)


def tree_bytes(tree) -> int:
    import jax

    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


def assert_trees_identical(got, want, what: str) -> int:
    """Leaf-by-leaf exact equality (dtype, shape, bits); returns leaf count."""
    import jax
    import numpy as np

    g_leaves, g_def = jax.tree_util.tree_flatten_with_path(got)
    w_leaves, w_def = jax.tree_util.tree_flatten_with_path(want)
    if g_def != w_def:
        raise AssertionError(f"{what}: tree structure differs: {g_def} vs {w_def}")
    for (path, g), (_, w) in zip(g_leaves, w_leaves):
        g, w = np.asarray(g), np.asarray(w)
        if g.dtype != w.dtype or g.shape != w.shape or not np.array_equal(
            np.ascontiguousarray(g).reshape(-1).view(np.uint8),
            np.ascontiguousarray(w).reshape(-1).view(np.uint8),
        ):
            raise AssertionError(f"{what}: leaf {jax.tree_util.keystr(path)} differs")
    return len(g_leaves)


# ------------------------------------------------------------------ phase 0


def phase_device(tiny: bool, chips: int):
    import jax

    devs = jax.devices()
    dev = devs[0]
    if not tiny and dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform {dev.platform!r}")
    if not tiny and len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, found {len(devs)}")
    log(f"phase 0: device {dev.device_kind!r} platform {dev.platform} count {len(devs)}")
    return dev, len(devs)


# ------------------------------------------------------------------ phase 1


def phase_train(tiny: bool, sz: Sizes) -> None:
    import jax
    import numpy as np

    from repro.configs import make_model
    from repro.core.store import TwoLevelStore
    from repro.launch.steps import init_state
    from repro.launch.train import run_training
    from repro.optim.adamw import AdamW
    from repro.runtime.checkpoint import CheckpointManager

    cfg = train_config(tiny)
    root = fresh_dir(STORE_ROOT / "train")
    store_kw = dict(mem_capacity_bytes=256 * 2**20, block_bytes=4 * 2**20)
    stamps: list[float] = []
    t_start = time.perf_counter()
    with TwoLevelStore(root, **store_kw) as store:
        res = run_training(
            cfg, store, total_steps=4, global_batch=sz.train_batch,
            seq_len=sz.train_seq, ckpt_every=4, ckpt_mode="async",
            on_step=lambda s, m: stamps.append(time.perf_counter()),
        )
    if res.steps_run != 4 or len(res.losses) != 4:
        raise AssertionError(f"phase 1: ran {res.steps_run} steps, want 4")
    if not all(np.isfinite(res.losses)):
        raise AssertionError(f"phase 1: non-finite loss {res.losses}")
    times = np.diff([t_start] + stamps)
    ckpt_bytes = tree_bytes(res.state)
    log(f"phase 1: {cfg.name} d_model {cfg.d_model} layers {cfg.n_layers} vocab {cfg.vocab} "
        f"batch {sz.train_batch}x{sz.train_seq}; losses {[float(x) for x in res.losses]}")
    log(f"phase 1: first step (set-up and compile included) {times[0]:.3f}s; "
        f"steps 2-4 {[round(float(t), 4) for t in times[1:]]}s")
    log(f"phase 1: checkpoint {ckpt_bytes} bytes; save critical path "
        f"{res.stalls['ckpt_save_critical_s']:.3f}s; data stall "
        f"{res.stalls['data_stall_total_s']:.3f}s; "
        f"free disk {shutil.disk_usage(root).free / 2**30:.1f} GiB")

    # Restore from a reopened store on the same root into a fresh template.
    model = make_model(cfg)
    template, _ = init_state(model, cfg, AdamW(), jax.random.PRNGKey(1), abstract=True)
    template["pipeline"] = {"epoch": np.int64(0), "step": np.int64(0)}
    t0 = time.perf_counter()
    with TwoLevelStore(root, **store_kw) as store:
        ckpt = CheckpointManager(store, tag=cfg.name)
        try:
            step, restored = ckpt.restore(template)
        finally:
            ckpt.close()
    restore_s = time.perf_counter() - t0
    if step != 4:
        raise AssertionError(f"phase 1: restored step {step}, want 4")
    n = assert_trees_identical(restored, res.state, "phase 1 restore")
    log(f"phase 1: restored step {step} from the reopened store in {restore_s:.3f}s; "
        f"{n} leaves bit-identical")
    del res, restored
    gc.collect()


# ------------------------------------------------------------------ phase 2


def _prompts(cfg, sz: Sizes, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab, size=sz.shared)
    return [
        np.concatenate([shared, rng.integers(1, cfg.vocab, size=sz.prompt - sz.shared)])
        .astype(np.int32)
        for _ in range(sz.sessions)
    ]


def phase_serve(tiny: bool, sz: Sizes):
    import jax
    import jax.numpy as jnp

    from repro.configs import make_model
    from repro.core.arbiter import MemoryArbiter
    from repro.core.store import TwoLevelStore
    from repro.kernels import ops
    from repro.nn.module import init_with_axes
    from repro.serving import SessionScheduler
    from repro.serving import scheduler as sched_mod

    cfg = serve_config(tiny)
    model = make_model(cfg)
    params, _ = init_with_axes(model.init, jax.random.PRNGKey(0), dtype=jnp.float32)
    log(f"phase 2: {cfg.name} layers {cfg.n_layers} vocab {cfg.vocab}, "
        f"f32 params {tree_bytes(params)} bytes")
    prompts = _prompts(cfg, sz)
    max_len = sz.prompt + sz.new_tokens + 1
    per_session = 2 * cfg.n_kv_heads * cfg.resolved_head_dim * max_len * 4 * cfg.n_layers
    total_kv = sz.sessions * per_session
    host_budget, hbm_budget = total_kv // 6, total_kv // 24  # as serve_sessions
    kw = dict(window=sz.window, page=sz.page, max_batch=sz.max_batch, dtype=jnp.float32)
    max_steps = 50 * sz.sessions * sz.new_tokens
    kernel_calls0 = ops._tiered_decode_jit._cache_size()

    t0 = time.perf_counter()
    with TwoLevelStore(fresh_dir(STORE_ROOT / "serve"), mem_capacity_bytes=64 << 20,
                       block_bytes=1 << 20, stripe_bytes=256 << 10) as store:
        arbiter = MemoryArbiter(total_bytes=host_budget + hbm_budget)
        sched = SessionScheduler(model, cfg, params, store=store, arbiter=arbiter,
                                 hbm_bytes=hbm_budget, host_bytes=host_budget, **kw)
        try:
            sids = [sched.submit(p, sz.new_tokens) for p in prompts]
            rep = sched.run(max_steps=max_steps)
            tokens = [sched.session_tokens(s) for s in sids]
        finally:
            sched.close()
    bounded_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ctrl = SessionScheduler(model, cfg, params, **kw)
    try:
        ctrl_sids = [ctrl.submit(p, sz.new_tokens) for p in prompts]
        ctrl_rep = ctrl.run(max_steps=max_steps)
        ctrl_tokens = [ctrl.session_tokens(s) for s in ctrl_sids]
    finally:
        ctrl.close()
    control_s = time.perf_counter() - t0

    log(f"phase 2: {rep['retired']}/{rep['sessions']} retired over {rep['steps']} steps; "
        f"demotions {rep['demotions']} evictions {rep['evictions']} resumes {rep['resumes']}; "
        f"dedup {rep['dedup_ratio']:.3f}; ttft p50 {rep['ttft_p50_s']:.3f}s "
        f"p99 {rep['ttft_p99_s']:.3f}s; decode {rep['decode_tok_per_s']:.1f} tok/s; "
        f"bounded run {bounded_s:.1f}s, control {control_s:.1f}s")
    plane = ("Pallas tiered_decode" if ops._tiered_decode_jit._cache_size() > kernel_calls0
             else f"vmapped jnp reference ({sched_mod._batched_attend._cache_size()} "
                  f"compiled group shapes)")
    if rep["retired"] != sz.sessions or ctrl_rep["retired"] != sz.sessions:
        raise AssertionError(f"phase 2: retired {rep['retired']}/{ctrl_rep['retired']} "
                             f"of {sz.sessions}")
    if any(len(t) != sz.new_tokens for t in tokens):
        raise AssertionError("phase 2: a session retired without all its tokens")
    if not (rep["evictions"] > 0 and rep["resumes"] > 0 and rep["demotions"] > 0):
        raise AssertionError("phase 2: budgets did not force evictions, resumes and demotions")
    diverged = [i for i, (a, b) in enumerate(zip(tokens, ctrl_tokens)) if a != b]
    if diverged:
        first = [next(j for j, (x, y) in enumerate(zip(tokens[i], ctrl_tokens[i])) if x != y)
                 for i in diverged]
        raise AssertionError(f"phase 2: sessions {diverged} diverged from the control "
                             f"at token {first}")
    log(f"phase 2: all {sz.sessions} sessions token-identical to the unbounded control")
    return model, cfg, params, plane


# ------------------------------------------------------------------ phase 3


def phase_kernel(model, cfg, params, sz: Sizes, plane: str, tiny: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.launch.steps import tiered_serve_loop
    from repro.serving import TieredKVCache

    rng = np.random.default_rng(1)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (sz.kernel_batch, sz.prompt)), jnp.int32)
    calls0 = ops._tiered_decode_jit._cache_size()
    gen, prefill_s, decode_s, caches = tiered_serve_loop(
        model, cfg, params, prompts, sz.kernel_tokens, window=sz.window, page=sz.page
    )
    tiered_path = ("Pallas tiered_decode" if ops._tiered_decode_jit._cache_size() > calls0
                   else "XLA tiered path")
    log(f"phase 2/3: decode attention: session plane: {plane}; "
        f"single-batch tiered path: {tiered_path}")
    if not tiny and tiered_path != "Pallas tiered_decode":
        raise AssertionError("phase 3: the single-batch tiered path did not run the kernel")
    cache = next(c for c in caches.values() if isinstance(c, TieredKVCache))
    if cache.hot_len == 0 or cache.cold_len == 0:
        raise AssertionError("phase 3: the cache does not hold both tiers")
    q = jnp.asarray(
        rng.standard_normal((sz.kernel_batch, cfg.n_heads, 1, cfg.resolved_head_dim)),
        jnp.float32,
    )
    out_k = cache.attend(q, impl="kernel")
    out_x = cache.attend(q, impl="xla")
    err = float(jnp.max(jnp.abs(out_k.astype(jnp.float32) - out_x.astype(jnp.float32))))

    # The jitted call attend(impl="kernel") made, lowered again with the same
    # operands and static arguments: native means a Mosaic custom call.
    interpret = ops._interpret_default()
    lens = np.asarray([cache.hot_len, cache.cold_len, cache.ring_newest], np.int32)
    hlo = ops._tiered_decode_jit.lower(
        q.astype(cache.dtype), cache.hot_k, cache.hot_v, cache._cold_k_dev,
        cache._cold_v_dev, lens, block_k=cache._block_k, interpret=interpret,
    ).as_text()
    native = "tpu_custom_call" in hlo
    log(f"phase 3: batch {sz.kernel_batch} hot {cache.hot_len} cold {cache.cold_len} "
        f"block_k {cache._block_k}: kernel vs XLA max abs diff {err:.3e} "
        f"(tol {KERNEL_TOL}); interpret={interpret}, tpu_custom_call={native}; "
        f"prefill {prefill_s:.3f}s decode {decode_s:.3f}s for {sz.kernel_tokens} tokens")
    if not np.isfinite(err) or err > KERNEL_TOL:
        raise AssertionError(f"phase 3: kernel differs from the XLA path by {err}")
    if not tiny and (interpret or not native):
        raise AssertionError("phase 3: the kernel ran interpreted, not natively")
    if int(gen.shape[1]) != sz.kernel_tokens + 1:
        raise AssertionError(f"phase 3: generated {gen.shape}")


# --------------------------------------------------------------- --chips 4


def phase_mesh(tiny: bool, sz: Sizes, chips: int) -> None:
    """The phase-1 train step sharded over a 1 x ``chips`` mesh, against the
    same steps on one chip; then checkpoints restored across the layouts."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.configs import make_model
    from repro.core.store import TwoLevelStore
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import (
        batch_shardings, init_state, make_train_step, state_shardings,
    )
    from repro.launch.train import jit_train_step
    from repro.nn.module import axis_rules
    from repro.optim.adamw import AdamW, cosine_warmup
    from repro.runtime.checkpoint import CheckpointManager

    cfg = train_config(tiny)
    model = make_model(cfg)
    # run_training's optimizer for a 4-step run.
    optimizer = AdamW(learning_rate=cosine_warmup(1e-3, 10, 20))
    step_fn = make_train_step(model, cfg, optimizer)
    one = SingleDeviceSharding(jax.devices()[0])

    state, axes = init_state(model, cfg, optimizer, jax.random.PRNGKey(0))
    host0 = jax.device_get(state)
    del state
    rng = np.random.default_rng(0)
    batches = [
        {k: rng.integers(0, cfg.vocab, (sz.train_batch, sz.train_seq)).astype(np.int32)
         for k in ("inputs", "labels")}
        for _ in range(2)
    ]

    def run(step, state, place_batch):
        losses, gnorms, lrs = [], [], []
        for b in batches:
            state, m = step(state, place_batch(b))
            losses.append(float(m["loss"]))
            gnorms.append(float(m["grad_norm"]))
            lrs.append(float(m["lr"]))
        return state, losses, gnorms, lrs

    t0 = time.perf_counter()
    step1 = jit_train_step(model, cfg, optimizer)
    s1, loss1, gn1, lrs = run(step1, jax.device_put(host0, one),
                              lambda b: jax.device_put(b, one))
    host1 = jax.device_get(s1)
    log(f"mesh: one chip, 2 steps in {time.perf_counter() - t0:.3f}s (compile included): "
        f"losses {loss1} grad norms {gn1}")

    mesh = make_local_mesh(n_model=chips)
    tmpl = jax.eval_shape(lambda: host0)
    with mesh, axis_rules(mesh):
        st_sh = state_shardings(tmpl, axes, mesh)
        b_sh = batch_shardings(jax.eval_shape(lambda: batches[0]), mesh)
        step4 = jax.jit(step_fn, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
                        donate_argnums=0)
        t0 = time.perf_counter()
        s4, loss4, gn4, _ = run(step4, jax.device_put(host0, st_sh),
                                lambda b: jax.device_put(b, b_sh))
        host4 = jax.device_get(s4)
    log(f"mesh: {dict(mesh.shape)} mesh, 2 steps in {time.perf_counter() - t0:.3f}s "
        f"(compile included): losses {loss4} grad norms {gn4}")

    # Losses and grad norms: bf16 compute, with partial sums reduced in
    # another order across chips.  Params: AdamW's normalised step is at
    # most ~1 per weight for the first two steps (1.0004 at b1=0.9,
    # b2=0.95), so the layouts differ by at most twice the summed lr; and
    # since they mostly agree on each update's sign, the total disagreement
    # must stay a small fraction of the total update.
    np.testing.assert_allclose(loss4, loss1, rtol=KERNEL_TOL)
    np.testing.assert_allclose(gn4, gn1, rtol=KERNEL_TOL)
    bound = 2 * sum(lrs) * 1.01 + 1e-6
    worst = disagree = moved = 0.0
    for p0, p1, p4 in zip(*(jax.tree_util.tree_leaves(h["params"]) for h in (host0, host1, host4))):
        d = np.abs(p4.astype(np.float64) - p1)
        worst = max(worst, float(d.max()))
        disagree += float(d.sum())
        moved += float(np.abs(p1.astype(np.float64) - p0).sum())
    log(f"mesh: params max |4-chip - 1-chip| {worst:.3e} (bound {bound:.3e}); "
        f"mean disagreement / mean update {disagree / moved:.4f}")
    if worst > bound or disagree > 0.1 * moved:
        raise AssertionError("mesh: sharded params diverge from the one-chip run")

    # Checkpoints across layouts, each through a fresh store: the one-chip
    # state restored onto the mesh, the 4-chip state onto one chip.
    def through_store(state, shardings, want, what):
        root = fresh_dir(STORE_ROOT / "mesh")
        # Memory tier sized to hold the checkpoint: phase 1 already proved the
        # PFS path, this one is about the layouts.
        with TwoLevelStore(root, mem_capacity_bytes=tree_bytes(want) + 2**30) as store:
            ckpt = CheckpointManager(store, tag="smoke", mode="sync")
            try:
                ckpt.save(2, state)
                _, placed = ckpt.restore_sharded(tmpl, shardings, step=2)
            finally:
                ckpt.close()
        log(f"mesh: free disk with one checkpoint stored "
            f"{shutil.disk_usage(root).free / 2**30:.1f} GiB")
        shutil.rmtree(root)
        return placed, assert_trees_identical(placed, want, what)

    # Device 0 holds the whole one-chip state: drop each state once saved.
    on_mesh, n = through_store(s1, st_sh, host1, "one-chip checkpoint on the mesh")
    del s1
    on_one, _ = through_store(s4, jax.tree_util.tree_map(lambda _: one, tmpl), host4,
                              "4-chip checkpoint on one chip")
    del s4, on_one
    # Every leaf the rules shard, and every large leaf, is split over all chips.
    leaves = jax.tree_util.tree_leaves(on_mesh)
    split = [len({str(s.index) for s in x.addressable_shards}) for x in leaves]
    sharded = [k for x, k in zip(leaves, split) if any(x.sharding.spec) or x.size >= 1 << 20]
    if not sharded or any(k != chips for k in sharded):
        raise AssertionError(f"mesh: sharded leaves split {sharded}, want {chips} each")
    log(f"mesh: checkpoints restored bit-identically across layouts ({n} leaves); "
        f"{len(sharded)} of {len(leaves)} leaves split {chips} ways over "
        f"{len({d for x in leaves for d in x.sharding.device_set})} devices")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the sharded train step on four chips")
    ap.add_argument("--tiny", action="store_true",
                    help="reduced sizes on any platform (CPU rehearsal)")
    args = ap.parse_args()
    sz = TINY if args.tiny else FULL

    cache_dir = enable_compile_cache()
    dev, count = phase_device(args.tiny, args.chips)
    log(f"phase 0: compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips > 1:
            phase_mesh(args.tiny, sz, args.chips)
        else:
            phase_train(args.tiny, sz)
            log(f"phase 1 done at {time.perf_counter() - t0:.1f}s")
            model, cfg, params, plane = phase_serve(args.tiny, sz)
            log(f"phase 2 done at {time.perf_counter() - t0:.1f}s")
            phase_kernel(model, cfg, params, sz, plane, args.tiny)
            log(f"phase 3 done at {time.perf_counter() - t0:.1f}s")
    finally:
        shutil.rmtree(STORE_ROOT, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
