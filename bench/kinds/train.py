"""Traffic kind ``train``: the program's trainer, ``run_training``, on a
fresh ``TwoLevelStore``: its loader streams the corpus through the store,
and its async checkpoints go into the store's memory tier and flush to the
PFS tier.

One ``run_training`` call is the whole run.  Its warm-up steps are set-up
(the first compiles); the window runs from the end of the last warm-up step
to the moment every save is durable on the PFS tier after the last step,
so the work per window is fixed by the traffic file and a save's whole
cost, its flush included, falls inside it (:func:`window_plan`).

``run_training`` takes no seed, so the benchmark hands it its inputs by the
names the trainer module looks up: ``init_state`` (the weights, made from
``--seed`` by :func:`bench.model.make_params`) and ``SyntheticCorpus`` (the
corpus, seeded).  Its spans come from subclasses of the loader and the
checkpoint manager and from a wrapper around the compiled step, which also
keeps what the check needs: the first three batches, the first gradient
as the optimizer holds it after step 1, the parameters step 4 receives,
and a hash of every state saved in the window.

``correct``: losses of steps 1-3, the first gradient and the parameters'
change after three steps against the plain float32 reference
(``bench/reference/<model_type>.py``) from the same seed, each by its worst
leaf; every checkpoint saved in the window read back from a reopened store
and compared bit for bit (by hash) on every leaf; and the fed rows, which
must all differ and be next-token pairs.
"""

from __future__ import annotations

import contextlib
import gc
import math
import time

import numpy as np

#: the numbers compared, in the order they are printed
CHECKS = ("loss_gap", "grad_gap", "update_gap", "ckpt_bad_leaves", "rows_bad")
#: leaves below this share of the median leaf's reference gradient are left
#: out of the gradient and update comparisons (round-off moves them)
TINY_GRAD = 1e-3
#: host bytes of checkpoint leaves restored at once in the read-back
READBACK_GROUP_BYTES = 1 << 30


def window_plan(traffic: dict, seconds: float) -> tuple[int, int, int]:
    """(warm-up steps, total steps, ckpt_every) for a window of ``seconds``.

    The window holds ``ceil((seconds - save_s) / step_s)`` steps: ``step_s``
    is a step's time on the chip and ``save_s`` what one save adds to the
    window (its stall and its lane's hold on the host), both measured.
    With ``saves_in_window`` 1 the run's only save is that of the last
    warm-up step, made just after the window opens, so its lane runs
    inside the window.  The trainer saves every ``ckpt_every`` steps, so
    the next save must lie past the last step: the warm-up is at least as
    long as the window."""
    warm = int(traffic["warmup_steps"])
    if warm < 4:
        raise ValueError("warmup_steps must cover the three checked steps and step 4")
    if not traffic["saves_in_window"]:
        total = warm + max(1, math.ceil(seconds / traffic["step_s"]))
        return warm, total, total + 1
    n = max(1, math.ceil((seconds - traffic["save_s"]) / traffic["step_s"]))
    warm = max(warm, n + 1)
    return warm, warm + n, warm


# ------------------------------------------------------------ device helpers


def _jit_norms():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))


def _leaf_hash(x, xp):
    """Order-sensitive 2 x 32-bit hash of a leaf's bits (mod 2**32 sums)."""
    flat = x.reshape(-1)
    if xp is np:
        u = np.ascontiguousarray(flat).view(np.uint32)
        idx = np.arange(1, u.size + 1, dtype=np.uint32)
        return np.array([np.sum(u, dtype=np.uint32), np.sum(u * idx, dtype=np.uint32)], np.uint32)
    import jax

    u = jax.lax.bitcast_convert_type(flat, xp.uint32)
    idx = xp.arange(1, u.size + 1, dtype=xp.uint32)
    return xp.stack([xp.sum(u, dtype=xp.uint32), xp.sum(u * idx, dtype=xp.uint32)])


def _jit_hash():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jax.tree_util.tree_map(lambda x: _leaf_hash(x, jnp), t))


def host_hash(arr: np.ndarray) -> np.ndarray:
    return _leaf_hash(np.asarray(arr), np)


# ------------------------------------------------------------------ probe


class Probe:
    """Everything the benchmark hooks into ``run_training``."""

    def __init__(self, ctx, conf: dict, warm: int, total: int, ckpt_every: int):
        self.ctx, self.conf = ctx, conf
        self.warm, self.total, self.ckpt_every = warm, total, ckpt_every
        self.calls = 0
        self.batches: list[dict] = []
        self.m1_norms = None
        self.p3 = None
        self.hashes: dict[int, object] = {}
        self.losses: list[float] = []
        self.store = None
        self.ckpt = None  # the trainer's CheckpointManager
        self.state_shapes = None  # abstract train state, for read-back templates
        self.tiers: list[dict] = []
        self.step_ends: list[float] = []  # host clock at each window step's end
        self._norms = _jit_norms()
        self._hash = _jit_hash()

    def wrap_step(self, inner):
        import jax

        def step(state, batch):
            self.calls += 1
            i = self.calls
            if i == 2:
                self._hash(state)  # compile the hash on a step's output, in set-up
            if i <= 3:
                self.batches.append(jax.device_get(batch))
            if i == 2:
                self.m1_norms = self._norms(state["opt"]["m"])
            if i == 4:
                self.p3 = jax.device_get(state["params"])
            saved = i - 1
            if saved >= 1 and saved % self.ckpt_every == 0:
                self.hashes[saved] = self._hash(state)
            with self.ctx.span("bench.train.step_dispatch"):
                return inner(state, batch)

        return step

    def on_step(self, step_no: int, metrics: dict) -> None:
        k = step_no + 1
        self.losses.append(float(metrics["loss"]))
        if k > self.warm:
            self.step_ends.append(time.perf_counter())
        if k == self.warm:
            self.ctx.open_window()
            self.tiers.append(self.store.tier_stats())
        elif k == self.total:
            # What run_training does after its last step, inside the window:
            # every save serialised and on the PFS tier.
            with self.ctx.span("bench.ckpt.durable_wait"):
                self.ckpt.wait_until_durable()
            self.ctx.close_window()
            self.tiers.append(self.store.tier_stats())


@contextlib.contextmanager
def hooked(probe: Probe, seed: int, device):
    """Install the benchmark's inputs and spans into the trainer module."""
    import jax.numpy as jnp

    import repro.launch.train as T
    from bench import model as bm

    saved = {n: getattr(T, n) for n in
             ("init_state", "jit_train_step", "SyntheticCorpus", "ShardedLoader", "CheckpointManager")}
    ctx, conf = probe.ctx, probe.conf

    def init_state(model, cfg, optimizer, rng, abstract=False):
        state, axes = saved["init_state"](model, cfg, optimizer, rng, abstract=True)
        if abstract:
            return state, axes
        params = bm.make_params(model, cfg, conf, seed, device=device)
        return {"params": params, "opt": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}, axes

    def jit_train_step(*a, **kw):
        return probe.wrap_step(saved["jit_train_step"](*a, **kw))

    class SeededCorpus(saved["SyntheticCorpus"]):
        def __init__(self, *a, **kw):
            kw["seed"] = seed
            super().__init__(*a, **kw)

    class TimedLoader(saved["ShardedLoader"]):
        def __next__(self):
            with ctx.span("bench.train.data_wait"):
                return super().__next__()

    class TimedCheckpoints(saved["CheckpointManager"]):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            probe.ckpt = self

        def save(self, step, state):
            with ctx.span("bench.ckpt.save"):
                super().save(step, state)

        def _bg_save(self, *a, **kw):
            with ctx.span("bench.ckpt.background"):
                return super()._bg_save(*a, **kw)

    repl = {"init_state": init_state, "jit_train_step": jit_train_step,
            "SyntheticCorpus": SeededCorpus, "ShardedLoader": TimedLoader,
            "CheckpointManager": TimedCheckpoints}
    for n, v in repl.items():
        setattr(T, n, v)
    try:
        yield
    finally:
        for n, v in saved.items():
            setattr(T, n, v)


# -------------------------------------------------------------- reference


def reference_readings(conf: dict, model, cfg, seed: int, batches: list[dict],
                       device, quant=None, rows: slice | None = None) -> dict:
    """The plain reference through the first three steps from ``seed``:
    losses, per-leaf norms of the first (clipped) gradient, and per-leaf
    norms of the parameters' change after three steps.  ``rows`` takes a
    subset of each batch's rows (the half-batch fault, planted here)."""
    import jax
    import jax.numpy as jnp

    from bench import model as bm

    ref = reference_module(conf)
    opt = conf["train"]["optimizer"]
    params = bm.make_params(model, cfg, conf, seed, dtype=jnp.float32, device=device)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)

    def row_grad(acc, p, x, y, stats, n):
        loss, g = jax.value_and_grad(ref.lm_loss)(p, x[None], y[None], conf_s, quant, *stats)
        return jax.tree_util.tree_map(lambda a, b: a + b / n, acc, g), loss / n

    conf_s = ref._Frozen(conf)
    row_grad = jax.jit(row_grad, static_argnums=(5,), donate_argnums=(0,))
    # A loss term over the whole batch (a load-balance term's expert shares)
    # takes the family's ``batch_stats``, the mean of its rows' readings.
    row_stats = getattr(ref, "batch_stats", None)
    if row_stats is not None:
        row_stats = jax.jit(row_stats, static_argnums=(2, 3))
    norms = _jit_norms()

    def step_fn(p, g, m_, v_, count):
        p, m_, v_, g = ref.adamw(p, g, m_, v_, count, opt)
        return p, m_, v_, jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(x * x)), g)

    step_fn = jax.jit(step_fn, static_argnums=(4,), donate_argnums=(0, 2, 3))
    losses, grad_norms = [], None
    for count, b in enumerate(batches[:3], start=1):
        xs, ys = np.asarray(b["inputs"]), np.asarray(b["labels"])
        if rows is not None:
            xs, ys = xs[rows], ys[rows]
        stats = ()
        if row_stats is not None:
            stats = (sum(row_stats(params, jnp.asarray(x)[None], conf_s, quant) for x in xs) / len(xs),)
        g = zeros(params)
        loss = 0.0
        for r in range(xs.shape[0]):
            g, lr_ = row_grad(g, params, jnp.asarray(xs[r]), jnp.asarray(ys[r]), stats, xs.shape[0])
            loss += float(lr_)
        params, m, v, gn = step_fn(params, g, m, v, count)
        losses.append(loss)
        if count == 1:
            grad_norms = jax.device_get(gn)
    del m, v, g
    p0 = bm.make_params(model, cfg, conf, seed, dtype=jnp.float32, device=device)
    delta = jax.device_get(norms(jax.tree_util.tree_map(jnp.subtract, params, p0)))
    del params, p0
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


def reference_module(conf: dict):
    """The plain reference of the configuration's model family:
    ``bench/reference/<model_type>.py``."""
    from bench.harness import BENCH, load_module

    return load_module(BENCH / "reference" / f"{conf['hf']['model_type']}.py")


def program_readings(probe: Probe, conf: dict, model, cfg, seed: int, device) -> dict:
    """The same readings from what the program did."""
    import jax
    import jax.numpy as jnp

    from bench import model as bm

    b1 = conf["train"]["optimizer"]["b1"]
    grads = jax.tree_util.tree_map(lambda n: float(n) / (1 - b1), jax.device_get(probe.m1_norms))
    p0 = bm.make_params(model, cfg, conf, seed, dtype=jnp.float32, device=device)
    p3 = jax.device_put(probe.p3, device)
    delta = jax.device_get(_jit_norms()(jax.tree_util.tree_map(jnp.subtract, p3, p0)))
    del p0, p3
    return {"losses": probe.losses[:3], "grad_norms": grads, "delta_norms": delta}


def worst_leaf_gap(got, want, keep) -> float:
    """max over kept leaves of |got - want| / max(want, median want)."""
    import jax

    g = np.asarray(jax.tree_util.tree_leaves(got), np.float64)
    w = np.asarray(jax.tree_util.tree_leaves(want), np.float64)
    g, w = g[keep], w[keep]
    floor = np.median(w)
    return float(np.max(np.abs(g - w) / np.maximum(w, floor)))


def compare(prog: dict, refr: dict) -> dict:
    import jax

    rg = np.asarray(jax.tree_util.tree_leaves(refr["grad_norms"]), np.float64)
    keep = rg >= TINY_GRAD * np.median(rg)
    lp, lr = np.asarray(prog["losses"]), np.asarray(refr["losses"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": worst_leaf_gap(prog["grad_norms"], refr["grad_norms"], keep),
        "update_gap": worst_leaf_gap(prog["delta_norms"], refr["delta_norms"], keep),
        "leaves_left_out": int((~keep).sum()),
    }


# ------------------------------------------------------------ other checks


def rows_bad(batches: list[dict]) -> int:
    """Rows fed more than once, plus rows whose labels are not the inputs
    shifted by one token."""
    xs = np.concatenate([np.asarray(b["inputs"]) for b in batches])
    ys = np.concatenate([np.asarray(b["labels"]) for b in batches])
    dup = len(xs) - len({r.tobytes() for r in xs})
    unshifted = int(np.sum(np.any(ys[:, :-1] != xs[:, 1:], axis=1)))
    return dup + unshifted


def ckpt_bad_leaves(probe: Probe, store_kw: dict, root: str, tag: str) -> tuple[int, int]:
    """Read every checkpoint saved in the window back from a reopened store
    and compare every leaf bit for bit (by hash) with the state the program
    saved.  Returns (bad leaves, leaves compared)."""
    import jax

    from repro.core.store import TwoLevelStore
    from repro.runtime.checkpoint import CheckpointManager

    bad = n = 0
    with TwoLevelStore(root, **store_kw) as store:
        ckpt = CheckpointManager(store, tag=tag, mode="sync")
        try:
            committed = set(ckpt.steps())
            for step, dev_hash in sorted(probe.hashes.items()):
                want = jax.device_get(dev_hash)
                flat = jax.tree_util.tree_flatten_with_path(want)[0]
                n += len(flat)
                if step not in committed:
                    bad += len(flat)
                    continue
                for template in _templates(flat, probe.state_shapes):
                    _, got = ckpt.restore(template, step=step)
                    for path, arr in jax.tree_util.tree_flatten_with_path(got)[0]:
                        bad += int(not np.array_equal(host_hash(arr), np.asarray(_at(want, path))))
                    del got
        finally:
            ckpt.close()
    return bad, n


def _at(tree, path):
    for p in path:
        tree = tree[p.key]
    return tree


def _templates(flat, shapes, group_bytes: int = READBACK_GROUP_BYTES):
    """Every leaf of the saved state, as nested templates of it that each
    hold about ``group_bytes`` (one leaf at the least)."""
    groups: list[list] = [[]]
    size = 0
    for path, _ in flat:
        leaf = _at(shapes, path)
        nbytes = leaf.size * leaf.dtype.itemsize
        if groups[-1] and size + nbytes > group_bytes:
            groups.append([])
            size = 0
        groups[-1].append((tuple(p.key for p in path), leaf))
        size += nbytes
    for group in groups:
        tmpl: dict = {}
        for key, leaf in group:
            node = tmpl
            for part in key[:-1]:
                node = node.setdefault(part, {})
            node[key[-1]] = leaf
        yield tmpl


# -------------------------------------------------------------------- run


def drive(ctx):
    """Run ``run_training`` through warm-up and window with the benchmark's
    hooks; returns what the window measured and what the checks need.  The
    store root is left in place for the read-back."""
    import types

    import jax

    import repro.launch.train as T
    from bench import flops
    from bench import model as bm
    from bench.harness import fresh_dir, warm_host_memory
    from repro.configs import make_model
    from repro.core.store import TwoLevelStore
    from repro.launch.steps import init_state

    conf, tr = ctx.config, ctx.traffic
    with ctx.span("bench.setup.warm_host"):
        warm_host_memory(int(tr.get("warm_host_bytes", 0)))
    cfg = bm.arch_config(conf)
    model = make_model(cfg)
    device = jax.devices()[0]
    warm, total, ckpt_every = window_plan(tr, ctx.seconds)
    batch, seq = int(tr["batch"]), int(tr["seq_len"])
    root = str(fresh_dir(ctx.store_root))
    probe = Probe(ctx, conf, warm, total, ckpt_every)
    probe.state_shapes = init_state(model, cfg, _adamw_of(conf), jax.random.PRNGKey(0),
                                    abstract=True)[0]
    with hooked(probe, ctx.seed, device):
        with TwoLevelStore(root, **conf["store"]) as store:
            probe.store = store
            res = T.run_training(
                cfg, store, total_steps=total, global_batch=batch, seq_len=seq,
                ckpt_every=ckpt_every, ckpt_mode="async",
                peak_lr=conf["train"]["optimizer"]["peak_lr"], on_step=probe.on_step)
            stats = device.memory_stats() or {}
            del res.state
    losses = list(res.losses)
    del res
    gc.collect()
    steps = total - warm
    pfs0, pfs1 = probe.tiers[0]["pfs"], probe.tiers[1]["pfs"]
    busy = lambda s: s["write_busy_seconds"] + max(0.0, s["write_span_end"] - s["write_span_start"])
    counters = {
        "train.tokens": steps * batch * seq,
        "train.steps": steps,
        "train.flops_per_token": flops.train_flops_per_token(conf["hf"], seq, conf.get("published")),
        "pfs.bytes_written": pfs1["bytes_written"] - pfs0["bytes_written"],
        "pfs.write_busy_s": busy(pfs1) - busy(pfs0),
    }
    return types.SimpleNamespace(
        probe=probe, cfg=cfg, model=model, device=device, root=root, losses=losses,
        warm=warm, total=total, ckpt_every=ckpt_every, steps=steps, counters=counters,
        peak=int(stats.get("peak_bytes_in_use", 0)))


def run(ctx):
    import shutil

    from bench.harness import Check, KindResult

    conf = ctx.config
    try:
        d = drive(ctx)
        with ctx.span("bench.check.ckpt_readback"):
            bad_leaves, n_leaves = ckpt_bad_leaves(d.probe, conf["store"], d.root, d.cfg.name)
        with ctx.span("bench.check.reference"):
            prog = program_readings(d.probe, conf, d.model, d.cfg, ctx.seed, d.device)
            refr = reference_readings(conf, d.model, d.cfg, ctx.seed, d.probe.batches, d.device)
        got = compare(prog, refr)
        got["ckpt_bad_leaves"] = bad_leaves
        got["rows_bad"] = rows_bad(d.probe.batches)
    finally:
        shutil.rmtree(ctx.store_root, ignore_errors=True)
    t0, t1 = ctx.window
    limits = conf["limits"]["train"]
    checks = [Check(k, float(got[k]), float(limits[k])) for k in CHECKS]
    span_s = lambda name: [round(e - s, 3) for n, s, e in ctx.spans if n == name]
    notes = [
        f"steps: warm-up {d.warm}, window {d.steps}, ckpt_every {d.ckpt_every}; "
        f"saves in window {sum(1 for s in d.probe.hashes if s >= d.warm)}; "
        f"background save lane {span_s('bench.ckpt.background')} s; "
        f"durable wait {span_s('bench.ckpt.durable_wait')} s; "
        f"read-back {span_s('bench.check.ckpt_readback')} s; "
        f"reference {span_s('bench.check.reference')} s; "
        f"host memory warm-up {span_s('bench.setup.warm_host')} s",
        step_times(d.probe.step_ends, t0, ctx.spans),
        f"losses {d.losses[:4]}; reference {refr['losses']}",
        f"checkpoint leaves compared {n_leaves}; leaves left out {got['leaves_left_out']}",
    ]
    return KindResult(
        metrics={"train_tokens_per_s": d.counters["train.tokens"] / (t1 - t0)},
        attempted=d.steps, failed=0, checks=checks, memory_peak_bytes=d.peak,
        counters=d.counters, notes=notes)


def step_times(ends: list[float], t_open: float, spans) -> str:
    """Window steps' host times, split at the end of the save lane."""
    dt = np.diff([t_open] + ends)
    lane = [e for n, _, e in spans if n == "bench.ckpt.background"]
    cut = max(lane) if lane else t_open
    parts = []
    for label, m in (("during the save lane", np.asarray(ends) <= cut),
                     ("after it", np.asarray(ends) > cut)):
        x = dt[m]
        if x.size:
            parts.append(f"{label} {x.size} steps, median {np.median(x):.4f} s, "
                         f"max {x.max():.4f} s, sum {x.sum():.3f} s")
    return "window steps: " + "; ".join(parts)


def _adamw_of(conf: dict):
    from repro.optim.adamw import AdamW

    o = conf["train"]["optimizer"]
    return AdamW(b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"],
                 max_grad_norm=o["max_grad_norm"])
