"""Resilient training driver: the paper's storage system under a real loop.

Wiring (DESIGN.md §2): the token pipeline reads through the TwoLevelStore
(hot shards in the memory tier, all shards durable on the PFS tier); the
checkpoint manager writes two-level checkpoints (sync or async); a
heartbeat watches liveness; a failure injector simulates host loss; on
failure the driver restores the last committed checkpoint AND the exact
pipeline cursor, then continues — the recovery path is the paper's read
mode (f): memory tier first, PFS fallback.

CLI:  python -m repro.launch.train --arch starcoder2-3b --steps 20 --reduced --store <dir>
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced, make_model
from repro.core.store import TwoLevelStore
from repro.core.trace import span
from repro.data.pipeline import PipelineState, ShardedLoader, SyntheticCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import init_state, make_train_step
from repro.optim.adamw import AdamW, cosine_warmup
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.failure import FailureInjector, Heartbeat, SimulatedFailure


@dataclasses.dataclass
class TrainResult:
    state: dict
    losses: list
    restarts: int
    steps_run: int
    #: per-phase stall breakdown (seconds): where the step wall time went
    stalls: dict = dataclasses.field(default_factory=dict)
    #: accumulated two-level data-path stats across all loaders of the run
    loader_stats: dict = dataclasses.field(default_factory=dict)


def jit_train_step(model, cfg, optimizer: AdamW, accum_steps: int = 1):
    """``train_step(state, batch)`` compiled as :func:`run_training` runs it.

    The state is donated, so the new state reuses the old one's buffers:
    without that a step holds two copies of params and optimizer state,
    which decides whether a real model's one-chip share fits the chip.
    The caller must not read a state after passing it in.
    """
    return jax.jit(make_train_step(model, cfg, optimizer, accum_steps=accum_steps),
                   donate_argnums=0)


def run_training(
    cfg,
    store: TwoLevelStore,
    total_steps: int,
    global_batch: int = 8,
    seq_len: int = 64,
    ckpt_every: int = 5,
    ckpt_mode: str = "async",
    peak_lr: float = 1e-3,
    injector: FailureInjector | None = None,
    max_restarts: int = 8,
    heartbeat_timeout: float = 300.0,
    on_step: Callable[[int, dict], None] | None = None,
    accum_steps: int = 1,
) -> TrainResult:
    """Train with checkpoint/restart through the two-level store."""
    model = make_model(cfg)
    optimizer = AdamW(learning_rate=cosine_warmup(peak_lr, 10, max(total_steps, 20)))
    train_step = jit_train_step(model, cfg, optimizer, accum_steps)

    corpus = SyntheticCorpus(
        store, vocab_size=cfg.vocab, n_shards=8,
        tokens_per_shard=max(global_batch * (seq_len + 1) * 4, 1 << 14),
    )
    corpus.generate()
    ckpt = CheckpointManager(store, tag=cfg.name, mode=ckpt_mode, keep_last=2)
    injector = injector or FailureInjector()
    # Stall totals (seconds) from the loop's spans: time in next(loader),
    # time on the checkpoint's critical path (cursor sync + save), and the
    # part of it inside save() (the device->host snapshot in async mode:
    # packing and store puts run off the step path).
    data_stall_s = ckpt_stall_s = save_critical_s = 0.0
    agg_loader: dict[str, float] = {}

    def fold_loader_stats(loader: ShardedLoader) -> None:
        for k, v in dataclasses.asdict(loader.stats).items():
            agg_loader[k] = agg_loader.get(k, 0) + v

    def fresh_state():
        state, _ = init_state(model, cfg, optimizer, jax.random.PRNGKey(0))
        state["pipeline"] = {"epoch": np.int64(0), "step": np.int64(0)}
        return state

    state = fresh_state()
    if ckpt.latest_step() is not None:
        _, state = ckpt.restore(state)

    losses: list = []
    restarts = 0
    steps_run = 0

    try:
        with Heartbeat(timeout_s=heartbeat_timeout) as hb:
            while True:
                pstate = PipelineState(
                    int(state["pipeline"]["epoch"]), int(state["pipeline"]["step"])
                )
                loader = ShardedLoader(
                    corpus, global_batch, seq_len, prefetch_depth=2, state=pstate
                )
                try:
                    while int(state["step"]) < total_steps:
                        step_no = int(state["step"])
                        injector.maybe_fail(step_no)
                        with span("train.data_wait", step_no) as waited:
                            inputs, labels = next(loader)
                        data_stall_s += waited.seconds
                        batch = {"inputs": jnp.asarray(inputs), "labels": jnp.asarray(labels)}
                        # The step sees {params, opt, step} only: passing the
                        # host-side cursor too, as the state carries it after
                        # a restore or a save, gives the step a second
                        # signature and so a second compile.
                        with span("train.dispatch", step_no):
                            state, metrics = train_step(
                                {k: state[k] for k in ("params", "opt", "step")}, batch
                            )
                        hb.beat()
                        with span("train.result_wait", step_no):
                            loss = float(metrics["loss"])
                        losses.append(loss)
                        steps_run += 1
                        if on_step:
                            on_step(step_no, metrics)
                        if int(state["step"]) % ckpt_every == 0:
                            saved = int(state["step"])
                            with span("train.ckpt", saved) as stalled:
                                cursor = loader.sync()
                                state["pipeline"] = {
                                    "epoch": np.int64(cursor.epoch),
                                    "step": np.int64(cursor.step),
                                }
                                ckpt.save(saved, state)
                            ckpt_stall_s += stalled.seconds
                            save_critical_s += ckpt.last_save.seconds
                    break  # completed
                except SimulatedFailure:
                    restarts += 1
                    if restarts > max_restarts:
                        raise
                    # Recovery: last committed two-level checkpoint (memory-
                    # tier hit when the tier survived; PFS read mode (f)
                    # otherwise).
                    state = fresh_state()
                    if ckpt.latest_step() is not None:
                        _, state = ckpt.restore(state)
                finally:
                    loader.close()
                    fold_loader_stats(loader)

        ckpt.wait_until_durable()
    finally:
        ckpt.close()  # stop the background save lane (joins pending saves)
    stalls = {
        "data_stall_total_s": data_stall_s,
        "ckpt_stall_total_s": ckpt_stall_s,
        "ckpt_save_critical_s": save_critical_s,
    }
    return TrainResult(
        state=state,
        losses=losses,
        restarts=restarts,
        steps_run=steps_run,
        stalls=stalls,
        loader_stats=agg_loader,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--store", required=True,
                    help="store root; training resumes from any checkpoint found there")
    ap.add_argument("--ckpt-mode", default="async", choices=["sync", "async", "memory_only"])
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--distributed", action="store_true",
                    help="join --store as a DistributedStore host shard (leases, peer "
                         "reads, background reclamation)")
    ap.add_argument("--host-id", type=int, default=1,
                    help="host id for --distributed (unique per process)")
    ap.add_argument("--lease-ttl", type=float, default=5.0,
                    help="heartbeat/lease ttl seconds for --distributed")
    ap.add_argument("--chaos", nargs="*", default=[], metavar="SITE:KIND[,k=v...]",
                    help="arm chaos faults, e.g. peer.request:delay,prob=0.2,delay_s=0.05 "
                         "(see repro.runtime.failure.ChaosInjector)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    chaos = None
    if args.chaos:
        from repro.runtime.failure import ChaosInjector

        chaos = ChaosInjector.from_specs(args.chaos, seed=args.chaos_seed)
    store_kw = dict(mem_capacity_bytes=256 * 2**20, block_bytes=4 * 2**20)
    dstore = None
    if args.distributed:
        from repro.core.dstore import DistributedStore

        dstore = DistributedStore(
            args.host_id, args.store, lease_ttl_s=args.lease_ttl, chaos=chaos, **store_kw
        )
        store = dstore.store  # training I/O runs this shard's local data path
    else:
        store = TwoLevelStore(args.store, chaos=chaos, **store_kw)
    try:
        res = run_training(
            cfg,
            store,
            total_steps=args.steps,
            global_batch=args.batch,
            seq_len=args.seq,
            ckpt_mode=args.ckpt_mode,
            injector=FailureInjector(args.fail_at),
            on_step=lambda s, m: print(f"step {s:4d} loss {float(m['loss']):.4f}"),
        )
    finally:
        if dstore is not None:
            dstore.close()
        else:
            store.close()
    print(
        f"done: {res.steps_run} steps run ({res.restarts} restarts), "
        f"final loss {res.losses[-1]:.4f}"
    )
    print(
        f"stalls: data {res.stalls['data_stall_total_s']:.2f}s, "
        f"ckpt {res.stalls['ckpt_stall_total_s']:.2f}s "
        f"(save critical path {res.stalls['ckpt_save_critical_s']:.2f}s)"
    )
    if dstore is not None:
        st = dstore.stats
        print(
            f"dstore[h{dstore.host_id}]: {st.lease_claims} leases "
            f"({st.takeovers} takeovers, {st.reclaimed_files} reclaimed in "
            f"{st.reclaim_ticks} ticks), {st.peer_retries} peer retries, "
            f"{st.peer_reconnects} reconnects, {st.cold_fallback_reads} cold fallbacks"
        )
    if chaos is not None:
        print(f"chaos: {chaos.fired_count()} faults fired ({len(chaos.history)} events)")


if __name__ == "__main__":
    main()
