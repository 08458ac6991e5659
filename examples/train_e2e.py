"""End-to-end resilient training driver.

Default: a ~15 M-param model, 30 steps, failure injected at step 12,
async two-level checkpoints — finishes in a couple of minutes on CPU.

--full: the ~100 M-param config for a few hundred steps (the deliverable
configuration; hours on CPU, minutes on a real accelerator host).

    PYTHONPATH=src python examples/train_e2e.py [--full]
"""

import argparse
import dataclasses
import tempfile
import time

from repro.configs.base import ArchConfig
from repro.core import IOController, TwoLevelStore
from repro.launch.train import run_training
from repro.runtime.failure import FailureInjector


def model_100m() -> ArchConfig:
    return ArchConfig(
        name="repro-100m",
        family="dense",
        n_layers=12,
        d_model=768,
        n_heads=12,
        n_kv_heads=12,
        d_ff=3072,
        vocab=32_768,
        attn_type="gqa",
        tie_embeddings=True,
        max_seq_len=2048,
        remat="none",
        dtype="float32",
    )


def model_15m() -> ArchConfig:
    return dataclasses.replace(
        model_100m(), name="repro-15m", n_layers=4, d_model=320, n_heads=8,
        n_kv_heads=8, d_ff=1280, vocab=8192,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="~100M params, 300 steps")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    args = ap.parse_args()

    cfg = model_100m() if args.full else model_15m()
    steps = args.steps or (300 if args.full else 30)
    batch = args.batch or (8 if args.full else 4)
    seq = args.seq or (512 if args.full else 128)
    fail_at = steps // 2

    print(f"model {cfg.name}: {cfg.param_count()/1e6:.1f}M params; "
          f"{steps} steps of {batch}x{seq} tokens; failure injected at step {fail_at}")

    t0 = time.time()
    tokens_seen = 0

    def on_step(s, metrics):
        nonlocal tokens_seen
        tokens_seen += batch * seq
        if s % 5 == 0 or s == steps - 1:
            dt = time.time() - t0
            print(f"  step {s:4d}  loss {float(metrics['loss']):.4f}  "
                  f"{tokens_seen / max(dt, 1e-9):,.0f} tok/s")

    with tempfile.TemporaryDirectory() as d:
        ctl = IOController()  # adaptive I/O control plane (DESIGN.md §10)
        with TwoLevelStore(d + "/pfs", mem_capacity_bytes=512 * 2**20, block_bytes=4 * 2**20,
                           controller=ctl) as store:
            res = run_training(
                cfg,
                store,
                total_steps=steps,
                global_batch=batch,
                seq_len=seq,
                ckpt_every=max(steps // 6, 5),
                ckpt_mode="async",
                injector=FailureInjector([fail_at]),
                on_step=on_step,
            )
            print(f"\ncompleted: {res.steps_run} steps run, {res.restarts} restart(s) "
                  f"(recovered from the injected failure via the two-level checkpoint)")
            print(f"final loss {res.losses[-1]:.4f}; first loss {res.losses[0]:.4f}")
            st = store.tier_stats()
            print(f"checkpoint traffic to PFS tier: {st['pfs']['bytes_written']/2**20:.1f} MiB; "
                  f"async flushes: {st['store']['async_flushes']}")

            s, n = res.stalls, max(res.steps_run, 1)
            print("\nstep stall breakdown (where the wall time went):")
            print(f"  data stall:  {s['data_stall_total_s']:7.2f}s total, "
                  f"{s['data_stall_total_s'] / n * 1e3:7.2f}ms/step mean")
            print(f"  ckpt stall:  {s['ckpt_stall_total_s']:7.2f}s total, "
                  f"{s['ckpt_stall_total_s'] / n * 1e3:7.2f}ms/step mean "
                  f"(async save critical path {s['ckpt_save_critical_s']:.2f}s)")

            ls = res.loader_stats
            slab_total = ls.get("slab_hits", 0) + ls.get("slab_misses", 0)
            win_total = ls.get("local_windows", 0) + ls.get("remote_windows", 0)
            ss = st["store"]
            mem_total = ss["mem_hits"] + ss["mem_misses"]
            print("two-level hit rates:")
            print(f"  loader slab cache: {ls.get('slab_hits', 0)}/{slab_total} hits "
                  f"({ls.get('slab_hits', 0)/max(slab_total,1):.1%}), "
                  f"{ls.get('bytes_fetched', 0)/2**20:.1f} MiB fetched via ranged reads")
            print(f"  window locality:   {ls.get('local_windows', 0)}/{win_total} "
                  f"windows on owned shards")
            print(f"  store memory tier: {ss['mem_hits']}/{mem_total} hits "
                  f"({ss['mem_hits']/max(mem_total,1):.1%}); "
                  f"{ss['range_reads']} ranged reads, "
                  f"{ss['range_bytes']/2**20:.1f} MiB ranged")

            rep = ctl.report()
            print("\nadaptive I/O controller (online Eq. 1-7 model):")
            print(f"  tier rates (EWMA):  nu={rep['nu_mbps']:.0f} MB/s mem, "
                  f"q_read={rep['q_read_mbps']:.0f} / q_write={rep['q_write_mbps']:.0f} MB/s PFS")
            print(f"  admission:          {rep['admits']} promoted, {rep['bypasses']} bypassed, "
                  f"{rep['flush_drops']} flush-dropped "
                  f"(per class: "
                  + ", ".join(f"{c}={cs['admits']}/{cs['bypasses']}"
                              for c, cs in rep['classes'].items()) + ")")
            traj = rep['readahead_trajectory']
            depths = {c: d for c, d in rep['readahead'].items()}
            print(f"  readahead depths:   {depths}"
                  + (f"; trajectory {[(c, dep) for _, c, dep in traj[-6:]]}" if traj else ""))
            print(f"  flush lanes:        {rep['flush_lanes']} now"
                  + (f"; trajectory {[n for _, n in rep['lane_trajectory'][-8:]]}"
                     if rep['lane_trajectory'] else ""))
            print(f"  in-memory fraction: measured f={rep['measured_f']:.3f} vs "
                  f"plan target f={rep['target_f']:.3f} "
                  f"(Eq. 7 demand needs f>={rep['f_required_for_demand']:.3f}; "
                  f"predicted read {rep['predicted_read_mbps']:.0f} MB/s)")


if __name__ == "__main__":
    main()
