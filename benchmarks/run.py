"""Benchmark entry point: ``python -m benchmarks.run [--quick]``.

One module per paper table/figure; prints ``name,value,derived`` CSV
(value is the figure's native unit: MB/s, node counts, seconds, ratios —
noted in the derived column).

``--quick`` runs every module at smoke-test sizes (small files / few
records) — used by CI to catch throughput-path regressions on every PR
without paying full-measurement wall time.

Every module additionally emits a ``BENCH_<label>.json`` artifact (rows +
elapsed wall time) into ``$BENCH_ARTIFACT_DIR`` (default: current
directory) — CI uploads these so the perf trajectory (agg MB/s, tok/s,
bytes/step) is tracked across PRs.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="smoke-test sizes (CI mode)")
    ap.add_argument("--only", nargs="*", help="run only these module labels")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        chaos_soak,
        compress_scaling,
        fig1_tiers,
        fig5_crossover,
        fig6_mountain,
        fig7_terasort,
        mixed_scaling,
        multihost_scaling,
        parallel_scaling,
        repair_scaling,
        roofline,
        serve_scaling,
        serve_sessions,
        terasort_scaling,
        train_io_scaling,
    )

    modules = [
        ("fig1", fig1_tiers),
        ("fig5", fig5_crossover),
        ("fig6", fig6_mountain),
        ("fig7", fig7_terasort),
        ("pscale", parallel_scaling),
        ("sscale", serve_scaling),
        ("tscale", train_io_scaling),
        ("terascale", terasort_scaling),
        ("mixed", mixed_scaling),
        ("compress", compress_scaling),
        ("multihost", multihost_scaling),
        ("chaos", chaos_soak),
        ("serve_sessions", serve_sessions),
        ("repair", repair_scaling),
        ("roofline", roofline),
    ]
    if args.only:
        known = {label for label, _ in modules}
        unknown = [label for label in args.only if label not in known]
        if unknown:
            # A typo'd label must not silently run nothing (a CI leg that
            # filters by label would pass vacuously).
            sys.exit(
                f"run.py: unknown --only label(s): {', '.join(unknown)} "
                f"(known: {', '.join(sorted(known))})"
            )
        modules = [(label, mod) for label, mod in modules if label in args.only]
    art_dir = os.environ.get("BENCH_ARTIFACT_DIR", ".")
    os.makedirs(art_dir, exist_ok=True)
    print("name,value,derived")
    failures = 0
    for label, mod in modules:
        t0 = time.perf_counter()
        try:
            if "quick" in inspect.signature(mod.run).parameters:
                rows = mod.run(quick=args.quick)
            else:
                rows = mod.run()
        except Exception as e:  # keep the harness running
            failures += 1
            print(f"{label}.ERROR,0,{type(e).__name__}: {e}")
            continue
        elapsed = time.perf_counter() - t0
        for name, value, derived in rows:
            print(f"{name},{value},{derived}")
        print(f"{label}.elapsed_s,{elapsed:.2f},harness")
        with open(os.path.join(art_dir, f"BENCH_{label}.json"), "w") as fh:
            json.dump(
                {
                    "label": label,
                    "quick": args.quick,
                    "elapsed_s": round(elapsed, 3),
                    "rows": {n: {"value": v, "derived": d} for n, v, d in rows},
                },
                fh,
                indent=2,
            )
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
