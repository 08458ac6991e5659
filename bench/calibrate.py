"""Readings that set the limits of ``correct``: the program's, the
control's and the planted faults', seed by seed, in one process.

    python bench/calibrate.py --workload train.stream --seeds 1 2 3 ...

For a train cell: the program through its first steps, against the plain
float32 reference; the control (the reference computed from float8
operands, put in the program's place); and the half-batch fault (the
reference over half of each batch's rows, the mean taken over them).
The benchmark's own runs never run this.  One JSON line per seed on
stdout.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness  # noqa: E402


def train_readings(cell, seed: int) -> dict:
    import shutil

    kind = harness.kind_module(cell.kind)
    ctx = harness.RunContext(cell, seed, cell.traffic["step_s"], False, time.perf_counter())
    try:
        d = kind.drive(ctx)
    finally:
        shutil.rmtree(ctx.store_root, ignore_errors=True)
    conf, batches = cell.config, d.probe.batches
    prog = kind.program_readings(d.probe, conf, d.model, d.cfg, seed, d.device)
    ref = kind.reference_readings(conf, d.model, d.cfg, seed, batches, d.device)
    ctl = kind.reference_readings(conf, d.model, d.cfg, seed, batches, d.device, quant="fp8")
    half = kind.reference_readings(conf, d.model, d.cfg, seed, batches, d.device,
                                   rows=slice(0, cell.traffic["batch"] // 2))
    return {"program": kind.compare(prog, ref), "control": kind.compare(ctl, ref),
            "half_batch": kind.compare(half, ref), "losses": prog["losses"],
            "reference_losses": ref["losses"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    try:
        harness.require_chips(cell.chips)
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    if cell.kind != "train":
        print(f"calibrate: no readings for kind {cell.kind!r}", file=sys.stderr)
        return 1
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = train_readings(cell, seed)
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
