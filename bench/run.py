"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name: the cell in
``BENCHMARK.json``, the configuration in ``bench/configs/<config>.json``, the
traffic mix in ``bench/traffic/<traffic>.json``, whose ``kind`` names the
driver module ``bench/kinds/<kind>.py``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  Adding a cell of an existing kind, a
configuration or a metric is adding files and entries; no file here changes.

There is no CPU fallback: a run that finds no TPU, or fewer chips than the
cell asks for, names the platform on stderr and exits 1 with no result.
The last stdout line is one JSON object; with ``--trace 0`` its metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# libtpu logs under /tmp unless told otherwise; a run writes only in its checkout.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    try:
        harness.require_program()
        harness.enable_compile_cache()
        devices = harness.require_chips(cell.chips)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              devices=devices, t_process=T_PROCESS)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
