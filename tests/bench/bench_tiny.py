"""Tiny versions of the benchmark's cells, for its CPU tests.

The shapes are the configuration's own architecture at toy widths; the
limits here are for these sizes only (the configuration file holds the
limits set on the chip at the published widths).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness  # noqa: E402

TINY_HF = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=256, head_dim=16)
#: limits at the tiny size, set from the readings of
#: bench/calibrate.py's functions at this size on four seeds (sound runs
#: read below a third of them; the float8 control and the faults above)
TINY_TRAIN_LIMITS = {"loss_gap": 1.5e-3, "grad_gap": 2e-2, "update_gap": 6e-3,
                     "ckpt_bad_leaves": 0, "rows_bad": 0}


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_cell(name: str) -> harness.Cell:
    """The cell at toy widths; a save adds no time to its window (``save_s``
    0), so the window holds ``seconds / step_s`` steps."""
    cell = harness.load_cell(name, spec())
    conf, tr = cell.config, cell.traffic
    conf["hf"].update(TINY_HF)
    conf["store"].update(mem_capacity_bytes=64 << 20, block_bytes=1 << 20,
                         stripe_bytes=256 << 10)
    tr.update(batch=2, seq_len=32, step_s=0.02, warm_host_bytes=0)
    if tr["saves_in_window"]:
        tr["save_s"] = 0.0
    conf["limits"]["train"] = dict(TINY_TRAIN_LIMITS)
    return cell


def family_cell(config_file: str, name: str = "train.ckpt") -> harness.Cell:
    """The cell ``name`` at toy sizes with its configuration replaced by a
    tiny one kept under ``data/``, which holds its own store and limits."""
    cell = tiny_cell(name)
    cell.config = harness.load_config(DATA / config_file)
    return cell


def run_tiny(cell: harness.Cell, tmp_path: Path, seed: int = 2**33 + 7,
             seconds: float = 0.3, trace: bool = False) -> dict:
    import jax

    return harness.run_cell(cell, seed, seconds, trace, devices=jax.devices(),
                            t_process=time.perf_counter(), store_root=tmp_path / "store",
                            trace_dir=tmp_path / "trace")
