"""The harness: files found by name, additions as new files alone, the
contract's shape of BENCHMARK.json, and no run off the chip."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT, spec
from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", [w["name"] for w in spec()["workloads"]])
def test_cells_find_their_files_by_name(name):
    s = spec()
    w = {w["name"]: w for w in s["workloads"]}[name]
    cell = harness.load_cell(name, s)
    assert cell.config["name"] == w["config"]
    kind = harness.kind_module(cell.kind)
    assert kind.run
    assert kind.reference_module(cell.config).lm_loss
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, name
    for m in cell.per_layer:
        assert m["moves"] in names
        assert harness.reader_module(m["name"]).read


def test_benchmark_json_keeps_the_contract_shape():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in s[key]]
        assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in s["configs"]:
        assert (ROOT / c["file"]).is_file() and all(NAME.match(k) for k in c["reduced"])
    for m in s["end_to_end"] + s["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in s["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    assert sum(w["chips"] == 4 for w in s["workloads"]) <= len(s["workloads"]) // 2


def test_a_config_cell_and_metric_are_added_as_files_alone(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench, ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    conf = json.loads((bench / "configs/qwen3-8b-train-share.json").read_text())
    conf.update(name="other-model")
    (bench / "configs/other-model.json").write_text(json.dumps(conf))
    traffic = json.loads((bench / "traffic/train_stream.json").read_text())
    traffic["batch"] = 8
    (bench / "traffic/train_big_batch.json").write_text(json.dumps(traffic))
    (bench / "metrics/train.steps_seen.py").write_text(
        "def read(rec):\n    return rec.counters.get('train.steps')\n")
    s = spec()
    s["configs"].append({"name": "other-model", "source": "https://example.org/other",
                         "file": "bench/configs/other-model.json", "reduced": [], "why": "test"})
    s["workloads"].append({"name": "train.big", "config": "other-model",
                           "traffic": "train_big_batch", "chips": 1, "why": "test"})
    s["per_layer"].append({"name": "train.steps_seen", "unit": "steps", "better": "higher",
                           "source": "program_counter", "layer": "train loop",
                           "moves": "train_tokens_per_s"})
    s["end_to_end"][0].setdefault("workloads", []).append("train.big")
    cell = harness.load_cell("train.big", s, bench_dir=bench)
    assert cell.config["name"] == "other-model" and cell.traffic["batch"] == 8
    assert cell.kind == "train"
    assert "train.steps_seen" in {m["name"] for m in cell.per_layer}
    rec = harness.RunRecord("train.big", cell.config, cell.traffic, {}, 1.0, [],
                            {"train.steps": 36}, None)
    assert harness.reader_module("train.steps_seen", bench).read(rec) == 36
    # the existing cells are untouched by the addition
    assert "train.steps_seen" in {m["name"] for m in harness.load_cell(
        "train.stream", s, bench_dir=bench).per_layer}


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no.such.cell", spec())
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v0 imaginary")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_the_reference_is_found_by_the_model_type():
    kind = harness.kind_module("train")
    conf = harness.load_cell("train.stream", spec()).config
    assert kind.reference_module(conf).__file__.endswith("bench/reference/qwen3.py")
    conf["hf"]["model_type"] = "no_such_family"
    with pytest.raises(harness.BenchError):
        kind.reference_module(conf)


def _run(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", "--workload", "train.stream",
                           "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0", *extra],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_off_the_chip_names_the_platform_and_fails():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert r.stdout.strip() == ""


def test_a_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "program under test is missing" in r.stderr
