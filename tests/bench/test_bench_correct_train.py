"""``correct`` of the train kind at a tiny size on the CPU: a sound run
passes; the float8 control and the planted faults fail."""

import time

import jax
import pytest

import bench_tiny as bt
from bench import calibrate, harness


@pytest.fixture(scope="module")
def readings():
    return calibrate.train_readings(bt.tiny_cell("train.stream"), 2**33 + 5)


def test_a_sound_run_is_correct(tmp_path):
    res = bt.run_tiny(bt.tiny_cell("train.ckpt"), tmp_path)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "update_gap", "ckpt_bad_leaves", "rows_bad"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert res["attempted"] == 15 and res["failed"] == 0


def test_the_window_holds_the_whole_save(tmp_path, monkeypatch):
    """The save's lane and its flush to the PFS tier end inside the window,
    however long they take against the steps."""
    from repro.core.store import TwoLevelStore
    from repro.runtime.checkpoint import CheckpointManager

    kind = harness.kind_module("train")
    bg, drain = CheckpointManager._bg_save, TwoLevelStore.drain

    def slow_lane(self, *a, **kw):
        time.sleep(0.5)
        return bg(self, *a, **kw)

    def slow_drain(self, *a, **kw):
        time.sleep(0.3)
        return drain(self, *a, **kw)

    monkeypatch.setattr(CheckpointManager, "_bg_save", slow_lane)
    monkeypatch.setattr(TwoLevelStore, "drain", slow_drain)
    cell = bt.tiny_cell("train.ckpt")
    ctx = harness.RunContext(cell, 2**33 + 9, 0.1, False, time.perf_counter(),
                             store_root=tmp_path / "store", trace_dir=tmp_path / "trace")
    d = kind.drive(ctx)
    lo, hi = ctx.window
    lanes = [(s, e) for n, s, e in ctx.spans if n == "bench.ckpt.background"]
    assert len(lanes) == 1 and lo <= lanes[0][0] and lanes[0][1] <= hi
    waits = [e - s for n, s, e in ctx.spans if n == "bench.ckpt.durable_wait"]
    assert len(waits) == 1 and waits[0] >= 0.3
    assert hi - lo >= 0.8 and sorted(d.probe.hashes) == [d.warm]


def test_the_control_fails_and_the_program_passes(readings):
    lim = bt.TINY_TRAIN_LIMITS
    assert all(readings["program"][k] <= lim[k] / 3 for k in ("loss_gap", "grad_gap", "update_gap"))
    assert any(readings["control"][k] > lim[k] for k in ("loss_gap", "grad_gap", "update_gap"))
    assert any(readings["half_batch"][k] > lim[k] for k in ("loss_gap", "grad_gap", "update_gap"))


def _faulty_step(fault):
    from repro.launch.steps import make_train_step

    def jit_train_step(model, cfg, optimizer, accum_steps=1):
        step = make_train_step(model, cfg, optimizer)

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return dict(state, step=state["step"] + 1), metrics

        def half_batch(state, batch):
            return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

        return jax.jit(unchanged if fault == "state_unchanged" else half_batch)

    return jit_train_step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    import repro.launch.train as T

    monkeypatch.setattr(T, "jit_train_step", _faulty_step(fault))
    res = bt.run_tiny(bt.tiny_cell("train.ckpt"), tmp_path)
    assert not res["correct"]
    failed = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failed & {"grad_gap", "update_gap", "loss_gap"}


def _corrupt_checkpoint(monkeypatch):
    from repro.runtime.checkpoint import CheckpointManager

    save = CheckpointManager.save
    monkeypatch.setattr(CheckpointManager, "save",
                        lambda self, step, state: save(self, step, dict(state, step=state["step"] + 1)))


def _alter_a_label(monkeypatch):
    from repro.data.pipeline import ShardedLoader

    nxt = ShardedLoader.__next__

    def altered(self):
        inputs, labels = nxt(self)
        labels = labels.copy()
        labels[0, 0] = (labels[0, 0] + 1) % 256
        return inputs, labels

    monkeypatch.setattr(ShardedLoader, "__next__", altered)


@pytest.mark.parametrize("fault,check", [(_corrupt_checkpoint, "ckpt_bad_leaves"),
                                         (_alter_a_label, "rows_bad")],
                         ids=["checkpoint_altered", "label_altered"])
def test_an_altered_answer_is_not_correct(tmp_path, monkeypatch, fault, check):
    fault(monkeypatch)
    res = bt.run_tiny(bt.tiny_cell("train.ckpt"), tmp_path)
    assert not res["correct"]
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]

