"""``correct`` of the train kind on a DeepSeek-V3-family configuration
(``data/deepseek-v3-tiny.json``) at a tiny size on the CPU: a sound run
passes; an expert layer that drops its shared expert or changes its gates'
scale fails."""

import dataclasses

import pytest

import bench_tiny as bt

CONFIG = "deepseek-v3-tiny.json"


def test_a_sound_run_is_correct(tmp_path):
    res = bt.run_tiny(bt.family_cell(CONFIG), tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 15 and res["failed"] == 0


def _shared_expert_dropped(moe):
    return dataclasses.replace(moe, n_shared=0)


def _gate_scale_changed(moe):
    return dataclasses.replace(moe, normalize_gates=False)


@pytest.mark.parametrize("fault", [_shared_expert_dropped, _gate_scale_changed],
                         ids=["shared_expert_dropped", "gate_scale_changed"])
def test_a_broken_expert_layer_is_not_correct(tmp_path, monkeypatch, fault):
    import repro.nn.layers as L

    moe_apply = L.moe_apply
    monkeypatch.setattr(L, "moe_apply", lambda p, x, cfg, *a, **kw: moe_apply(
        p, x, dataclasses.replace(cfg, moe=fault(cfg.moe)), *a, **kw))
    res = bt.run_tiny(bt.family_cell(CONFIG), tmp_path)
    assert not res["correct"]
    failed = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failed & {"grad_gap", "update_gap", "loss_gap"}
