"""Operation and byte counts from shapes, against counts by hand."""

import json

import pytest

from bench_tiny import ROOT
from bench import flops

QWEN = json.loads((ROOT / "bench/configs/qwen3-8b-train-share.json").read_text())["hf"]


def test_qwen3_share_matmul_weights():
    # per layer: q 4096x4096, k and v 4096x1024 each, o 4096x4096, SwiGLU 3x4096x12288
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 12288
    assert layer == 192_937_984
    # two layers and the untied LM head over the 18,992-row share
    assert flops.matmul_params(QWEN) == 2 * layer + 4096 * 18_992 == 463_667_200


def test_qwen3_share_train_flops_per_token():
    matmuls = 6 * 463_667_200  # 2.78 GFLOP before attention
    assert matmuls == pytest.approx(2.782e9, rel=1e-3)
    # causal attention at 2048: 2 layers x 4 x 32 heads x 128 x mean context 1024.5, x3
    attn = 3 * 2 * 4 * 32 * 128 * 1024.5
    assert flops.train_flops_per_token(QWEN, 2048) == pytest.approx(matmuls + attn)
