"""Operation and byte counts from shapes, against counts by hand."""

import json

import pytest

from bench_tiny import DATA, ROOT
from bench import flops, harness

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


MOONLIGHT = harness.load_config(DATA / "moonlight-16b-a3b-share.json")


def test_moonlight_share_matmul_weights():
    # MLA with no query low-rank path: q 2048x16x192, kv latent 2048x(512+64),
    # its expansion 512x16x(128+128), o 16x128x2048
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    dense = attn + 3 * 2048 * 11264
    # router over all 64 experts, 2 shared experts, and top 6 of 64 experts
    # of which 8 are held: 6 x 8/64 of one 3x2048x1408 expert a token
    moe = attn + 2048 * 64 + 2 * 3 * 2048 * 1408 + 6 * 8 * 3 * 2048 * 1408 // 64
    assert (dense, moe) == (82_968_576, 37_683_200)
    got = flops.matmul_params(MOONLIGHT["hf"], MOONLIGHT["published"])
    assert got == dense + 5 * moe + 2048 * 20_480 == 313_327_616


def test_moonlight_share_train_flops_per_token():
    # attention per key and layer 2x16x(128+64) + 2x16x128, 6 layers, mean
    # context 1024.5 at 2048, x3 for the backward
    attn = 3 * 6 * 10_240 * 1024.5
    got = flops.train_flops_per_token(MOONLIGHT["hf"], 2048, MOONLIGHT["published"])
    assert got == 6 * 313_327_616 + attn == 2_068_801_536


def test_deepseek_v3_tiny_matmul_weights_with_the_query_latent():
    conf = harness.load_config(DATA / "deepseek-v3-tiny.json")
    # q 64x32 + 32x4x24, kv 64x24 + 16x4x32, o 4x16x64; every expert held
    attn = 64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 4 * 16 * 64
    dense = attn + 3 * 64 * 128
    moe = attn + 64 * 8 + 3 * 64 * 32 + 2 * 3 * 64 * 32
    assert flops.matmul_params(conf["hf"], conf["published"]) == dense + 3 * moe + 64 * 512 == 165_376
