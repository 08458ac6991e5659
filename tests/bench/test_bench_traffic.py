"""The train kind's inputs and work: the same seed gives the same weights
and corpus, and the window plan fixes the steps and saves of a window."""

import numpy as np
import pytest

import bench_tiny as bt
from bench import model as bm
from bench.harness import kind_module, load_json

BIG = 2**33 + 12345
TRAIN = kind_module("train")


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import make_model

    conf = bt.tiny_cell("train.stream").config
    cfg = bm.arch_config(conf)
    return make_model(cfg), cfg, conf


def _leaves(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("seed", [3, BIG], ids=["small", "over_32_bits"])
def test_same_seed_same_weights(tiny_model, seed):
    model, cfg, conf = tiny_model
    a = _leaves(bm.make_params(model, cfg, conf, seed))
    b = _leaves(bm.make_params(model, cfg, conf, seed))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert all(x.dtype == np.float32 for x in a)  # the configuration's parameter dtype


def test_seeds_that_differ_past_32_bits_give_other_weights(tiny_model):
    model, cfg, conf = tiny_model
    a = _leaves(bm.make_params(model, cfg, conf, 5))
    b = _leaves(bm.make_params(model, cfg, conf, 5 + 2**32))
    assert not np.array_equal(a[0], b[0])


def _corpus(root, seed):
    from repro.core.store import TwoLevelStore
    from repro.data.pipeline import SyntheticCorpus

    with TwoLevelStore(str(root), mem_capacity_bytes=8 << 20) as store:
        c = SyntheticCorpus(store, vocab_size=256, n_shards=2, tokens_per_shard=1024, seed=seed)
        c.generate()
        return [c.read_shard(i).copy() for i in range(2)]


def test_same_seed_same_corpus(tmp_path):
    a, b = _corpus(tmp_path / "a", BIG), _corpus(tmp_path / "b", BIG)
    c = _corpus(tmp_path / "c", BIG + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])


@pytest.mark.parametrize("name,saves", [("train_ckpt", 1), ("train_stream", 0)])
def test_train_window_holds_its_saves(name, saves):
    traffic = load_json(bt.ROOT / f"bench/traffic/{name}.json")
    for seconds in (3, 10, 51):
        warm, total, every = TRAIN.window_plan(traffic, seconds)
        # the window opens after step `warm`, before that step's save
        in_window = [s for s in range(warm, total + 1) if s % every == 0]
        assert len(in_window) == saves
        assert not any(s % every == 0 for s in range(1, warm))
        cost = traffic.get("save_s", 0.0) if saves else 0.0
        assert total - warm == max(1, int(np.ceil((seconds - cost) / traffic["step_s"])))


def test_the_save_opens_the_window_and_its_cost_fills_the_seconds():
    traffic = load_json(bt.ROOT / "bench/traffic/train_ckpt.json")
    warm, total, every = TRAIN.window_plan(traffic, 51)
    assert every == warm  # the save of the last warm-up step, made after the window opens
    assert total < 2 * every  # and the run's only one
    assert (total - warm) * traffic["step_s"] + traffic["save_s"] == pytest.approx(51, abs=0.3)
