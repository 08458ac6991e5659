"""The read-back of the train kind's checkpoint covers every leaf: a change
to any one leaf of the saved state, however large, makes a run not correct."""

import jax
import pytest

import bench_tiny as bt


def _alter_saved_leaf(monkeypatch, path):
    """Save the state with one element of the leaf at ``path`` changed."""
    from repro.runtime.checkpoint import CheckpointManager

    save = CheckpointManager.save

    def altered(self, step, state):
        state = jax.tree_util.tree_map(lambda x: x, state)  # a new tree, the same leaves
        node = state
        for key in path[:-1]:
            node = node[key]
        leaf = node[path[-1]]
        node[path[-1]] = leaf.at[(0,) * leaf.ndim].add(1.0)
        return save(self, step, state)

    monkeypatch.setattr(CheckpointManager, "save", altered)


#: the largest leaves of the state, and one of each optimizer moment
LARGE_LEAVES = [("params", "embed", "table"), ("params", "head", "w"),
                ("opt", "m", "periods", "slot_0", "ffn", "w_down"),
                ("opt", "v", "periods", "slot_0", "mixer", "wq")]


@pytest.mark.parametrize("path", LARGE_LEAVES, ids=["/".join(p) for p in LARGE_LEAVES])
def test_an_altered_large_leaf_in_the_checkpoint_is_not_correct(tmp_path, monkeypatch, path):
    _alter_saved_leaf(monkeypatch, path)
    res = bt.run_tiny(bt.tiny_cell("train.ckpt"), tmp_path)
    assert not res["correct"]
    assert res["checks"]["ckpt_bad_leaves"]["value"] == 1
