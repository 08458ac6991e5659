"""A configuration file mapped onto the program's config, and its seeded
weights: the Qwen3 share as before, the DeepSeek-V3 family's expert and
latent-attention keys, and the values the program has no mechanism for."""

import copy
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as bt
from bench import harness
from bench import model as bm

QWEN = bt.ROOT / "bench/configs/qwen3-8b-train-share.json"
MOONLIGHT = bt.DATA / "moonlight-16b-a3b-share.json"
TINY_DS = bt.DATA / "deepseek-v3-tiny.json"


def todays_arch_config(conf: dict):
    """The mapping as it stood before the expert and latent-attention keys."""
    from repro.configs import get_config

    hf_to_arch = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                  "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
                  "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab",
                  "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps", "norm_epsilon": "norm_eps",
                  "tie_word_embeddings": "tie_embeddings"}
    flags = {"norm": ("norm_type", {"rmsnorm": "rmsnorm", "layernorm": "layernorm"}),
             "mlp": ("mlp_type", {"swiglu": "swiglu", "gelu_tanh": "gelu"}),
             "qk_norm": ("qk_norm", {True: True, False: False}),
             "bias": ("use_bias", {True: True, False: False})}
    hf, arch = conf["hf"], conf["architecture"]
    kw = {f: hf[k] for k, f in hf_to_arch.items() if k in hf}
    kw["head_dim"] = int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"])
    for key, (field, table) in flags.items():
        kw[field] = table[arch[key]]
    kw.update(conf["program"])
    return dataclasses.replace(get_config(conf["program_arch"]), **kw)


def todays_init_leaf(key, path, shape, dtype, conf: dict):
    """The weights' distribution as it stood, by leaf position."""
    name = str(getattr(path[-1], "key", path[-1]))
    init = conf["init"]
    core = shape[1:] if any(getattr(p, "key", None) == "periods" for p in path) else shape
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "table":
        v = z * init["embed_std"]
    elif name in ("scale", "q_norm", "k_norm"):
        v = 1.0 + z * init["norm_std"]
    elif name in ("bias", "bq", "bk", "bv", "b_up", "b_down"):
        v = z * init["bias_std"]
    else:
        fan_in = core[0] * core[1] if name == "wo" else core[0]
        v = z / jnp.sqrt(jnp.float32(fan_in))
    return v.astype(dtype)


def test_the_qwen3_share_maps_as_before():
    conf = harness.load_config(QWEN)
    assert bm.arch_config(conf) == todays_arch_config(conf)


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_the_qwen3_weights_are_bit_identical_to_before(monkeypatch, seed):
    from repro.configs import make_model

    conf = bt.tiny_cell("train.stream").config
    cfg = bm.arch_config(conf)
    model = make_model(cfg)
    now = jax.tree_util.tree_leaves(bm.make_params(model, cfg, conf, seed))
    monkeypatch.setattr(bm, "init_leaf", todays_init_leaf)
    before = jax.tree_util.tree_leaves(bm.make_params(model, cfg, conf, seed))
    assert len(now) == len(before) > 0
    for a, b in zip(now, before):
        assert np.array_equal(np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32))


def test_a_catalog_file_holds_its_published_keys_at_the_top_level():
    conf = harness.load_config(MOONLIGHT)
    assert conf["hf"]["hidden_size"] == 2048 and conf["hf"]["q_lora_rank"] is None
    assert not set(conf["hf"]) & set(harness.CONFIG_BLOCKS)
    assert harness.load_config(QWEN)["hf"]["hidden_size"] == 4096  # an ``hf`` block stays as it is


def _stand_in(cls, drop=(), **types):
    """``cls`` as a new frozen dataclass without the fields ``drop`` and
    with the annotations ``types`` given."""
    return dataclasses.make_dataclass(cls.__name__, [
        (f.name, types.get(f.name, f.type),
         dataclasses.field(default=f.default, default_factory=f.default_factory))
        for f in dataclasses.fields(cls) if f.name not in drop], frozen=True)


@pytest.fixture
def program_without_shares(monkeypatch):
    """The program's MoE and MLA configs without the fields a chip's expert
    share, the routed scaling and a null query rank need, whether or not the
    program has them, so that the refusals are tested as such."""
    from repro.configs import base, get_config

    get_config("deepseek_v3_671b")  # imported before the patch, so it keeps the program's classes
    monkeypatch.setattr(base, "MoEConfig", _stand_in(base.MoEConfig, ("experts_held", "routed_scale")))
    monkeypatch.setattr(base, "MLAConfig", _stand_in(base.MLAConfig, q_lora_rank="int"))


def test_the_moonlight_share_needs_the_programs_expert_share(program_without_shares):
    with pytest.raises(harness.BenchError, match="MoEConfig.experts_held"):
        bm.arch_config(harness.load_config(MOONLIGHT))


def test_a_null_query_rank_needs_a_direct_query_projection(program_without_shares):
    conf = harness.load_config(MOONLIGHT)
    conf["hf"].update(n_routed_experts=64, routed_scaling_factor=1.0)
    with pytest.raises(harness.BenchError, match="q_lora_rank null"):
        bm.arch_config(conf)


@pytest.fixture
def program_with_shares(monkeypatch):
    """The program's MoE and MLA configs with the fields a chip's expert
    share, the routed scaling and a null query rank need: the program's own
    where it has them."""
    from repro.configs import base, get_config

    get_config("deepseek_v3_671b")  # imported before the patch, so it keeps the program's classes
    if not {"experts_held", "routed_scale"} <= {f.name for f in dataclasses.fields(base.MoEConfig)}:

        @dataclasses.dataclass(frozen=True)
        class MoE(base.MoEConfig):
            experts_held: int = 0
            routed_scale: float = 1.0

        monkeypatch.setattr(base, "MoEConfig", MoE)
    q_rank = next(f for f in dataclasses.fields(base.MLAConfig) if f.name == "q_lora_rank")
    if "None" not in str(q_rank.type):

        @dataclasses.dataclass(frozen=True)
        class MLA(base.MLAConfig):
            q_lora_rank: int | None = 1536

        monkeypatch.setattr(base, "MLAConfig", MLA)


#: the MoEConfig and MLAConfig fields a configuration file maps (its published
#: keys and ``program.moe``'s ``capacity_factor``); the program's may hold more
MOE_MAPPED = ("n_experts", "top_k", "expert_ff", "n_shared", "capacity_factor", "router_type",
              "normalize_gates", "first_k_dense")
MLA_MAPPED = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")


def _fields_of(obj, names) -> dict:
    return {n: getattr(obj, n) for n in names}


def test_the_moonlight_share_maps_every_published_key(program_with_shares):
    cfg = bm.arch_config(harness.load_config(MOONLIGHT))
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (6, 2048, 16, 11264, 20480)
    assert (cfg.attn_type, cfg.mtp, cfg.tie_embeddings, cfg.rope_theta, cfg.norm_eps) == (
        "mla", False, False, 50000, 1e-05)
    assert _fields_of(cfg.mla, MLA_MAPPED) == {"q_lora_rank": None, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                                               "qk_rope_head_dim": 64, "v_head_dim": 128}
    assert _fields_of(cfg.moe, MOE_MAPPED + ("experts_held", "routed_scale")) == {
        "n_experts": 64, "experts_held": 8, "top_k": 6, "expert_ff": 1408, "n_shared": 2,
        "capacity_factor": 10.7, "router_type": "sigmoid", "normalize_gates": True, "first_k_dense": 1,
        "routed_scale": 2.446}


def test_the_tiny_family_file_maps_onto_the_programs_reduced_shape():
    from repro.configs import get_reduced

    cfg = bm.arch_config(harness.load_config(TINY_DS))
    red = get_reduced("deepseek_v3_671b")
    assert _fields_of(cfg.mla, MLA_MAPPED) == _fields_of(red.mla, MLA_MAPPED)
    assert _fields_of(cfg.moe, MOE_MAPPED) == _fields_of(red.moe, MOE_MAPPED)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (
        red.n_layers, red.d_model, red.n_heads, red.d_ff, red.vocab)
    assert cfg.attn_type == "mla" and not cfg.mtp


@pytest.mark.parametrize("key,value", [
    ("moe_layer_freq", 2), ("n_group", 8), ("topk_group", 4), ("rope_scaling", {"type": "yarn"}),
    ("num_nextn_predict_layers", 1), ("topk_method", "group_limited_greedy"), ("scoring_func", "sqrtsoftplus"),
])
def test_a_value_the_program_has_no_mechanism_for_is_an_error(key, value):
    conf = harness.load_config(TINY_DS)
    conf["hf"][key] = value
    with pytest.raises(harness.BenchError, match=re.escape(f"{key} {value!r}")):
        bm.arch_config(conf)


def test_noaux_tc_needs_the_balancing_bias_assumed_zero():
    conf = harness.load_config(TINY_DS)
    del conf["assumed"]["e_score_correction_bias"]
    with pytest.raises(harness.BenchError, match="e_score_correction_bias"):
        bm.arch_config(conf)
    conf["hf"]["topk_method"] = "greedy"
    assert bm.arch_config(conf).moe.n_experts == 8


@pytest.mark.parametrize("drop,inherited", [
    (("num_nextn_predict_layers",), "mtp"),
    (("n_routed_experts",), "moe"),
    (("kv_lora_rank",), "mla"),
])
def test_a_mechanism_the_file_does_not_state_is_an_error(drop, inherited):
    conf = harness.load_config(TINY_DS)
    for key in drop:
        del conf["hf"][key]
    if inherited == "moe":
        del conf["program"]["moe"]
    with pytest.raises(harness.BenchError, match=f"does not state: .*'{inherited}'"):
        bm.arch_config(conf)


def test_execution_fields_may_not_restate_a_published_one():
    conf = harness.load_config(TINY_DS)
    conf["program"]["moe"]["top_k"] = 4
    with pytest.raises(harness.BenchError, match=r"program.moe restates the mapped \['top_k'\]"):
        bm.arch_config(conf)
    conf = copy.deepcopy(harness.load_config(QWEN))
    conf["program"]["moe"] = {"capacity_factor": 2.0}
    with pytest.raises(harness.BenchError, match="states no experts"):
        bm.arch_config(conf)


@pytest.mark.parametrize("name,shape,fan_in", [
    ("w_gate", (5, 64, 2048, 1408), 2048),  # scanned expert stack: (periods, E, d, f)
    ("w_up", (64, 2048, 1408), 2048),
    ("w_down", (5, 64, 1408, 2048), 1408),
    ("w_gate", (2048, 11264), 2048),  # dense or shared
    ("router", (5, 2048, 64), 2048),
    ("wq", (2048, 16, 192), 2048),
    ("wq_a", (2048, 1536), 2048),
    ("wq_b", (5, 1536, 16, 192), 1536),
    ("wkv_a", (2048, 576), 2048),
    ("wkv_b", (512, 16, 256), 512),
    ("wo", (5, 16, 128, 2048), 16 * 128),
    ("w", (2048, 20480), 2048),
])
def test_a_projections_fan_in_is_its_contracted_axes(name, shape, fan_in):
    assert bm.fan_in(name, shape) == fan_in


def test_latent_attention_norms_are_scales_and_unknown_weights_an_error():
    conf = harness.load_config(TINY_DS)
    key = jax.random.PRNGKey(0)
    path = (jax.tree_util.DictKey("mixer"), jax.tree_util.DictKey("kv_a_norm"))
    v = np.asarray(bm.init_leaf(key, path, (4096,), jnp.float32, conf))
    assert abs(v.mean() - 1.0) < 0.01 and abs(v.std() - 0.1) < 0.01
    with pytest.raises(harness.BenchError, match="'w_new'"):
        bm.init_leaf(key, (jax.tree_util.DictKey("w_new"),), (8, 8), jnp.float32, conf)
