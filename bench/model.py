"""A configuration file as the program runs it, and the seeded weights.

The configuration files (``bench/configs/<name>.json``) hold the model in
the published config's own keys (``hf``, or the file's top level), the cuts
(``reduced``), what was assumed, the precision and the store settings.
This module maps them onto the program's ``ArchConfig``, with its
``MoEConfig`` and ``MLAConfig`` for expert and latent-attention keys, and
makes the weights from ``--seed``: one jitted call on the device, in the
dtype the configuration states, with the program's parameter names (taken
from its abstract init) and the benchmark's own distribution, each leaf by
its role.  The plain reference reads the same names.
"""

from __future__ import annotations

import dataclasses
import math

from bench.harness import BenchError

#: published config key -> ArchConfig field
HF_TO_ARCH = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "norm_epsilon": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
#: published mixture-of-experts key -> MoEConfig field (``n_routed_experts``,
#: ``scoring_func`` and ``routed_scaling_factor`` are mapped by hand)
HF_TO_MOE = {
    "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "expert_ff",
    "n_shared_experts": "n_shared",
    "first_k_dense_replace": "first_k_dense",
    "norm_topk_prob": "normalize_gates",
}
#: published latent-attention keys, named alike in MLAConfig; present
#: ``kv_lora_rank`` makes the attention MLA
MLA_KEYS = ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
#: published keys whose other values the program has no mechanism for
ONLY = {
    "moe_layer_freq": (1,),
    "n_group": (1,),
    "topk_group": (1,),
    "rope_scaling": (None,),
    "num_nextn_predict_layers": (0,),
    "topk_method": ("greedy", "noaux_tc"),
    "scoring_func": ("sigmoid", "softmax"),
}
#: ArchConfig fields of a mechanism: each has to come from the file, and one
#: that ``program_arch``'s config() sets and the file does not state is an error
MECHANISMS = ("moe", "mla", "mtp", "window", "global_every", "attn_logit_softcap", "post_norms",
              "embed_scale", "recurrent", "encdec", "vlm")
#: the configuration's ``architecture`` block -> ArchConfig field and value
ARCH_FLAGS = {
    "norm": ("norm_type", {"rmsnorm": "rmsnorm", "layernorm": "layernorm"}),
    "mlp": ("mlp_type", {"swiglu": "swiglu", "gelu_tanh": "gelu"}),
    "qk_norm": ("qk_norm", {True: True, False: False}),
    "bias": ("use_bias", {True: True, False: False}),
}


def head_dim(hf: dict) -> int:
    return int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"])


def _merged(conf: dict, where: str, mapped: dict, given: dict) -> dict:
    """The mapped fields with the execution fields the file's ``program``
    block gives (``scan_layers``, a ``moe``'s ``capacity_factor``), which
    may not restate a mapped one."""
    clash = sorted(set(mapped) & set(given))
    if clash:
        raise BenchError(f"{conf['name']}: {where} restates the mapped {clash}")
    return {**mapped, **given}


def _moe_config(conf: dict, base, given: dict):
    """The program's MoEConfig for the file's published expert keys.

    ``hf.n_routed_experts`` is how many experts this chip holds (a key
    listed in ``reduced``); ``published.n_routed_experts`` is the router's
    width, which the program's ``n_experts`` takes.  A held share smaller
    than the published count needs the program's ``experts_held``, and a
    ``routed_scaling_factor`` other than 1 its ``routed_scale``: where the
    program's MoEConfig has no such field, the file cannot be run."""
    hf = conf["hf"]
    held = int(hf["n_routed_experts"])
    routed = int(conf.get("published", {}).get("n_routed_experts", held))
    if held > routed:
        raise BenchError(f"{conf['name']}: n_routed_experts {held} held of {routed} published")
    if hf.get("topk_method") == "noaux_tc" and conf.get("assumed", {}).get("e_score_correction_bias") != 0:
        raise BenchError(f"{conf['name']}: topk_method 'noaux_tc' needs assumed.e_score_correction_bias 0 "
                         "(the balancing bias held at zero, never updated)")
    kw = {f: hf[k] for k, f in HF_TO_MOE.items() if k in hf}
    kw["n_experts"] = routed
    if "scoring_func" in hf:
        kw["router_type"] = hf["scoring_func"]
    wanted = {}
    if held < routed:
        wanted["experts_held"] = ("n_routed_experts", held)
    scale = float(hf.get("routed_scaling_factor", 1.0))
    if scale != 1.0:
        wanted["routed_scale"] = ("routed_scaling_factor", scale)
    fields = {f.name for f in dataclasses.fields(base.MoEConfig)}
    missing = [f"MoEConfig.{f} (published key {k})" for f, (k, _) in wanted.items() if f not in fields]
    if missing:
        raise BenchError(f"{conf['name']}: the program has no field " + ", ".join(missing))
    kw.update({f: v for f, (_, v) in wanted.items()})
    return base.MoEConfig(**_merged(conf, "program.moe", kw, given))


def _mla_config(conf: dict, base, given: dict):
    """The program's MLAConfig; a null ``q_lora_rank`` (no query low-rank
    path) needs a program whose ``q_lora_rank`` admits None."""
    kw = {k: conf["hf"][k] for k in MLA_KEYS if k in conf["hf"]}
    if "q_lora_rank" in kw and kw["q_lora_rank"] is None:
        ann = next(f.type for f in dataclasses.fields(base.MLAConfig) if f.name == "q_lora_rank")
        if "None" not in str(ann):
            raise BenchError(f"{conf['name']}: q_lora_rank null needs MLAConfig.q_lora_rank to admit None "
                             f"(a direct query projection 'wq'); the program's is {ann}")
    return base.MLAConfig(**_merged(conf, "program.mla", kw, given))


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file; every key the
    file states is carried over, and a key, value or mechanism the program
    cannot honour is a ``BenchError`` rather than a silent departure."""
    from repro.configs import base, get_config

    hf, arch = conf["hf"], conf["architecture"]
    for key, allowed in ONLY.items():
        if key in hf and hf[key] not in allowed:
            raise BenchError(f"{conf['name']}: {key} {hf[key]!r} is not one of {list(allowed)}")
    kw = {f: hf[k] for k, f in HF_TO_ARCH.items() if k in hf}
    kw["head_dim"] = head_dim(hf)
    for key, (field, table) in ARCH_FLAGS.items():
        kw[field] = table[arch[key]]
    program = dict(conf["program"])
    moe, mla = program.pop("moe", None), program.pop("mla", None)
    if "n_routed_experts" in hf:
        kw["moe"] = _moe_config(conf, base, moe or {})
    elif moe is not None:
        raise BenchError(f"{conf['name']}: program.moe given, but the file states no experts")
    if "kv_lora_rank" in hf:
        kw["mla"], kw["attn_type"] = _mla_config(conf, base, mla or {}), "mla"
    elif mla is not None:
        raise BenchError(f"{conf['name']}: program.mla given, but the file states no latent attention")
    if "num_nextn_predict_layers" in hf:
        kw["mtp"] = hf["num_nextn_predict_layers"] > 0
    cfg = dataclasses.replace(get_config(conf["program_arch"]), **_merged(conf, "program", kw, program))
    inherited = [f for f in MECHANISMS if f not in kw and getattr(cfg, f)]
    if inherited:
        raise BenchError(f"{conf['name']}: the program config has parts the file does not state: {inherited}")
    return cfg


#: norm scales, drawn as 1 + norm_std * z
SCALES = ("scale", "q_norm", "k_norm", "q_a_norm", "kv_a_norm")
BIASES = ("bias", "bq", "bk", "bv", "b_up", "b_down")
#: projection -> (contracted axes, output axes), counted from the end of its
#: shape; leading axes (the scan's ``periods``, an expert stack) are neither
PROJECTIONS = {
    "wq": (1, 2), "wk": (1, 2), "wv": (1, 2), "wo": (2, 1),
    "wq_a": (1, 1), "wq_b": (1, 2), "wkv_a": (1, 1), "wkv_b": (1, 2),
    "router": (1, 1), "w_gate": (1, 1), "w_up": (1, 1), "w_down": (1, 1), "w": (1, 1),
}


def _leaf_key(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def fan_in(name: str, shape) -> int:
    """The size of a projection's contracted axes: d for ``wq``, ``router``
    or an expert's ``w_gate``, f for ``w_down``, the latent rank for
    ``wq_b`` and ``wkv_b``, heads x head size for ``wo``."""
    if name not in PROJECTIONS:
        raise BenchError(f"no initialisation for a weight named {name!r} of shape {tuple(shape)}")
    n_in, n_out = PROJECTIONS[name]
    return math.prod(shape[len(shape) - n_in - n_out: len(shape) - n_out])


def init_leaf(key, path, shape, dtype, conf: dict):
    """One weight from the benchmark's own distribution, by its role."""
    import jax
    import jax.numpy as jnp

    name = _leaf_key(path)
    init = conf["init"]
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "table":
        v = z * init["embed_std"]
    elif name in SCALES:
        v = 1.0 + z * init["norm_std"]
    elif name in BIASES:
        v = z * init["bias_std"]
    else:
        v = z / jnp.sqrt(jnp.float32(fan_in(name, shape)))
    return v.astype(dtype)


def seed_key(seed: int):
    """A PRNG key from any whole seed (wider than 32 bits included)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF)


def make_params(model, cfg, conf: dict, seed: int, dtype=None, device=None):
    """The weights for ``seed``: one jitted call, on the device, in ``dtype``
    (the configuration's parameter dtype by default)."""
    import jax
    import jax.numpy as jnp

    from repro.nn.module import init_with_axes

    dtype = jnp.dtype(dtype or conf["program"]["param_dtype"])
    abstract, _ = init_with_axes(model.init, jax.random.PRNGKey(0), abstract=True, dtype=dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def build(key):
        keys = jax.random.split(key, len(flat))
        return treedef.unflatten([
            init_leaf(k, p, leaf.shape, dtype, conf) for k, (p, leaf) in zip(keys, flat)
        ])

    out_sh = None if device is None else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=out_sh)(seed_key(seed))
