"""A configuration file as the program runs it, and the seeded weights.

The configuration files (``bench/configs/<name>.json``) hold the model in
the published config's own keys (``hf``), the cuts (``reduced``), what was
assumed, the precision and the store settings.  This module maps them onto
the program's ``ArchConfig`` and makes the weights from ``--seed``: one
jitted call on the device, in the dtype the configuration states, with the
program's parameter names (taken from its abstract init) and the
benchmark's own distribution.  The plain reference reads the same names.
"""

from __future__ import annotations

import dataclasses

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
#: the configuration's ``architecture`` block -> ArchConfig field and value
ARCH_FLAGS = {
    "norm": ("norm_type", {"rmsnorm": "rmsnorm", "layernorm": "layernorm"}),
    "mlp": ("mlp_type", {"swiglu": "swiglu", "gelu_tanh": "gelu"}),
    "qk_norm": ("qk_norm", {True: True, False: False}),
    "bias": ("use_bias", {True: True, False: False}),
}


def head_dim(hf: dict) -> int:
    return int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"])


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file; every key the
    file states is carried over, and a flag the program cannot honour is
    an error rather than a silent departure."""
    from repro.configs import get_config

    hf, arch = conf["hf"], conf["architecture"]
    kw = {f: hf[k] for k, f in HF_TO_ARCH.items() if k in hf}
    kw["head_dim"] = head_dim(hf)
    for key, (field, table) in ARCH_FLAGS.items():
        kw[field] = table[arch[key]]
    kw.update(conf["program"])
    cfg = dataclasses.replace(get_config(conf["program_arch"]), **kw)
    if cfg.window or cfg.attn_logit_softcap or cfg.moe or cfg.mla or cfg.post_norms or cfg.embed_scale:
        raise ValueError(f"{conf['name']}: the program config has parts the file does not state")
    return cfg


def _leaf_key(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _stacked(path) -> bool:
    return any(getattr(p, "key", None) == "periods" for p in path)


def init_leaf(key, path, shape, dtype, conf: dict):
    """One weight from the benchmark's own distribution, by its name."""
    import jax
    import jax.numpy as jnp

    name = _leaf_key(path)
    init = conf["init"]
    core = shape[1:] if _stacked(path) else shape
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
