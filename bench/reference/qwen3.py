"""Plain reference of Qwen3 (hf Qwen/Qwen3-8B), for configurations whose
``model_type`` is ``qwen3``.

Written from the published architecture, in ``jax.numpy`` and float32 at
``Precision.HIGHEST``, with no kernel, cache or batching of the program's:
pre-norm RMSNorm blocks; grouped-query attention with per-head RMSNorm on
queries and keys before rotary embedding (rotate-half); SwiGLU MLP; untied
LM head.

Weights are read by the program's parameter names (as a checkpoint
converter maps a published checkpoint's names); nothing else of the
program is used.  ``quant="fp8"`` computes every matmul from float8
(e4m3) operands with float32 accumulation: the control, one precision
below the bfloat16 compute the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _q(x, quant):
    x = x.astype(jnp.float32)
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def mm(spec: str, a, b, quant=None):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_params(params: dict, n_layers: int) -> list[dict]:
    """Per-layer weights, from a scanned (``periods``) or unrolled stack."""
    if "periods" in params:
        stack = params["periods"]["slot_0"]
        return [jax.tree_util.tree_map(lambda x, i=i: x[i], stack) for i in range(n_layers)]
    return [params[f"prefix_{i}"] for i in range(n_layers)]


def rms_norm(scale, x, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def rope(x, positions, theta: float):
    """Rotary embedding, rotate-half form: x (B, S, H, D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p: dict, x, hf: dict, quant=None):
    b, s, _ = x.shape
    h, kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    eps = hf["rms_norm_eps"]
    q = rms_norm(p["q_norm"], mm("bsd,dhk->bshk", x, p["wq"], quant), eps)
    k = rms_norm(p["k_norm"], mm("bsd,dhk->bshk", x, p["wk"], quant), eps)
    v = mm("bsd,dhk->bshk", x, p["wv"], quant)
    pos = jnp.arange(s)
    q, k = rope(q, pos, hf["rope_theta"]), rope(k, pos, hf["rope_theta"])
    g = h // kv
    qg = q.reshape(b, s, kv, g, -1)
    scores = mm("bskgd,btkd->bkgst", qg, k, quant) / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = mm("bkgst,btkd->bskgd", probs, v, quant).reshape(b, s, h, -1)
    return mm("bshk,hkd->bsd", out, p["wo"], quant)


def mlp(p: dict, x, quant=None):
    hid = jax.nn.silu(mm("bsd,df->bsf", x, p["w_gate"], quant)) * mm("bsd,df->bsf", x, p["w_up"], quant)
    return mm("bsf,fd->bsd", hid, p["w_down"], quant)


def block(p: dict, x, hf: dict, quant=None):
    eps = hf["rms_norm_eps"]
    x = x + attention(p["mixer"], rms_norm(p["pre_norm"]["scale"], x, eps), hf, quant)
    return x + mlp(p["ffn"], rms_norm(p["pre_ffn_norm"]["scale"], x, eps), quant)


def logits(params: dict, tokens, conf: dict, quant=None, remat: bool = False):
    """Float32 logits (B, S, V) of a causal forward pass over ``tokens``."""
    hf = conf["hf"]
    if hf["model_type"] != "qwen3" or hf["tie_word_embeddings"]:
        raise ValueError(f"the qwen3 reference cannot run {hf['model_type']!r} (tied head: "
                         f"{hf['tie_word_embeddings']})")
    x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(jnp.float32)
    fn = jax.checkpoint(block, static_argnums=(2, 3)) if remat else block
    for p in layer_params(params, hf["num_hidden_layers"]):
        x = fn(p, x, _Frozen(hf), quant)
    x = rms_norm(params["final_norm"]["scale"], x, hf["rms_norm_eps"])
    return mm("bsd,dv->bsv", x, params["head"]["w"], quant)


class _Frozen(dict):
    """A dict usable as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def lm_loss(params: dict, inputs, labels, conf: dict, quant=None):
    """Mean next-token cross-entropy plus the configuration's z-loss."""
    lg = logits(params, inputs, conf, quant, remat=True)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold) + conf["train"]["z_loss_weight"] * jnp.mean(logz ** 2)


def adamw(params, grads, m, v, count: int, opt: dict):
    """One AdamW step (decoupled weight decay, global-norm clipping, linear
    warm-up); returns (params, m, v, clipped grads)."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / (gnorm + 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    lr = opt["peak_lr"] * min(count / opt["warmup_steps"], 1.0)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count

    def upd(p, m_, v_):
        step = (m_ / c1) / (jnp.sqrt(v_ / c2) + opt["eps"]) + opt["weight_decay"] * p
        return p - lr * step

    return jax.tree_util.tree_map(upd, params, m, v), m, v, grads
