"""Plain reference of the DeepSeek-V3 family (hf deepseek-ai/DeepSeek-V3,
arXiv:2412.19437; Moonlight-16B-A3B is of it), for configurations whose
``model_type`` is ``deepseek_v3``.

Written from the published architecture, in ``jax.numpy`` and float32 at
``Precision.HIGHEST``, with no kernel, cache, dispatch or capacity of the
program's: pre-norm RMSNorm blocks; multi-head latent attention (MLA): the
query from a low-rank latent with its RMSNorm (``q_lora_rank``) or, where
that is null, projected directly; keys and values from one
``kv_lora_rank`` latent with its RMSNorm, beside a ``qk_rope_head_dim``
key shared by the heads; rotary embedding on the rope part of query and
key; scores over ``qk_nope_head_dim + qk_rope_head_dim`` scaled by its
inverse square root.  The first ``first_k_dense_replace`` layers have a
SwiGLU MLP of ``intermediate_size``; each other layer a mixture of
experts: sigmoid or softmax scores (``scoring_func``) over all published
experts, the top ``num_experts_per_tok`` chosen, their scores as gates,
normalised (``norm_topk_prob``) and times ``routed_scaling_factor``;
SwiGLU experts of ``moe_intermediate_size``, and ``n_shared_experts``
shared experts that every token passes.  Untied LM head.

Expert share: the configuration's ``hf.n_routed_experts`` experts are
held here, experts ``0 .. held - 1`` of ``published.n_routed_experts``.
The router scores all of them; only the held experts' part of the
result is summed, in the reference as in the program (guide: the chip's
share of a stated deployment).

Loss: mean next-token cross-entropy, the z-loss, and the load-balance
term the configuration states (``train.load_balance``): only the form
``top1_softmax``, E x sum_e f_e P_e per expert layer with E the published
experts, f_e the share of tokens whose first choice is e and P_e the mean
softmax of the router's logits, summed over layers, times its weight.

Departures from the published model, each what a checkpoint converter or
the configuration states: the rope dimensions in rotate-half order (the
permutation a converter applies to ``wq_b``'s and ``wkv_a``'s rope
columns in deepseek_v3's interleaved layout); ``noaux_tc``'s balancing
bias held at zero, so the choice is the top k of the scores (one group);
the load-balance term above in place of the published one; no
multi-token prediction module (``num_nextn_predict_layers`` 0).

Weights are read by the program's parameter names; nothing else of the
program is used.  ``quant="fp8"`` computes every matmul from float8
(e4m3) operands with float32 accumulation: the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.qwen3 import HIGHEST, _Frozen, adamw, mlp, mm, rms_norm, rope

__all__ = ["adamw", "_Frozen", "logits", "lm_loss", "batch_stats"]

#: the load-balance forms this reference computes
LOAD_BALANCE_FORMS = ("top1_softmax",)


def routed_experts(conf: dict) -> int:
    """The router's width: the published expert count."""
    hf = conf["hf"]
    return int(conf.get("published", {}).get("n_routed_experts", hf["n_routed_experts"]))


def layer_params(params: dict, n_layers: int, n_dense: int) -> list[dict]:
    """Per-layer weights: the dense layers unrolled, the expert layers from
    a scanned (``periods``) stack, or every layer unrolled."""
    if "periods" not in params:
        return [params[f"prefix_{i}"] for i in range(n_layers)]
    stack = params["periods"]["slot_0"]
    return [params[f"prefix_{i}"] for i in range(n_dense)] + [
        jax.tree_util.tree_map(lambda x, i=i: x[i], stack) for i in range(n_layers - n_dense)]


def attention(p: dict, x, hf: dict, quant=None):
    s = x.shape[1]
    nope, rd, r = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["kv_lora_rank"]
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    if hf.get("q_lora_rank"):
        cq = rms_norm(p["q_a_norm"], mm("bsd,dr->bsr", x, p["wq_a"], quant), eps)
        q = mm("bsr,rhk->bshk", cq, p["wq_b"], quant)
    else:
        q = mm("bsd,dhk->bshk", x, p["wq"], quant)
    kv_a = mm("bsd,dr->bsr", x, p["wkv_a"], quant)
    c_kv = rms_norm(p["kv_a_norm"], kv_a[..., :r], eps)
    kv = mm("bsr,rhk->bshk", c_kv, p["wkv_b"], quant)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    pos = jnp.arange(s)
    q_pe = rope(q[..., nope:], pos, theta)
    k_pe = rope(kv_a[..., None, r:], pos, theta)[:, :, 0]
    scores = (mm("bshk,bthk->bhst", q[..., :nope], k_nope, quant)
              + mm("bshk,btk->bhst", q_pe, k_pe, quant)) / jnp.sqrt(jnp.float32(nope + rd))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = mm("bhst,bthk->bshk", probs, v, quant)
    return mm("bshk,hkd->bsd", out, p["wo"], quant)


def route(p: dict, x, hf: dict, n_routed: int, quant=None):
    """Gates (B, S, E) over all E published experts, zero but at each
    token's chosen ones; the router's softmax (B, S, E); and each token's
    first choice, one-hot (B, S, E)."""
    if hf["topk_method"] not in ("greedy", "noaux_tc"):
        raise ValueError(f"topk_method {hf['topk_method']!r}: the reference chooses the plain top k")
    logits = mm("bsd,de->bse", x, p["router"], quant)
    scores = jax.nn.sigmoid(logits) if hf["scoring_func"] == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(scores, hf["num_experts_per_tok"])  # + noaux_tc's bias, zero
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if hf["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * hf.get("routed_scaling_factor", 1.0)
    gates = jnp.einsum("bske,bsk->bse", jax.nn.one_hot(idx, n_routed), w, precision=HIGHEST)
    return gates, jax.nn.softmax(logits, axis=-1), jax.nn.one_hot(idx[..., 0], n_routed)


def held_part(p: dict, x, gates, first: int = 0, quant=None):
    """What the experts ``first .. first + held - 1`` (the stacks in ``p``)
    add to the layer's output, each weighted by its gate."""
    held = p["w_gate"].shape[0]
    hid = jax.nn.silu(mm("bsd,edf->bsef", x, p["w_gate"], quant)) * mm("bsd,edf->bsef", x, p["w_up"], quant)
    y = mm("bsef,efd->bsed", hid, p["w_down"], quant)
    return jnp.einsum("bse,bsed->bsd", gates[..., first:first + held], y, precision=HIGHEST)


def moe(p: dict, x, hf: dict, n_routed: int, quant=None):
    """The expert layer's output from the held experts and the shared
    ones, with the router's softmax and first choices for the balance."""
    gates, probs, top1 = route(p, x, hf, n_routed, quant)
    y = held_part(p, x, gates, 0, quant)
    if hf.get("n_shared_experts"):
        y = y + mlp(p["shared"], x, quant)
    return y, probs, top1


def block(p: dict, x, hf: dict, n_routed: int, dense: bool, quant=None):
    """A layer; for an expert layer also the router's mean softmax and
    first-choice share over the tokens, each (E,)."""
    eps = hf["rms_norm_eps"]
    x = x + attention(p["mixer"], rms_norm(p["pre_norm"]["scale"], x, eps), hf, quant)
    h = rms_norm(p["pre_ffn_norm"]["scale"], x, eps)
    if dense:
        return x + mlp(p["ffn"], h, quant), None
    y, probs, top1 = moe(p["ffn"], h, hf, n_routed, quant)
    return x + y, (jnp.mean(probs, axis=(0, 1)), jnp.mean(top1, axis=(0, 1)))


def forward(params: dict, tokens, conf: dict, quant=None, remat: bool = False):
    """Float32 logits (B, S, V) of a causal forward pass over ``tokens``,
    and per expert layer (mean softmax, first-choice share)."""
    hf = conf["hf"]
    if hf["model_type"] != "deepseek_v3" or hf["tie_word_embeddings"]:
        raise ValueError(f"the deepseek_v3 reference cannot run {hf['model_type']!r} (tied head: "
                         f"{hf['tie_word_embeddings']})")
    n_dense, n_routed = hf["first_k_dense_replace"], routed_experts(conf)
    x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(jnp.float32)
    fn = jax.checkpoint(block, static_argnums=(2, 3, 4, 5)) if remat else block
    balance = []
    for i, p in enumerate(layer_params(params, hf["num_hidden_layers"], n_dense)):
        x, b = fn(p, x, _Frozen(hf), n_routed, i < n_dense, quant)
        if b is not None:
            balance.append(b)
    x = rms_norm(params["final_norm"]["scale"], x, hf["rms_norm_eps"])
    return mm("bsd,dv->bsv", x, params["head"]["w"], quant), balance


def logits(params: dict, tokens, conf: dict, quant=None, remat: bool = False):
    return forward(params, tokens, conf, quant, remat)[0]


def batch_stats(params: dict, tokens, conf: dict, quant=None):
    """Each expert layer's first-choice share over ``tokens`` (layers, E):
    the load-balance term's f, a statistic of the whole batch."""
    return jnp.stack([f for _, f in forward(params, tokens, conf, quant)[1]])


def lm_loss(params: dict, inputs, labels, conf: dict, quant=None, stats=None):
    """Mean next-token cross-entropy, the configuration's z-loss and its
    load-balance term; ``stats`` is the batch's :func:`batch_stats` where
    ``inputs`` are some of its rows (by default these rows' own)."""
    lb = conf["train"].get("load_balance", {})
    if lb.get("form") not in LOAD_BALANCE_FORMS:
        raise ValueError(f"train.load_balance.form {lb.get('form')!r} is not one of {LOAD_BALANCE_FORMS}")
    lg, balance = forward(params, inputs, conf, quant, remat=True)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(logz - gold) + conf["train"]["z_loss_weight"] * jnp.mean(logz ** 2)
    if not balance:
        return loss
    share = jnp.stack([f for _, f in balance]) if stats is None else stats
    mean_p = jnp.stack([pr for pr, _ in balance])
    aux = routed_experts(conf) * jnp.sum(jax.lax.stop_gradient(share) * mean_p)
    return loss + lb["weight"] * aux
