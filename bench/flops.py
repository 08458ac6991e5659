"""Operations the model's work needs, from its shapes alone.

Counts are of what the algorithm requires: a multiply-add is two
operations, recomputation (remat) is not counted, and a causal query
attends to itself and the positions before it.  The shapes are the
configuration's published keys (``hf``) and, for a chip's share of the
experts, the published expert count beside them (``published``).
"""

from __future__ import annotations

from bench.model import head_dim


def _attention_shapes(hf: dict) -> tuple[int, int, int]:
    """(query and key width, value width, projection weights) per head and
    layer: latent attention (MLA) where ``kv_lora_rank`` is given, with or
    without the query's low-rank path, else grouped-query attention."""
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    if "kv_lora_rank" in hf:
        nope, rope, v, r = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"],
                            hf["kv_lora_rank"])
        qk = nope + rope
        q_rank = hf.get("q_lora_rank")
        q = d * q_rank + q_rank * h * qk if q_rank else d * h * qk
        weights = q + d * (r + rope) + r * h * (nope + v) + h * v * d
        return qk, v, weights
    hd, kv = head_dim(hf), hf["num_key_value_heads"]
    return hd, hd, d * h * hd + 2 * d * kv * hd + h * hd * d


def matmul_params(hf: dict, published: dict | None = None) -> float:
    """Weights that take part in a matmul per token: the projections of
    every layer and the LM head (the embedding gather is no matmul).

    In a mixture of experts the first ``first_k_dense_replace`` layers are
    dense (width ``intermediate_size``); each other layer has the router
    over all published experts, the ``n_shared_experts`` shared experts
    and, of the ``n_routed_experts`` held here, the expected share that
    uniform routing gives a token: ``num_experts_per_tok`` x held /
    published experts.  The share the router really sends is the
    program's to count."""
    d, n = hf["hidden_size"], hf["num_hidden_layers"]
    gates = 3 if hf["hidden_act"] == "silu" else 2
    attn = _attention_shapes(hf)[2]
    dense = attn + gates * d * hf["intermediate_size"]
    if "n_routed_experts" not in hf:
        return n * dense + d * hf["vocab_size"]
    held = hf["n_routed_experts"]
    routed = (published or {}).get("n_routed_experts", held)
    expert = gates * d * hf["moe_intermediate_size"]
    moe = (attn + d * routed + hf.get("n_shared_experts", 0) * expert
           + hf["num_experts_per_tok"] * held * expert / routed)
    k = hf.get("first_k_dense_replace", 0)
    return k * dense + (n - k) * moe + d * hf["vocab_size"]


def attn_fwd_flops(hf: dict, context: int) -> int:
    """Forward attention operations of one query over ``context`` keys
    (scores and the weighted sum), summed over layers."""
    qk, v, _ = _attention_shapes(hf)
    return hf["num_hidden_layers"] * 2 * hf["num_attention_heads"] * (qk + v) * context


def train_flops_per_token(hf: dict, seq_len: int, published: dict | None = None) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    weight, and three times the causal attention (mean context
    ``(seq_len + 1) / 2``)."""
    return 6 * matmul_params(hf, published) + 3 * attn_fwd_flops(hf, 1) * (seq_len + 1) / 2
