"""Operations the model's work needs, from its shapes alone.

Counts are of what the algorithm requires: a multiply-add is two
operations, recomputation (remat) is not counted, and a causal query
attends to itself and the positions before it.
"""

from __future__ import annotations

from bench.model import head_dim


def matmul_params(hf: dict) -> int:
    """Weights that take part in a matmul per token: the projections of
    every layer and the LM head (the embedding gather is no matmul)."""
    d, h, kv, ff = (hf["hidden_size"], hf["num_attention_heads"],
                    hf["num_key_value_heads"], hf["intermediate_size"])
    hd = head_dim(hf)
    gates = 3 if hf["hidden_act"] == "silu" else 2
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + gates * d * ff
    return hf["num_hidden_layers"] * layer + d * hf["vocab_size"]


def attn_fwd_flops(hf: dict, context: int) -> int:
    """Forward attention operations of one query over ``context`` keys
    (scores and the weighted sum), summed over layers."""
    return hf["num_hidden_layers"] * 4 * hf["num_attention_heads"] * head_dim(hf) * context


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    weight, and three times the causal attention (mean context
    ``(seq_len + 1) / 2``)."""
    return 6 * matmul_params(hf) + 3 * attn_fwd_flops(hf, 1) * (seq_len + 1) / 2
