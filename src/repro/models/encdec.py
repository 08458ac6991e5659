"""Encoder-decoder transformer (whisper-large-v3 backbone).

The audio conv frontend is a STUB per the assignment: the batch's
``frames`` carry precomputed frame embeddings (B, n_frames, d_model) — the
mel-spectrogram conv stack's output.  Faithful whisper details kept:
LayerNorm, GELU MLP, biases, learned decoder positions, sinusoidal
encoder positions, MHA (n_kv == n_heads), tied decoder embedding/head,
no RoPE.

Decode uses a self-KV cache plus per-layer cross-KV computed once from
the encoder output at prefill.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.nn import layers as L
from repro.nn.module import Scope, stacked_init

Params = Any


def _sinusoids(length: int, channels: int) -> jnp.ndarray:
    """Whisper's sinusoidal position embedding."""
    log_timescale = jnp.log(10_000.0) / (channels // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(channels // 2, dtype=jnp.float32))
    scaled = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)


class EncDec:
    def __init__(self, cfg: ArchConfig):
        if cfg.encdec is None:
            raise ValueError("EncDec requires cfg.encdec")
        self.cfg = cfg

    # ------------------------------------------------------------------ init

    def _enc_layer_init(self, s: Scope) -> None:
        cfg = self.cfg
        L.norm_init(s, "pre_norm", cfg.d_model, cfg)
        L.attention_init(s, "attn", cfg)
        L.norm_init(s, "pre_ffn_norm", cfg.d_model, cfg)
        L.mlp_init(s, "ffn", cfg)

    def _dec_layer_init(self, s: Scope) -> None:
        cfg = self.cfg
        L.norm_init(s, "pre_self_norm", cfg.d_model, cfg)
        L.attention_init(s, "self_attn", cfg)
        L.norm_init(s, "pre_cross_norm", cfg.d_model, cfg)
        L.attention_init(s, "cross_attn", cfg)
        L.norm_init(s, "pre_ffn_norm", cfg.d_model, cfg)
        L.mlp_init(s, "ffn", cfg)

    def init(self, scope: Scope) -> None:
        cfg = self.cfg
        enc = scope.child("encoder")
        stacked_init(enc, "periods", cfg.encdec.n_encoder_layers, self._enc_layer_init)
        L.norm_init(enc, "final_norm", cfg.d_model, cfg)

        dec = scope.child("decoder")
        L.embedding_init(dec, "embed", cfg.vocab, cfg.d_model)
        dec.child("pos").param(
            "table", (cfg.max_seq_len, cfg.d_model), ("seq", "embed"), init="normal", scale=0.01
        )
        stacked_init(dec, "periods", cfg.n_layers, self._dec_layer_init)
        L.norm_init(dec, "final_norm", cfg.d_model, cfg)

    # --------------------------------------------------------------- encoder

    def encode(self, params: Params, frames: jax.Array) -> jax.Array:
        """frames: (B, n_frames, d_model) precomputed conv-frontend output."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x = frames.astype(dt) + _sinusoids(frames.shape[1], cfg.d_model).astype(dt)[None]

        # Encoder is bidirectional: attend with an all-visible mask by
        # treating the sequence as cross-attention onto itself.
        def body_bidir(x, p):
            h = L.norm_apply(p["pre_norm"], x, cfg)
            dtl = h.dtype
            k = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"].astype(dtl))
            v = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"].astype(dtl))
            if "bk" in p["attn"]:
                k = k + p["attn"]["bk"].astype(dtl)
                v = v + p["attn"]["bv"].astype(dtl)
            a, _ = L.attention_apply(p["attn"], h, cfg, mode="train", use_rope=False, cross_kv=(k, v))
            x = x + a
            h2 = L.norm_apply(p["pre_ffn_norm"], x, cfg)
            return x + L.mlp_apply(p["ffn"], h2, cfg), 0

        if cfg.remat != "none":
            body_bidir = jax.checkpoint(body_bidir)
        x, _ = jax.lax.scan(body_bidir, x, params["encoder"]["periods"])
        return L.norm_apply(params["encoder"]["final_norm"], x, cfg)

    # ------------------------------------------------------------- cross kv

    def cross_kv(self, params: Params, enc_out: jax.Array) -> dict:
        """Per-decoder-layer (k, v) of the encoder memory, stacked."""
        cfg = self.cfg
        dt = enc_out.dtype

        def one(p):
            k = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross_attn"]["wk"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross_attn"]["wv"].astype(dt))
            if "bk" in p["cross_attn"]:
                k = k + p["cross_attn"]["bk"].astype(dt)
                v = v + p["cross_attn"]["bv"].astype(dt)
            return {"k": k, "v": v}

        return jax.vmap(one, in_axes=0)(params["decoder"]["periods"])

    # --------------------------------------------------------------- decoder

    def _dec_body(self, cfg, mode):
        def body(carry, xs):
            x, offset = carry
            p, cache, ckv = xs
            h = L.norm_apply(p["pre_self_norm"], x, cfg)
            sa, new_cache = L.attention_apply(
                p["self_attn"], h, cfg, cache=cache, mode=mode, use_rope=False
            )
            x = x + sa
            h2 = L.norm_apply(p["pre_cross_norm"], x, cfg)
            ca, _ = L.attention_apply(
                p["cross_attn"], h2, cfg, mode="train", use_rope=False, cross_kv=(ckv["k"], ckv["v"])
            )
            x = x + ca
            h3 = L.norm_apply(p["pre_ffn_norm"], x, cfg)
            x = x + L.mlp_apply(p["ffn"], h3, cfg)
            return (x, offset), (new_cache if cache is not None else 0)

        return body

    def _decode_stack(self, params, x, caches, cross, mode):
        cfg = self.cfg
        body = self._dec_body(cfg, mode)
        if cfg.remat != "none" and mode == "train":
            body = jax.checkpoint(body)
        (x, _), new_caches = jax.lax.scan(
            body, (x, 0), (params["decoder"]["periods"], caches, cross)
        )
        x = L.norm_apply(params["decoder"]["final_norm"], x, cfg)
        return x, new_caches

    def _embed_dec(self, params: Params, tokens: jax.Array, start: jax.Array | int) -> jax.Array:
        cfg = self.cfg
        x = L.embedding_apply(params["decoder"]["embed"], tokens, cfg)
        pos = jax.lax.dynamic_slice_in_dim(
            params["decoder"]["pos"]["table"], start, tokens.shape[1], axis=0
        )
        return x + pos.astype(x.dtype)[None]

    # ----------------------------------------------------------- public api

    def train_logits(self, params: Params, frames: jax.Array, tokens: jax.Array) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        enc_out = self.encode(params, frames)
        cross = self.cross_kv(params, enc_out)
        x = self._embed_dec(params, tokens, 0)
        body = self._dec_body(cfg, "train")
        if cfg.remat != "none":
            body = jax.checkpoint(body)
        (x, _), _ = jax.lax.scan(body, (x, 0), (params["decoder"]["periods"], None, cross))
        x = L.norm_apply(params["decoder"]["final_norm"], x, cfg)
        logits = L.logits_apply(params["decoder"]["embed"], None, x, cfg)
        return logits, jnp.zeros((), jnp.float32)

    def init_caches(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg

        def one(_):
            return L.make_cache(cfg, batch, max_seq, dtype)

        return {"self": jax.vmap(one)(jnp.arange(cfg.n_layers)), "cross": None}

    def prefill(self, params: Params, frames: jax.Array, tokens: jax.Array, caches: dict) -> tuple[jax.Array, dict]:
        enc_out = self.encode(params, frames)
        cross = self.cross_kv(params, enc_out)
        x = self._embed_dec(params, tokens, 0)
        x, new_self = self._decode_stack(params, x, caches["self"], cross, "prefill")
        logits = L.logits_apply(params["decoder"]["embed"], None, x[:, -1:, :], self.cfg)
        return logits, {"self": new_self, "cross": cross}

    def decode_step(self, params: Params, token: jax.Array, caches: dict) -> tuple[jax.Array, dict]:
        index = caches["self"]["index"][0]  # all layers share the position
        x = self._embed_dec(params, token, index)
        x, new_self = self._decode_stack(params, x, caches["self"], caches["cross"], "decode")
        logits = L.logits_apply(params["decoder"]["embed"], None, x, self.cfg)
        return logits, {"self": new_self, "cross": caches["cross"]}
