"""Serving driver: batched prefill + decode with KV caches.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --batch 4 --prompt-len 64 --tokens 32

``--kv-window W`` routes every full-attention layer's KV through the
two-level ``TieredKVCache`` (device hot ring of W tokens + paged host
cold tier, DESIGN.md §2a); ``--kv-page`` sets the cold staging page.
The tiered loop runs eagerly (host cold tier), reports the same
throughput lines plus the two-level stats: hot fraction (the paper's
Eq. 7 f), staged H2D bytes per step, and write-through flushes.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --batch 2 --prompt-len 48 --tokens 24 --kv-window 32 --kv-page 16

``--sessions N`` switches to the production serving plane (DESIGN.md
§14): N concurrent sessions under a continuous-batching
``SessionScheduler``, each owning per-layer tiered KV caches.
``--max-batch`` bounds the per-step decode batch; ``--hbm-budget-kb`` /
``--host-budget-kb`` bound the aggregate device/host KV footprint
(over-HBM demotes staging buffers, over-host evicts idle sessions fully
into the store and resumes them bit-identically); ``--shared-prefix``
gives sessions a common prompt prefix so the refcounted page registry
stores each shared cold page once.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --sessions 8 --max-batch 2 --prompt-len 48 --tokens 16 \
        --kv-window 16 --kv-page 8 --shared-prefix 32 \
        --store-root /tmp/kvstore --host-budget-kb 256
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced, make_model
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import (
    make_prefill_step,
    make_serve_step,
    tiered_cache_stats,
    tiered_serve_loop,
)
from repro.nn.module import init_with_axes


def serve_loop(cfg, batch: int, prompt_len: int, tokens: int, seed: int = 0):
    model = make_model(cfg)
    params, _ = init_with_axes(model.init, jax.random.PRNGKey(seed), dtype=jnp.float32)
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (batch, prompt_len)), jnp.int32)
    caches = model.init_caches(batch, prompt_len + tokens + 1, jnp.bfloat16)
    prefill = jax.jit(make_prefill_step(model, cfg))
    step = jax.jit(make_serve_step(model, cfg))

    t0 = time.perf_counter()
    tok, caches = prefill(params, {"inputs": prompts}, caches)
    tok = tok[:, None]
    jax.block_until_ready(tok)
    prefill_s = time.perf_counter() - t0

    out = [tok]
    t0 = time.perf_counter()
    for _ in range(tokens):
        tok, caches = step(params, tok, caches)
        out.append(tok)
    jax.block_until_ready(tok)
    decode_s = time.perf_counter() - t0
    return jnp.concatenate(out, axis=1), prefill_s, decode_s


def tiered_serve(cfg, batch: int, prompt_len: int, tokens: int, window: int,
                 page: int | None, seed: int = 0, store=None):
    """Decode loop routed through the two-level KV cache (eager).

    ``store`` adds the durable third level: completed cold KV pages
    persist through the (possibly distributed) two-level store.
    """
    cfg = dataclasses.replace(cfg, scan_layers=False)  # host cold tier can't ride a scan carry
    if cfg.attn_logit_softcap > 0:
        raise SystemExit("--kv-window: tiered KV does not support logit-softcap archs")
    model = make_model(cfg)
    params, _ = init_with_axes(model.init, jax.random.PRNGKey(seed), dtype=jnp.float32)
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (batch, prompt_len)), jnp.int32)
    gen, prefill_s, decode_s, caches = tiered_serve_loop(
        model, cfg, params, prompts, tokens, window=window, page=page, store=store
    )
    return gen, prefill_s, decode_s, tiered_cache_stats(caches)


def session_serve(cfg, n_sessions: int, max_batch: int, prompt_len: int,
                  tokens: int, window: int, page: int | None, seed: int = 0,
                  store=None, shared_prefix: int = 0,
                  hbm_bytes: int | None = None, host_bytes: int | None = None):
    """Continuous batching over ``n_sessions`` tiered sessions (eager)."""
    from repro.serving import SessionScheduler

    cfg = dataclasses.replace(cfg, scan_layers=False)
    model = make_model(cfg)
    params, _ = init_with_axes(model.init, jax.random.PRNGKey(seed), dtype=jnp.float32)
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, min(shared_prefix, prompt_len))
    sched = SessionScheduler(
        model, cfg, params, window=window, page=page, max_batch=max_batch,
        store=store, hbm_bytes=hbm_bytes, host_bytes=host_bytes,
    )
    for _ in range(n_sessions):
        tail = rng.integers(0, cfg.vocab, prompt_len - len(shared))
        sched.submit(np.concatenate([shared, tail]).astype(np.int32), tokens)
    report = sched.run()
    sched.close()
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--kv-window", type=int, default=0,
                    help="route full-attention KV through the tiered cache (hot ring size)")
    ap.add_argument("--kv-page", type=int, default=0,
                    help="cold-tier staging page in tokens (default min(window, 512))")
    ap.add_argument("--sessions", type=int, default=0,
                    help="continuous-batching serving plane over N sessions (needs --kv-window)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="with --sessions: per-step decode batch bound")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="with --sessions: common prompt prefix length (page dedup)")
    ap.add_argument("--hbm-budget-kb", type=int, default=0,
                    help="with --sessions: aggregate device KV budget (0 = unbounded)")
    ap.add_argument("--host-budget-kb", type=int, default=0,
                    help="with --sessions: aggregate host KV budget (0 = unbounded; "
                         "overflow evicts idle sessions into --store-root)")
    ap.add_argument("--store-root", default="",
                    help="persist cold KV pages through a two-level store at this root")
    ap.add_argument("--distributed", action="store_true",
                    help="with --store-root: join it as a DistributedStore host shard")
    ap.add_argument("--host-id", type=int, default=1,
                    help="host id for --distributed (unique per process)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    dstore = None
    store = None
    if args.store_root and args.kv_window > 0:
        if args.distributed:
            from repro.core.dstore import DistributedStore

            dstore = DistributedStore(args.host_id, args.store_root)
            store = dstore.store  # the KV pages ride this shard's write path
        else:
            from repro.core.store import TwoLevelStore

            store = TwoLevelStore(args.store_root)
    try:
        if args.sessions > 0:
            if args.kv_window <= 0:
                raise SystemExit("--sessions requires --kv-window")
            rep = session_serve(
                cfg, args.sessions, args.max_batch, args.prompt_len, args.tokens,
                window=args.kv_window, page=args.kv_page or None, store=store,
                shared_prefix=args.shared_prefix,
                hbm_bytes=args.hbm_budget_kb * 1024 or None,
                host_bytes=args.host_budget_kb * 1024 or None,
            )
            print(f"sessions {rep['sessions']} (retired {rep['retired']}) over "
                  f"{rep['steps']} steps, max_batch {args.max_batch}")
            print(f"decode {rep['decoded_tokens']} tokens: {rep['decode_s']:.3f}s "
                  f"({rep['decode_tok_per_s']:,.0f} tok/s aggregate)")
            print(f"ttft p50 {rep['ttft_p50_s']*1e3:.1f}ms  p99 {rep['ttft_p99_s']*1e3:.1f}ms")
            print(f"tier overflow: {rep['demotions']} demotions, "
                  f"{rep['evictions']} evictions, {rep['resumes']} resumes")
            if "dedup_ratio" in rep:
                print(f"shared pages: {rep['pages_logical']} logical / "
                      f"{rep['pages_stored']} stored (dedup {rep['dedup_ratio']:.2f}x)")
            return
        if args.kv_window > 0:
            gen, prefill_s, decode_s, st = tiered_serve(
                cfg, args.batch, args.prompt_len, args.tokens,
                window=args.kv_window, page=args.kv_page or None, store=store,
            )
        else:
            gen, prefill_s, decode_s = serve_loop(cfg, args.batch, args.prompt_len, args.tokens)
            st = None
    finally:
        if dstore is not None:
            dstore.close()
        elif store is not None:
            store.close()
    print(f"prefill {args.batch}x{args.prompt_len}: {prefill_s:.3f}s "
          f"({args.batch*args.prompt_len/prefill_s:,.0f} tok/s)")
    print(f"decode {args.tokens} steps: {decode_s:.3f}s "
          f"({args.batch*args.tokens/decode_s:,.0f} tok/s)")
    if st is not None and st["layers"]:
        steps = max(1, args.tokens)
        print(f"tiered KV ({st['layers']} layers, window {st['window']}, page {st['page']}): "
              f"hot fraction f={st['hot_fraction']:.3f}, "
              f"staged {st['bytes_staged']/steps:,.0f} B/step over {steps} steps "
              f"({st['pages_staged']} pages, each uploaded once), "
              f"{st['d2h_flushes']} batched write-through flushes")
    print(f"generated (row 0): {np.asarray(gen[0]).tolist()[:24]}")


if __name__ == "__main__":
    main()
