"""Serving launcher: batched prefill + decode over the facet-layout KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lm import init_lm
from repro.train.steps import make_decode_step, make_prefill_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 = temperature sampling")
    ap.add_argument("--top-k", type=int, default=0, help="top-k filter (0=off)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    max_seq = args.prompt_len + args.gen
    prefill = jax.jit(make_prefill_step(cfg, max_seq=max_seq))
    decode = jax.jit(make_decode_step(cfg))

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, size=(args.batch, args.prompt_len)), jnp.int32)
    ctx = None
    if cfg.family in ("vlm", "encdec"):
        ctx = jnp.asarray(
            rng.normal(size=(args.batch, cfg.n_context_tokens, cfg.d_model)) * 0.02,
            jnp.bfloat16)

    def pick(logits, key):
        lv = logits[:, : cfg.vocab].astype(jnp.float32)
        if args.temperature <= 0:
            return jnp.argmax(lv, -1).astype(jnp.int32)
        lv = lv / args.temperature
        if args.top_k > 0:
            kth = jnp.sort(lv, axis=-1)[:, -args.top_k][:, None]
            lv = jnp.where(lv < kth, -jnp.inf, lv)
        return jax.random.categorical(key, lv, axis=-1).astype(jnp.int32)

    key = jax.random.PRNGKey(args.seed + 1)
    t0 = time.time()
    if ctx is not None:
        logits, caches = prefill(params, prompts, ctx)
    else:
        logits, caches = prefill(params, prompts)
    jax.block_until_ready(logits)
    t1 = time.time()

    key, sub = jax.random.split(key)
    tok = pick(logits, sub)
    out_tokens = [tok]
    for i in range(args.gen - 1):
        logits, caches = decode(params, caches, tok, jnp.int32(args.prompt_len + i))
        key, sub = jax.random.split(key)
        tok = pick(logits, sub)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t2 = time.time()

    gen = np.stack([np.asarray(t) for t in out_tokens], 1)
    print(f"prefill: {args.batch}x{args.prompt_len} tokens in {t1-t0:.2f}s")
    print(f"decode: {args.batch}x{args.gen} tokens in {t2-t1:.2f}s "
          f"({args.batch*args.gen/(t2-t1):.1f} tok/s)")
    print("sample generations (token ids):")
    for row in gen[:2]:
        print(" ", row[:16].tolist())


if __name__ == "__main__":
    main()
