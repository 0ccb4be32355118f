"""Batched serving driver: prefill a batch of prompts, then decode tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.registry import get
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models.kvcache import init_cache
    from repro.models.model import init_model, make_smoke_batch

    cfg = get(args.arch)
    if args.smoke:
        cfg = cfg.smoke()

    key = jax.random.PRNGKey(0)
    params = init_model(cfg, key)
    batch = make_smoke_batch(cfg, key, batch=args.batch, seq=args.prompt_len)
    batch.pop("labels", None)

    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(2,))

    cache = init_cache(cfg, args.batch, max(cfg.max_cache_len,
                                            args.prompt_len + args.gen))
    t0 = time.time()
    logits, cache = prefill(params, batch, cache)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    toks = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.time()
    for i in range(args.gen):
        toks.append(np.asarray(tok)[:, 0])
        logits, cache = decode(params, tok, cache)
        if args.temperature > 0:
            k2 = jax.random.fold_in(key, i)
            tok = jax.random.categorical(
                k2, logits / args.temperature, axis=-1)[:, None]
        else:
            tok = jnp.argmax(logits, -1)[:, None]
        tok = tok.astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.time() - t0

    out = np.stack(toks, axis=1)
    print(f"prefill {args.prompt_len} tok x{args.batch}: {t_prefill:.3f}s")
    print(f"decode {args.gen} steps: {t_decode:.3f}s "
          f"({args.gen * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    print("generated ids:\n", out)
    return out


if __name__ == "__main__":
    main()
