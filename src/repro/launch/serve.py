"""Serving driver: batched prefill + decode with KV/SSM cache.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --reduced \
        --batch 2 --prompt-len 16 --gen 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.models.api import init_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.is_encoder_decoder:
        raise SystemExit("whisper decode at 32k+ is out of architectural "
                         "spec (DESIGN.md §4); use prefill for audio")
    lm = build(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = lm.init(key)

    B, P = args.batch, args.prompt_len
    S = P + args.gen
    prompt = jax.random.randint(jax.random.fold_in(key, 1), (B, P), 0,
                                cfg.vocab_size)
    cache = init_cache(cfg, B, S)

    decode = jax.jit(lambda p, t, c, i: lm.decode_step(p, t, c, i))

    # prefill via sequential decode (cache-consistency is the point here;
    # the production prefill path is lm.prefill + cache download)
    t0 = time.time()
    toks = prompt
    out_tokens = []
    logits = None
    for t in range(P):
        logits, cache = decode(params, toks[:, t:t + 1], cache, jnp.int32(t))
    print(f"prefill({P} tok) {time.time() - t0:.2f}s")

    rng = jax.random.fold_in(key, 7)
    cur = jnp.argmax(logits[:, -1], -1)[:, None]
    t0 = time.time()
    for g in range(args.gen):
        out_tokens.append(np.asarray(cur))
        logits, cache = decode(params, cur, cache, jnp.int32(P + g))
        if args.temperature > 0:
            rng = jax.random.fold_in(rng, g)
            cur = jax.random.categorical(
                rng, logits[:, -1] / args.temperature)[:, None]
        else:
            cur = jnp.argmax(logits[:, -1], -1)[:, None]
    dt = time.time() - t0
    gen = np.concatenate(out_tokens, axis=1)
    print(f"decode {args.gen} tok x {B} seq in {dt:.2f}s "
          f"({args.gen * B / max(dt, 1e-9):.1f} tok/s)")
    for b in range(B):
        print(f"  seq{b}: {gen[b].tolist()}")


if __name__ == "__main__":
    main()
