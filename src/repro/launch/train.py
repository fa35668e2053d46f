"""End-to-end training driver (CPU-runnable at reduced scale; same code
path the production mesh lowers).

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
        --steps 50 [--fedepth] [--budget-mb 64]

Modes:
  * standard   — full-model SGD-momentum pretraining steps
  * --fedepth  — the paper's technique: decompose by --budget-mb and train
    blocks sequentially, cycling the block schedule across steps.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced_config
from repro.core import decomposition, memory_model
from repro.data.tokens import TokenPipeline
from repro.launch import steps as step_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.train import checkpoint


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="yi-6b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--fedepth", action="store_true")
    ap.add_argument("--budget-mb", type=float, default=64.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    lm = build(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = lm.init(key)
    print(f"[{cfg.name}] params={sum(x.size for x in jax.tree.leaves(params)) / 1e6:.2f}M")

    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         batch_size=args.batch, seed=args.seed)
    batches = pipe.batches()

    def add_extras(b):
        b = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.is_encoder_decoder:
            b["encoder_embeds"] = jax.random.normal(
                key, (args.batch, cfg.max_source_positions, cfg.d_model)) * 0.1
        if cfg.family == "vlm":
            P = cfg.frontend_embed_tokens
            b["vision_embeds"] = jax.random.normal(
                key, (args.batch, P, cfg.d_model)) * 0.1
            b["mrope_positions"] = jnp.broadcast_to(
                jnp.arange(args.seq, dtype=jnp.int32), (3, args.batch, args.seq))
        return b

    if args.fedepth:
        mem = memory_model.lm_memory(cfg, args.batch, args.seq)
        budget = int(args.budget_mb * 2**20)
        dec = decomposition.decompose(mem, budget)
        print(decomposition.schedule_summary(dec, mem))
        block_steps = []
        opt_states = []
        from repro.core import blockwise
        runner = blockwise.lm_runner(lm)
        for (lo, hi) in dec.blocks:
            fn, _ = step_lib.make_fedepth_block_step(lm, lo, hi, lr=args.lr)
            block_steps.append(jax.jit(fn))
            opt_states.append(None)
        t0 = time.time()
        for s in range(args.steps):
            b = add_extras(next(batches))
            j = s % len(block_steps)
            lo, hi = dec.blocks[j]
            if opt_states[j] is None:
                train = runner.split(params, lo, hi)
                opt_states[j] = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), train)
            params, opt_states[j], m = block_steps[j](params, opt_states[j], b)
            print(f"step {s:4d} block[{lo}:{hi}] loss={float(m['loss']):.4f} "
                  f"({time.time() - t0:.1f}s)")
    else:
        step = jax.jit(step_lib.make_train_step(lm, lr=args.lr))
        opt = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        t0 = time.time()
        for s in range(args.steps):
            b = add_extras(next(batches))
            params, opt, m = step(params, opt, b)
            print(f"step {s:4d} loss={float(m['loss']):.4f} "
                  f"({time.time() - t0:.1f}s)")

    if args.ckpt_dir:
        path = checkpoint.save_round(args.ckpt_dir, args.steps, params,
                                     {"arch": cfg.name})
        print("saved", path)


if __name__ == "__main__":
    main()
