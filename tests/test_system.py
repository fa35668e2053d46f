"""End-to-end behaviour tests for the paper's system.

The core claim chain under test:
  1. the memory model prices depth vs width like the paper's Table 1;
  2. memory-adaptive decomposition lets a width-r-budget client train the
     FULL model depth-wise (the paper's B1->...->B7,8,9 schedule);
  3. depth-wise sequential FL (Algorithm 1) produces a global full-size
     model that learns, is aggregation-compatible with FedAvg, and
     tolerates cohorts with no memory-rich client;
  4. the train/serve drivers run end-to-end on reduced configs.
"""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.configs.preresnet20 import CONFIG as RN20, reduced as rn_reduced
from repro.core import aggregation, blockwise
from repro.core.decomposition import decompose, width_equivalent_budget
from repro.core.memory_model import resnet_memory
from repro.fl.data import build_federated
from repro.fl.engine import (BUDGET_SLACK, RoundEngine, SimConfig,
                             build_context)
from repro.fl.registry import get_strategy
from repro.models import build, resnet


def test_paper_training_order_reproduced():
    """Paper §Memory budgets: at the x1/6 budget the schedule is
    {B1 -> B2 -> B3 -> B4 -> B5,6 -> B7,8,9} (6 blocks); x1 trains in one."""
    mem = resnet_memory(RN20, batch=128)
    budget = int(width_equivalent_budget(mem, 1 / 6) * BUDGET_SLACK)
    dec = decompose(mem, budget)
    assert dec.covers_all(len(mem.units))
    assert dec.blocks == ((0, 1), (1, 2), (2, 3), (3, 4), (4, 6), (6, 9))
    full = decompose(mem, width_equivalent_budget(mem, 1.0))
    assert full.num_blocks == 1


def test_paper_claim_chain_small():
    cfg = rn_reduced(num_classes=10, image_size=16)
    mem = resnet_memory(cfg, batch=32)

    # (1) activations dominate
    assert sum(u.activations for u in mem.units) > \
        3 * sum(u.params for u in mem.units)

    # (2) a fraction-of-full budget still covers the full model
    budget = int(mem.full_train_bytes() * 0.6)
    dec = decompose(mem, budget)
    assert dec.covers_all(len(mem.units))
    assert dec.num_blocks >= 2

    # (3) federated depth-wise training learns
    data = build_federated(num_clients=8, alpha=1.0, n_train=1600,
                           n_test=300, image_size=16, seed=0)
    sim = SimConfig(rounds=10, participation=0.5, lr=0.08, local_steps=2,
                    batch_size=64, scenario="fair", seed=0)
    engine = RoundEngine(get_strategy("fedepth"),
                         build_context(data, sim, model_cfg=cfg))
    _, hist = engine.run(eval_every=10)
    assert hist[-1].accuracy > 0.25


def test_client_dropout_robustness():
    """Paper contribution 3: aggregation works with cohorts containing
    ONLY low-budget clients (HeteroFL/SplitMix degrade here)."""
    cfg = rn_reduced(num_classes=4, image_size=16)
    key = jax.random.PRNGKey(0)
    params = resnet.init(key, cfg)
    runner = blockwise.resnet_runner(cfg)
    mem = resnet_memory(cfg, batch=16)
    floor = max(mem.block_train_bytes(i, i + 1)
                for i in range(len(mem.units)))
    dec = decompose(mem, floor)
    assert dec.num_blocks >= 2  # genuinely low-budget schedule
    imgs = jax.random.normal(key, (16, 16, 16, 3))
    lbls = jax.random.randint(key, (16,), 0, 4)
    batch = {"images": imgs, "labels": lbls}
    locals_ = [blockwise.client_update(runner, params, dec, [batch], lr=0.05)
               for _ in range(2)]
    agg = aggregation.fedavg(locals_, [1.0, 1.0])
    l0 = float(blockwise.full_model_loss(runner, params, batch))
    l1 = float(blockwise.full_model_loss(runner, agg, batch))
    assert l1 < l0


def _run_cli(mod, args, timeout=560):
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return subprocess.run([sys.executable, "-m", mod, *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=Path(__file__).resolve().parents[1])


@pytest.mark.parametrize("args", [
    ["--arch", "yi-6b", "--reduced", "--steps", "2", "--batch", "2",
     "--seq", "16"],
    ["--arch", "zamba2-1.2b", "--reduced", "--steps", "2", "--batch", "2",
     "--seq", "16", "--fedepth", "--budget-mb", "16"],
])
def test_train_driver_cli(args):
    out = _run_cli("repro.launch.train", args)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss=" in out.stdout


def test_serve_driver_cli():
    out = _run_cli("repro.launch.serve",
                   ["--arch", "rwkv6-7b", "--reduced", "--batch", "1",
                    "--prompt-len", "4", "--gen", "3"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "tok/s" in out.stdout


def test_fedepth_block_step_memoryless_prefix():
    """The TPU-facing block step keeps optimizer state ONLY for the block."""
    from repro.launch import steps as step_lib
    cfg = get_reduced_config("yi-6b")
    lm = build(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    step, runner = step_lib.make_fedepth_block_step(lm, 0, 1,
                                                    kernel_force="ref")
    train = runner.split(params, 0, 1)
    full_size = sum(x.size for x in jax.tree.leaves(params))
    block_size = sum(x.size for x in jax.tree.leaves(train))
    assert block_size < full_size
    opt = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), train)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    p2, opt2, m = jax.jit(step)(params, opt, {"tokens": toks, "labels": toks})
    assert bool(jnp.isfinite(m["loss"]))
