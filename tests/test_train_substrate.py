"""Optimizers, schedules, checkpointing, token pipeline, sharding rules."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.data.tokens import TokenPipeline
from repro.launch import sharding, steps as step_lib
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.train import checkpoint, optim


# ---------------------------------------------------------------- schedules
def test_cosine_schedule_shape():
    s = optim.cosine(1.0, 100, warmup=10)
    assert float(s(jnp.int32(0))) == 0.0
    assert float(s(jnp.int32(10))) == pytest.approx(1.0, abs=1e-5)
    assert float(s(jnp.int32(100))) == pytest.approx(0.0, abs=1e-5)
    mid = float(s(jnp.int32(55)))
    assert 0.4 < mid < 0.6


def test_wsd_schedule_phases():
    s = optim.wsd(2.0, 1000)
    assert float(s(jnp.int32(1))) < 2.0                 # warmup
    assert float(s(jnp.int32(500))) == pytest.approx(2.0)  # stable
    assert float(s(jnp.int32(999))) < 0.2               # decay


def test_sgd_momentum_descends_quadratic():
    opt = optim.sgd(optim.constant(0.02), momentum=0.9)
    x = {"w": jnp.array([5.0, -3.0])}
    st = opt.init(x)
    for i in range(120):
        g = jax.tree.map(lambda v: 2 * v, x)  # grad of ||x||^2
        x, st = opt.update(x, g, st, jnp.int32(i))
    assert float(jnp.abs(x["w"]).max()) < 1e-2


def test_adamw_descends():
    opt = optim.adamw(optim.constant(0.05), weight_decay=0.0)
    x = {"w": jnp.array([5.0, -3.0])}
    st = opt.init(x)
    for i in range(200):
        g = jax.tree.map(lambda v: 2 * v, x)
        x, st = opt.update(x, g, st, jnp.int32(i))
    assert float(jnp.abs(x["w"]).max()) < 0.1


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = optim.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(20.0)
    total = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(clipped)))
    assert float(total) == pytest.approx(1.0, rel=1e-5)


# --------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_nested():
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
            "b": [jnp.zeros((2,)), (jnp.ones((1,)), jnp.full((3,), 7))],
            "c": {"d": jnp.float32(3.5)}}
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "x.npz")
        checkpoint.save(p, tree, {"note": "hi"})
        back, meta = checkpoint.load(p)
    assert meta["note"] == "hi"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_retention():
    tree = {"w": jnp.zeros((2,))}
    with tempfile.TemporaryDirectory() as d:
        for r in range(6):
            checkpoint.save_round(d, r, tree, keep=3)
        kept = sorted(os.listdir(d))
        assert len(kept) == 3
        assert checkpoint.latest(d).endswith("round_000005.npz")


# ------------------------------------------------------------ token pipeline
def test_token_pipeline_determinism_and_shards():
    tp = TokenPipeline(vocab_size=256, seq_len=16, batch_size=4, seed=3)
    a = next(tp.batches(host_id=0))
    b = next(TokenPipeline(vocab_size=256, seq_len=16, batch_size=4,
                           seed=3).batches(host_id=0))
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = next(tp.batches(host_id=1))
    assert not np.array_equal(a["tokens"], c["tokens"])
    # labels are next-token shifted with -100 tail
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    assert np.all(a["labels"][:, -1] == -100)


def test_token_pipeline_learnable_structure():
    """Bigram statistics are far from uniform (the LM has signal)."""
    tp = TokenPipeline(vocab_size=128, seq_len=256, batch_size=16, seed=0)
    toks = next(tp.batches())["tokens"]
    pairs = {}
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            pairs[(int(a), int(b))] = pairs.get((int(a), int(b)), 0) + 1
    # top bigram much more frequent than uniform expectation
    top = max(pairs.values())
    uniform = toks.size / (128 * 128)
    assert top > 20 * uniform


# ---------------------------------------------------------------- sharding
def test_param_specs_cover_tree_and_divide():
    mesh = make_host_mesh()
    for arch in ("yi-6b", "qwen3-moe-235b-a22b", "rwkv6-7b", "zamba2-1.2b",
                 "whisper-small"):
        cfg = get_reduced_config(arch)
        lm = build(cfg)
        shapes = step_lib.abstract_params(lm)
        specs = sharding.param_specs(cfg, shapes, mesh)
        flat_shapes = jax.tree.leaves(shapes)
        flat_specs = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec))
        assert len(flat_shapes) == len(flat_specs)
        for sd, spec in zip(flat_shapes, flat_specs):
            assert len(spec) <= len(sd.shape)
            for dim, ax in zip(sd.shape, tuple(spec)):
                if ax is not None:
                    assert dim % mesh.shape[ax] == 0


def test_train_step_on_host_mesh():
    """jit with shardings on the 1-device host mesh still runs (the same
    code path the production mesh lowers)."""
    mesh = make_host_mesh()
    cfg = get_reduced_config("yi-6b")
    lm = build(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    pspecs = sharding.to_named(
        sharding.param_specs(cfg, step_lib.abstract_params(lm), mesh), mesh)
    step = step_lib.make_train_step(lm, kernel_force="ref")
    opt = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step, in_shardings=(pspecs, None, None),
                         out_shardings=(pspecs, None, None))
        p2, o2, m = jitted(params, opt, {"tokens": toks, "labels": toks})
    assert bool(jnp.isfinite(m["loss"]))
