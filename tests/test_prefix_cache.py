"""PrefixCache: the buffered z_{lo-1} execution contract.

What must hold (see docs/prefix_cache.md):

* the incremental advance equals a from-scratch prefix forward through
  the current params, per runner family — including after the trained
  block's params change (the advance runs through the JUST-TRAINED
  units);
* cached and recompute ``client_update`` produce the same params, on
  the sequential and the batched (vmap) paths, and through the full
  ``RoundEngine`` for fedepth / m-fedepth (depthfl has no frozen prefix
  and must be byte-identical under either knob);
* the bytes the cache holds are EXACTLY what
  ``ModelMemory.buffered_z_bytes`` prices — one accounting between the
  runtime, the budget check, and the systime latency model;
* ``prox_mu > 0`` still anchors at the block-entry params.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.configs.preresnet20 import reduced as rn_reduced
from repro.configs.vit_t16 import reduced as vit_reduced
from repro.core import blockwise
from repro.core.decomposition import Decomposition
from repro.core.memory_model import resnet_memory, vit_memory
from repro.fl.data import build_federated
from repro.fl.engine import RoundEngine, SimConfig, build_context
from repro.fl.registry import get_strategy
from repro.models import build, resnet, vit
from repro.obs import Obs, activate


# ------------------------------------------------------------------ helpers
def _resnet_setup(key, batch=4):
    cfg = rn_reduced(num_classes=4, image_size=16)
    params = resnet.init(key, cfg)

    def mk(k):
        return {"images": jax.random.normal(jax.random.fold_in(key, k),
                                            (batch, 16, 16, 3)),
                "labels": jax.random.randint(jax.random.fold_in(key, 10 + k),
                                             (batch,), 0, 4)}
    return cfg, blockwise.resnet_runner(cfg), params, [mk(0), mk(1)]


def _vit_setup(key, batch=4):
    cfg = vit_reduced(num_classes=4)
    params = vit.init(key, cfg)

    def mk(k):
        return {"images": jax.random.normal(jax.random.fold_in(key, k),
                                            (batch, 16, 16, 3)),
                "labels": jax.random.randint(jax.random.fold_in(key, 10 + k),
                                             (batch,), 0, 4)}
    return cfg, blockwise.vit_runner(cfg), params, [mk(0), mk(1)]


def _lm_setup(key):
    cfg = get_reduced_config("yi-6b")
    lm = build(cfg)
    params = lm.init(key)

    def mk(k):
        toks = jax.random.randint(jax.random.fold_in(key, k), (2, 12), 0,
                                  cfg.vocab_size)
        return {"tokens": toks, "labels": toks}
    return cfg, blockwise.lm_runner(lm, kernel_force="ref"), params, [mk(0)]


SETUPS = {"resnet": _resnet_setup, "vit": _vit_setup, "lm": _lm_setup}


def _max_diff(a, b):
    return max(float(jnp.abs(jnp.asarray(x, jnp.float32)
                             - jnp.asarray(y, jnp.float32)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _per_unit_dec(n):
    return Decomposition(tuple((i, i + 1) for i in range(n)), 0, 0)


# ------------------------------------------------- incremental advance
@pytest.mark.parametrize("family", sorted(SETUPS))
def test_incremental_advance_equals_from_scratch(family):
    """After the trained block's params change, advancing the buffer
    through the new params must equal a from-scratch prefix forward —
    the cache never serves stale activations."""
    _, runner, params, batches = SETUPS[family](jax.random.PRNGKey(0))
    n = runner.n_units
    lo0, lo1 = (1, 2) if n >= 2 else (0, 1)
    cache = blockwise.PrefixCache(runner)
    cache.prepare(params, batches, lo0)
    # emulate training block [lo0, lo1): perturb exactly those units
    train = runner.split(params, lo0, lo1)
    new_params = runner.merge(
        params, jax.tree.map(lambda x: x + 0.01, train), lo=lo0, hi=lo1)
    zs_adv = cache.prepare(new_params, batches, lo1)
    fwd = blockwise.make_prefix_forward(runner, lo1)
    for z, b in zip(zs_adv, batches):
        scratch = fwd(new_params, b)
        assert _max_diff(z, scratch) <= 1e-5, family


def test_advance_is_incremental_not_replay():
    """The stable-runner advance must NOT recompute from scratch: it
    only sees units [prev_lo, lo), so corrupting the [0, prev_lo) prefix
    after buffering is invisible to it (replaying would pick it up)."""
    _, runner, params, batches = _resnet_setup(jax.random.PRNGKey(1))
    cache = blockwise.PrefixCache(runner)
    cache.prepare(params, batches, 1)
    corrupted = dict(params)
    corrupted["blocks"] = ([jax.tree.map(lambda x: x * 100.0,
                                         params["blocks"][0])]
                           + list(params["blocks"][1:]))
    zs = cache.prepare(corrupted, batches, 2)
    fwd = blockwise.make_prefix_forward(runner, 2)
    clean = [fwd(params, b) for b in batches]
    for z, c in zip(zs, clean):
        assert _max_diff(z, c) == 0.0


# ------------------------------------------------ cached == recompute
@pytest.mark.parametrize("family", sorted(SETUPS))
def test_cached_equals_recompute_sequential(family):
    _, runner, params, batches = SETUPS[family](jax.random.PRNGKey(2))
    dec = _per_unit_dec(runner.n_units)
    kw = dict(lr=0.05, momentum=0.9, local_steps=2)
    p_rec = blockwise.client_update(runner, params, dec, batches,
                                    prefix_cache=False, **kw)
    p_cac = blockwise.client_update(runner, params, dec, batches,
                                    prefix_cache=True, **kw)
    assert _max_diff(p_rec, p_cac) <= 1e-6, family


@pytest.mark.parametrize("local_steps", [2, 20])
def test_cached_equals_recompute_batched(local_steps):
    """The stacked (vmap) path, on both the fully-unrolled (2 steps) and
    the scan (20 x 2 batches > MAX_UNROLL_STEPS) regimes — the scan is
    where XLA CSE cannot buffer the prefix and the cache must."""
    _, runner, params, batches = _resnet_setup(jax.random.PRNGKey(3))
    dec = _per_unit_dec(runner.n_units)
    kw = dict(lr=0.02, momentum=0.9, local_steps=local_steps)
    groups = [batches, batches[::-1]]
    o_rec = blockwise.client_update_batched(runner, params, dec, groups,
                                            prefix_cache=False, **kw)
    o_cac = blockwise.client_update_batched(runner, params, dec, groups,
                                            prefix_cache=True, **kw)
    for a, b in zip(o_rec, o_cac):
        assert _max_diff(a, b) <= 1e-5


@pytest.mark.parametrize("method", ["fedepth", "m-fedepth", "depthfl"])
def test_engine_cached_equals_off(method):
    """RoundEngine(prefix_cache="on"|"off") aggregate to the same params
    (float tolerance); depthfl trains prefixes end-to-end — no frozen
    prefix — so the knob must be a strict no-op for it."""
    data = build_federated(num_clients=6, alpha=1.0, n_train=240,
                           n_test=80, image_size=16, seed=0)
    cfg = rn_reduced(num_classes=10, image_size=16)

    def run(pc):
        sim = SimConfig(rounds=2, participation=0.5, lr=0.05,
                        local_steps=2, batch_size=32, scenario="fair",
                        seed=0)
        engine = RoundEngine(get_strategy(method),
                             build_context(data, sim, model_cfg=cfg),
                             prefix_cache=pc)
        state, _ = engine.run(eval_every=2)
        return state

    d = _max_diff(run("on"), run("off"))
    if method == "depthfl":
        assert d == 0.0
    else:
        assert d <= 2e-5, method


def test_engine_prefix_cache_knob():
    ctx_args = dict(sim=SimConfig(), num_clients=2, sizes=np.ones(2),
                    rng=np.random.default_rng(0), key=None)
    from repro.fl.strategy import Context
    eng = RoundEngine(get_strategy("fedavg"), Context(**ctx_args))
    assert eng.ctx.prefix_cache is True
    eng = RoundEngine(get_strategy("fedavg"), Context(**ctx_args),
                      prefix_cache="off")
    assert eng.ctx.prefix_cache is False
    with pytest.raises(ValueError, match="prefix_cache"):
        RoundEngine(get_strategy("fedavg"), Context(**ctx_args),
                    prefix_cache="sometimes")


# ----------------------------------------------------- memory accounting
@pytest.mark.parametrize("family", ["resnet", "vit"])
def test_buffered_bytes_match_memory_model(family):
    """The cache's held bytes == ``ModelMemory.buffered_z_bytes`` at the
    runtime batch size — the single accounting the budget check and the
    systime pricing rely on (fp32 families: act_bytes matches dtype)."""
    cfg, runner, params, batches = SETUPS[family](jax.random.PRNGKey(4))
    B = batches[0]["images"].shape[0]
    mem = resnet_memory(cfg, B) if family == "resnet" else vit_memory(cfg, B)
    cache = blockwise.PrefixCache(runner)
    for lo in range(runner.n_units):
        cache.zs = None            # force a fresh buffer at each depth
        cache.prepare(params, batches, lo)
        assert cache.buffered_bytes() == mem.buffered_z_bytes(
            lo, n_batches=len(batches)), (family, lo)
    # and the budget check prices the extra buffers on top of the one
    # already inside the block's activation accounting
    extra = mem.block_train_bytes(1, 2, n_batches=3) \
        - mem.block_train_bytes(1, 2)
    assert extra == 2 * mem.buffered_z_bytes(1)


def test_end_to_end_cache_holds_last_blocks_prefix():
    cfg, runner, params, batches = _resnet_setup(jax.random.PRNGKey(5))
    dec = _per_unit_dec(runner.n_units)
    cache = blockwise.PrefixCache(runner)
    blockwise.client_update(runner, params, dec, batches, lr=0.05,
                            prefix_cache=cache)
    B = batches[0]["images"].shape[0]
    mem = resnet_memory(cfg, B)
    last_lo = dec.blocks[-1][0]
    assert cache.buffered_bytes() == mem.buffered_z_bytes(
        last_lo, n_batches=len(batches))


# ------------------------------------------------------------- FedProx
def test_prox_anchors_correctly_with_cache():
    """prox_mu > 0 must (a) still regularize toward the block-entry
    params and (b) match the recompute path exactly — the anchor is the
    same block-entry snapshot on both."""
    _, runner, params, batches = _resnet_setup(jax.random.PRNGKey(6))
    dec = Decomposition(((0, 3),), 0, 0)
    kw = dict(lr=0.05, local_steps=3)
    p_free = blockwise.client_update(runner, params, dec, batches,
                                     prox_mu=0.0, prefix_cache=True, **kw)
    p_prox = blockwise.client_update(runner, params, dec, batches,
                                     prox_mu=10.0, prefix_cache=True, **kw)
    p_prox_rec = blockwise.client_update(runner, params, dec, batches,
                                         prox_mu=10.0, prefix_cache=False,
                                         **kw)

    def dist(a, b):
        return sum(float(jnp.sum((x - y) ** 2)) for x, y in zip(
            jax.tree.leaves(a), jax.tree.leaves(b)))

    assert dist(p_prox, params) < dist(p_free, params)
    assert _max_diff(p_prox, p_prox_rec) <= 1e-6


# ---------------------------------------------------------- block carry
def _leaf_ptrs(tree):
    return [leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(tree)]


@pytest.mark.parametrize("family", sorted(SETUPS))
def test_block_carry_is_private(family, monkeypatch):
    """The step donates the (train, vel) carry, so ``block_carry`` must
    hand back fresh buffers: no output leaf may share a buffer with
    ``params`` (or with another output), ``train`` equals the split
    subtree, ``vel`` is zeros of its shapes and dtypes, and ``params``
    stays readable after a whole ``client_update`` whose steps donate
    their carry here too (XLA:CPU honors donation when asked)."""
    _, runner, params, batches = SETUPS[family](jax.random.PRNGKey(7))
    lo, hi = (1, 2) if runner.n_units >= 2 else (0, 1)
    split = runner.split(params, lo, hi)
    train, vel = blockwise.block_carry(split)

    out_ptrs = _leaf_ptrs((train, vel))
    assert len(set(out_ptrs)) == len(out_ptrs)
    assert not set(out_ptrs) & set(_leaf_ptrs(params))
    assert not set(out_ptrs) & set(_leaf_ptrs(split))
    assert jax.tree.structure(train) == jax.tree.structure(split)
    assert jax.tree.structure(vel) == jax.tree.structure(split)
    for s, t, v in zip(jax.tree.leaves(split), jax.tree.leaves(train),
                       jax.tree.leaves(vel)):
        assert t.shape == s.shape and t.dtype == s.dtype
        assert v.shape == s.shape and v.dtype == s.dtype
        np.testing.assert_array_equal(np.asarray(t), np.asarray(s))
        assert not np.asarray(v).any()

    monkeypatch.setattr(blockwise, "donate", lambda *argnums: argnums)
    blockwise.client_update(runner, params, _per_unit_dec(runner.n_units),
                            batches, lr=0.05, step_cache={})
    for leaf in jax.tree.leaves(params):
        assert not leaf.is_deleted()
        np.asarray(leaf)


def _per_leaf_setup_update(runner, params, dec, batches, *, buffered, lr,
                           momentum, local_steps, prox_mu):
    """The block set-up as it was before ``block_carry``: split, one
    eager copy and one ``zeros_like`` per trained leaf, then the same
    step calls and merge as ``client_update``."""
    cache = blockwise.PrefixCache(runner) if buffered else None
    for j, (lo, hi) in enumerate(dec.blocks):
        zs = cache.prepare(params, batches, lo) if buffered else None
        train = runner.split(params, lo, hi)
        anchor = jax.tree.map(jnp.asarray, train)
        train = jax.tree.map(jnp.copy, train)
        vel = jax.tree.map(jnp.zeros_like, train)
        make = (blockwise.make_buffered_block_step if buffered
                else blockwise.make_block_step)
        step = make(runner, lo, hi, j, lr=lr, momentum=momentum,
                    prox_mu=prox_mu)
        for _ in range(local_steps):
            for i, batch in enumerate(batches):
                extra = (zs[i],) if buffered else ()
                train, vel = step(params, train, vel, anchor, *extra, batch)
        params = runner.merge(params, train, lo=lo, hi=hi)
    return params


@pytest.mark.parametrize("prox_mu", [0.0, 10.0])
@pytest.mark.parametrize("buffered", [True, False])
def test_block_carry_keeps_the_numbers(buffered, prox_mu):
    """One carry program per block changes no number: ``client_update``
    is bitwise equal to the per-leaf set-up loop it replaced."""
    _, runner, params, batches = _resnet_setup(jax.random.PRNGKey(8))
    dec = Decomposition(((0, 1), (1, 3)), 0, 0)
    kw = dict(lr=0.05, momentum=0.9, local_steps=2, prox_mu=prox_mu)
    got = blockwise.client_update(runner, params, dec, batches,
                                  prefix_cache=buffered, **kw)
    want = _per_leaf_setup_update(runner, params, dec, batches,
                                  buffered=buffered, **kw)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_block_carry_counter_once_per_block():
    """With a capture active, ``block_carry`` counts one engagement per
    block: five for a 5-block decomposition of one client."""
    cfg = dataclasses.replace(rn_reduced(num_classes=4, image_size=16),
                              stage_blocks=(2, 2, 2))
    key = jax.random.PRNGKey(9)
    params = resnet.init(key, cfg)
    runner = blockwise.resnet_runner(cfg)
    batches = [{"images": jax.random.normal(key, (4, 16, 16, 3)),
                "labels": jnp.arange(4) % 4}]
    dec = Decomposition(tuple((i, i + 1) for i in range(1, 6)), 1, 0)
    obs = Obs()
    with activate(obs):
        blockwise.client_update(runner, params, dec, batches, lr=0.05)
    assert obs.metrics.value("block_carry") == 5
