"""End-to-end federation of the sequence families (ISSUE-7).

Three layers, per new family (mamba2 / rwkv6 / zamba2 / moe):

* ``client_update`` cached == recompute — the prefix-once contract holds
  for stateful-scan runners exactly as for the image families (the
  unstable families re-buffer per subproblem rather than advancing;
  tests/test_adapters.py pins the re-buffering itself);
* ``RoundEngine(prefix_cache="on") == "off"`` through the full fedepth
  round loop driven by ``fl.seq.build_lm_context``;
* the models actually LEARN through the federation: reduced mamba2 and
  MoE beat chance by a wide margin on the synthetic noisy-successor LM
  task (mean of the last three evals — the PR-1 flakiness recipe), the
  MoE run with the ``qsgd_int8`` lossy uplink codec active.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import blockwise
from repro.core.decomposition import Decomposition
from repro.fl.engine import RoundEngine, SimConfig
from repro.fl.registry import get_strategy
from repro.fl.seq import build_lm_context, build_seq_data
from repro.models import build

FAMILIES = {
    "mamba2": "mamba2-370m",
    "rwkv6": "rwkv6-7b",
    "zamba2": "zamba2-1.2b",
    "moe": "qwen3-moe-235b-a22b",
}


def _setup(arch, key, n_batches=2):
    cfg = get_reduced_config(arch)
    lm = build(cfg)
    params = lm.init(key)

    def mk(k):
        toks = jax.random.randint(jax.random.fold_in(key, k), (2, 12), 0,
                                  cfg.vocab_size)
        return {"tokens": toks, "labels": toks}
    runner = blockwise.lm_runner(lm, kernel_force="ref")
    return cfg, runner, params, [mk(i) for i in range(n_batches)]


def _max_diff(a, b):
    return max(float(jnp.abs(jnp.asarray(x, jnp.float32)
                             - jnp.asarray(y, jnp.float32)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ------------------------------------------------ cached == recompute
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_cached_equals_recompute_sequential(family):
    _, runner, params, batches = _setup(FAMILIES[family],
                                        jax.random.PRNGKey(2))
    n = runner.n_units
    dec = Decomposition(tuple((i, i + 1) for i in range(n)), 0, 0)
    kw = dict(lr=0.05, momentum=0.9, local_steps=2)
    p_rec = blockwise.client_update(runner, params, dec, batches,
                                    prefix_cache=False, **kw)
    p_cac = blockwise.client_update(runner, params, dec, batches,
                                    prefix_cache=True, **kw)
    assert _max_diff(p_rec, p_cac) <= 1e-6, family


# ------------------------------------------------ engine equivalence
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_engine_cached_equals_off(family):
    """fedepth through RoundEngine over the LM context: the prefix-cache
    knob must not change the aggregated params (float tolerance)."""
    cfg = get_reduced_config(FAMILIES[family])
    data = build_seq_data(4, n_per_client=16, n_test=32,
                          vocab_size=min(32, cfg.vocab_size), seq_len=12,
                          seed=0)
    sim = SimConfig(rounds=2, participation=0.5, lr=0.05, local_steps=2,
                    batch_size=8, scenario="fair", seed=0)

    def run(pc):
        ctx = build_lm_context(data, sim, cfg, kernel_force="ref")
        engine = RoundEngine(get_strategy("fedepth"), ctx, prefix_cache=pc)
        state, _ = engine.run(eval_every=10)   # no mid-run eval: params only
        return state

    assert _max_diff(run("on"), run("off")) <= 2e-5, family


# ------------------------------------------------ engine/strategy matrix
def test_seq_families_across_engines_and_strategies():
    """The LM context drives the whole execution surface, not just the
    sequential RoundEngine: depthfl's fixed-depth prefix, the
    event-driven AsyncEngine, the vectorized scheduler, and m-fedepth
    all run a sequence family end to end and produce an eval."""
    from repro.fl.systime.engine import AsyncEngine

    cfg = get_reduced_config("mamba2-370m")
    data = build_seq_data(4, n_per_client=16, n_test=32, vocab_size=32,
                          seq_len=12, seed=0)
    sim = SimConfig(rounds=2, participation=0.5, lr=0.1, local_steps=1,
                    batch_size=8, scenario="fair", seed=0)

    def ctx():
        return build_lm_context(data, sim, cfg, kernel_force="ref")

    runs = [
        RoundEngine(get_strategy("depthfl"), ctx()),
        RoundEngine(get_strategy("m-fedepth"), ctx()),
        RoundEngine(get_strategy("fedepth"), ctx(), scheduler="vectorized"),
        AsyncEngine(get_strategy("fedepth"), ctx()),
    ]
    for engine in runs:
        _, history = engine.run(eval_every=2)
        accs = [r.accuracy for r in history if r.accuracy is not None]
        assert accs and all(0.0 <= a <= 1.0 for a in accs), engine


# ------------------------------------------------------- learning
def _learn(arch, **engine_kw):
    cfg = get_reduced_config(arch)
    data = build_seq_data(8, n_per_client=64, n_test=128, vocab_size=32,
                          seq_len=16, seed=0)
    sim = SimConfig(rounds=10, participation=0.5, lr=0.3, local_steps=2,
                    batch_size=32, scenario="fair", seed=0)
    ctx = build_lm_context(data, sim, cfg, kernel_force="ref")
    engine = RoundEngine(get_strategy("fedepth"), ctx, **engine_kw)
    _, history = engine.run(eval_every=2)
    accs = [r.accuracy for r in history if r.accuracy is not None]
    assert len(accs) >= 3, history
    return float(np.mean(accs[-3:]))


def test_mamba2_learns_through_fedepth():
    """Reduced mamba2 federated depth-wise beats chance (1/32 ~ 0.031)
    decisively; the bigram task's Bayes accuracy is ~0.9."""
    acc = _learn("mamba2-370m")
    assert acc > 0.5, acc


def test_moe_learns_through_fedepth_with_qsgd_codec():
    """MoE federated with the lossy int8 uplink codec (error feedback
    on): quantization must not break learning."""
    acc = _learn("qwen3-moe-235b-a22b", codec="qsgd_int8")
    assert acc > 0.5, acc


# ------------------------------------------------ eval at full vocabulary
def _materialized_logits_fn(cfg):
    """The eval's logits as they were before vocab streaming: the full
    (B, T, V) array."""
    from repro.models import common as mcommon
    lm = build(cfg)

    @jax.jit
    def logits(p, toks):
        h, _ = lm.forward_hidden(p, toks, kernel_force="ref")
        h = mcommon.rms_norm(h, p["final_norm"], cfg.norm_eps)
        w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
        return h @ w

    return logits


def _materialized_lm_accuracy(logits, params, x, y, batch):
    correct = total = 0
    for i in range(0, len(x), batch):
        yb = jnp.asarray(y[i:i + batch])
        pred = jnp.argmax(logits(params, jnp.asarray(x[i:i + batch])), -1)
        correct += int(((pred == yb) & (yb >= 0)).sum())
        total += int((yb >= 0).sum())
    return correct / max(total, 1)


@pytest.mark.parametrize("tile_elems", [1 << 26, 24 * 128])
def test_argmax_over_vocab_matches_argmax(tile_elems, monkeypatch):
    """Streaming the argmax over vocab tiles (one tile, and several with
    a ragged last tile) picks exactly jnp.argmax's index, ties included."""
    from repro.fl.strategies import common
    monkeypatch.setattr(common, "_ARGMAX_TILE_ELEMS", tile_elems)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (3, 8, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 300))
    w = w.at[:, 290].set(w[:, 5])        # exact tie across tiles
    got = common.argmax_over_vocab(x, w)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(x @ w, -1)))


@pytest.mark.parametrize("arch", ["mamba2-370m", "yi-6b"])
def test_lm_accuracy_matches_materialized_logits(arch, monkeypatch):
    """The streamed eval returns the materialized eval's accuracy.  Half
    the labels are set to the model's own argmax so the accuracy being
    compared is far from 0, and a few positions are ignored (-100)."""
    from repro.fl.strategies import common
    monkeypatch.setattr(common, "_ARGMAX_TILE_ELEMS", 8 * 12 * 128)
    common.lm_predict_jit.cache_clear()
    cfg = get_reduced_config(arch)
    params = build(cfg).init(jax.random.PRNGKey(3))
    data = build_seq_data(2, vocab_size=cfg.vocab_size, seq_len=12,
                          n_test=40, seed=1)
    logits = _materialized_logits_fn(cfg)
    pred = np.asarray(jnp.argmax(logits(params, jnp.asarray(data.x_test)),
                                 -1))
    y = np.where(np.arange(data.y_test.size).reshape(data.y_test.shape)
                 % 2 == 0, pred, data.y_test)
    y[:, :3] = -100
    want = _materialized_lm_accuracy(logits, params, data.x_test, y, 16)
    got = common.lm_accuracy(cfg, params, data.x_test, y,
                             kernel_force="ref", batch=16)
    common.lm_predict_jit.cache_clear()
    assert want > 0.4
    assert got == want
