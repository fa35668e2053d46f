"""The program's spans on the profiler's clock (docs/observability.md
§Profiler): a FeDepth round under ``jax.profiler`` writes the
``repro.*`` span tree with its attributes, with the telemetry layer off
or on, and tracing changes no result."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.preresnet20 import reduced as rn_reduced
from repro.core.blockwise import host_nbytes
from repro.fl.data import build_federated
from repro.fl.engine import RoundEngine, SimConfig, build_context
from repro.fl.registry import get_strategy

CFG = rn_reduced(num_classes=10, image_size=16)
DATA = build_federated(num_clients=6, alpha=1.0, n_train=6 * 48, n_test=32,
                       image_size=16, seed=0)
LOCAL_STEPS = 2


class FixedSampler:
    """Every round draws the same cohort."""

    def __init__(self, cohort):
        self.cohort = np.asarray(cohort)

    def sample(self, ctx, round_idx):
        return self.cohort


def _engine(obs=None):
    sim = SimConfig(rounds=1, participation=0.5, lr=0.05,
                    local_steps=LOCAL_STEPS, batch_size=16, scenario="lack",
                    seed=0)
    ctx = build_context(DATA, sim, model_cfg=CFG)
    # the clients with the most blocks: their prefix is buffered, then
    # advanced
    order = sorted(range(len(ctx.decomps)),
                   key=lambda k: -len(ctx.decomps[k].blocks))
    return RoundEngine(get_strategy("fedepth"), ctx, obs=obs,
                       sampler=FixedSampler(order[:2]))


def _round(eng, profile_dir=None):
    """Round 0 of ``eng`` from its initial state (compiled beforehand on
    a throwaway engine, so that the trace holds no compile)."""
    eng.strategy.setup(eng.ctx)
    state = eng.strategy.init_state(eng.ctx)
    batch_fn = eng.default_batch_fn()
    if profile_dir is not None:
        jax.profiler.start_trace(profile_dir)
    try:
        state, _, _ = eng.run_round(state, 0, batch_fn)
        jax.block_until_ready(state)
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()
    return state


def _spans(logdir):
    """``repro.*`` host events: (name, start, end, stats, thread)."""
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         {k: v for k, v in ev.stats}, line.name)
                        for ev in line.events
                        if ev.name.startswith("repro.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parents(spans):
    """Each span's innermost enclosing span on its thread, or None."""
    out = []
    for i, (_, s, e, _, th) in enumerate(spans):
        enclosing = [j for j, (_, s2, e2, _, th2) in enumerate(spans)
                     if j != i and th2 == th and s2 <= s and e <= e2
                     and (s2, -e2) < (s, -e)]
        out.append(max(enclosing, key=lambda j: spans[j][1])
                   if enclosing else None)
    return out


@pytest.fixture(scope="module")
def warm():
    _round(_engine())          # compiles every program the round uses


def test_round_writes_the_span_tree(warm, tmp_path):
    eng = _engine()
    _round(eng, str(tmp_path))
    spans = _spans(str(tmp_path))
    parents = _parents(spans)
    name = lambda i: None if i is None else spans[i][0]  # noqa: E731
    by = {}
    for i, sp in enumerate(spans):
        by.setdefault(sp[0], []).append(i)

    rnd, = by["repro.round"]
    assert spans[rnd][3]["round"] == 0
    for kind in ("repro.sample", "repro.client-update", "repro.aggregate"):
        assert {name(parents[i]) for i in by[kind]} == {"repro.round"}
    assert len(by["repro.comm"]) == 2       # downlink, then the uplink
    assert {name(parents[i]) for i in by["repro.batch"]} \
        == {"repro.client-update"}
    assert {name(parents[i]) for i in by["repro.aggregate.finite"]} \
        == {"repro.aggregate"}
    assert {name(parents[i]) for i in by["repro.payload"]} \
        == {"repro.client-update"}

    cohort = [int(k) for k in eng.sampler.cohort]
    assert [spans[i][3]["client"] for i in by["repro.client-update"]] \
        == cohort
    decs = [eng.ctx.decomps[k] for k in cohort]
    blocks = [b for d in decs for b in d.blocks]
    assert [(spans[i][3]["lo"], spans[i][3]["hi"])
            for i in by["repro.block"]] == blocks
    assert {name(parents[i]) for i in by["repro.block"]} \
        == {"repro.client-update"}
    for kind in ("repro.prefix", "repro.block.setup", "repro.block.steps",
                 "repro.block.merge"):
        assert {name(parents[i]) for i in by[kind]} == {"repro.block"}
    # per block, in order: prefix, set-up, steps, merge
    for b in by["repro.block"]:
        kids = [spans[i][0] for i in range(len(spans)) if parents[i] == b]
        assert kids == ["repro.prefix", "repro.block.setup",
                        "repro.block.steps", "repro.block.merge"]

    # host_bytes: each step and each buffering prefix forward copies one
    # host batch; an advance reads the buffers already on the device
    n_batches = len(DATA.client_indices[cohort[0]]) // 16
    batch = DATA.client_batch(cohort[0], 16, np.random.default_rng(0))
    one = host_nbytes(batch)
    assert one == 16 * 16 * 16 * 3 * 4 + 16 * batch["labels"].itemsize
    modes = [spans[i][3]["mode"] for i in by["repro.prefix"]]
    want = [m for d in decs
            for m in ["buffer"] + ["advance"] * (len(d.blocks) - 1)]
    assert modes == want and "advance" in modes
    for i in by["repro.prefix"]:
        st = spans[i][3]
        assert st["host_bytes"] == (n_batches * one
                                    if st["mode"] == "buffer" else 0)
    for i in by["repro.block.steps"]:
        st = spans[i][3]
        assert st["steps"] == LOCAL_STEPS * n_batches
        assert st["host_bytes"] == LOCAL_STEPS * n_batches * one
    # attrs arrive as stats: the event names stay clean
    assert all("#" not in sp[0] for sp in spans)


def test_tracer_spans_reach_the_profiler(warm, tmp_path):
    eng = _engine(obs="on")
    _round(eng, str(tmp_path))
    spans = _spans(str(tmp_path))
    kinds = {}
    for sp in eng.obs.tracer.spans:
        kinds[sp.kind] = kinds.get(sp.kind, 0) + 1
    assert {"round", "client-update", "block", "aggregate"} <= set(kinds)
    for kind, n in kinds.items():
        assert sum(s[0] == "repro." + kind for s in spans) == n


def test_profiler_changes_no_result(warm, tmp_path):
    off = _round(_engine())
    on = _round(_engine(), str(tmp_path))
    assert _spans(str(tmp_path))
    for a, b in zip(jax.tree.leaves(off), jax.tree.leaves(on)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
