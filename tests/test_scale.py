"""Population-scale subsystem (fl/scale/): sharded execution
equivalence, on-mesh masked aggregation, spill stores, lazy population
traces, streaming history sinks.

The sharded==vectorized bitwise claims need a MULTI-device CPU mesh,
which XLA only grants at backend init (see ``launch.mesh``) — those
assertions run in a fresh subprocess via the ``multi_device_env``
fixture; everything else runs in-process on the default single device
(where ``make_data_mesh`` gives the 1-device mesh the psum-bitwise
contract is stated for).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs.preresnet20 import reduced as rn_reduced
from repro.core import aggregation, blockwise
from repro.fl.data import build_federated
from repro.fl.engine import RoundEngine, SimConfig, build_context
from repro.fl.registry import get_strategy
from repro.fl.sampling import VectorizedScheduler, make_scheduler
from repro.fl.scale import (HashedDutyCycle, InMemoryStore, JsonlHistorySink,
                            Population, PopulationSampler, PrefixedStore,
                            ShardedScheduler, SpillStore, mesh_aggregate_masked,
                            psum_masked_partials)
from repro.fl.scale.population import population_context, population_system
from repro.fl.scale.state_store import dumps, loads
from repro.fl.comm.error_feedback import ErrorFeedback
from repro.fl.comm.payload import CommChannel
from repro.fl.strategy import ClientResult


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ==========================================================================
# sharded scheduler: single-device equivalence + fallbacks (in-process)
# ==========================================================================
def _tiny_run(method, scheduler, *, scenario="fair", codec="none", rounds=1):
    data = build_federated(num_clients=6, alpha=1.0, n_train=180, n_test=60,
                           image_size=16, seed=0)
    cfg = rn_reduced(num_classes=10, image_size=16)
    sim = SimConfig(rounds=rounds, participation=0.5, lr=0.05, local_steps=1,
                    batch_size=32, scenario=scenario, seed=0)
    engine = RoundEngine(get_strategy(method),
                         build_context(data, sim, model_cfg=cfg),
                         scheduler=scheduler, codec=codec)
    return engine.run(eval_every=rounds)


@pytest.mark.parametrize("method,scenario", [("fedavg", "fair"),
                                             ("fedepth", "lack")])
def test_sharded_equals_vectorized_single_device(method, scenario):
    sv, hv = _tiny_run(method, VectorizedScheduler(min_group=1),
                       scenario=scenario)
    ss, hs = _tiny_run(method, ShardedScheduler(min_group=1),
                       scenario=scenario)
    assert _trees_equal(sv, ss)
    assert [r.comm_bytes for r in hv] == [r.comm_bytes for r in hs]


def test_sharded_fused_mesh_bitwise_on_one_device_mesh():
    # the ISSUE contract: psum of (masked-sum, count) partials ==
    # aggregate_masked BITWISE on a 1-device mesh (psum is identity,
    # fold order identical)
    sv, _ = _tiny_run("fedepth", VectorizedScheduler(min_group=1),
                      scenario="lack")
    ss, hs = _tiny_run("fedepth",
                       ShardedScheduler(min_group=1, aggregate="mesh"),
                       scenario="lack")
    assert _trees_equal(sv, ss)
    assert all(r.comm_bytes > 0 for r in hs)


def test_run_fused_ineligible_returns_notimplemented_without_side_effects():
    # probed BEFORE batches are drawn: the shared rng stream must not
    # advance on a fall-through
    data = build_federated(num_clients=6, alpha=1.0, n_train=180, n_test=60,
                           image_size=16, seed=0)
    cfg = rn_reduced(num_classes=10, image_size=16)
    sim = SimConfig(rounds=1, participation=0.5, seed=0)
    ctx = build_context(data, sim, model_cfg=cfg)
    strat = get_strategy("fedavg")      # unmasked -> ineligible
    strat.setup(ctx)
    state = strat.init_state(ctx)
    sched = ShardedScheduler(aggregate="mesh")
    before = ctx.rng.bit_generator.state
    out = sched.run_fused(ctx, strat, state, [0, 1, 2],
                          lambda k: pytest.fail("batch_fn must not run"))
    assert out is NotImplemented
    assert ctx.rng.bit_generator.state == before


def test_sharded_delegates_plain_strategies_to_fallback():
    calls = []

    class Plain:
        def client_update(self, ctx, state, client_id, batches):
            calls.append(client_id)
            return ClientResult(np.zeros(1), 1.0, comm_bytes=0)

    from repro.fl.strategy import Context
    ctx = Context(sim=SimConfig(participation=0.5), num_clients=8,
                  sizes=np.ones(8), rng=np.random.default_rng(0), key=None)
    out = ShardedScheduler().run(ctx, Plain(), None, [3, 1, 2],
                                 lambda k: [{"x": np.zeros((4, 2),
                                                           np.float32)}])
    assert calls == [3, 1, 2]
    assert len(out) == 3


def test_make_scheduler_resolves_sharded_lazily():
    sched = make_scheduler("sharded")
    assert isinstance(sched, ShardedScheduler)
    # resolution is cached: second lookup hits the class, same behavior
    assert isinstance(make_scheduler("sharded"), ShardedScheduler)
    engine_sched = RoundEngine(
        get_strategy("fedavg"),
        build_context(build_federated(num_clients=4, alpha=1.0, n_train=80,
                                      n_test=40, image_size=16, seed=0),
                      SimConfig()), scheduler="sharded").scheduler
    assert isinstance(engine_sched, ShardedScheduler)


def test_chunk_widths_invariants():
    for G in range(1, 40):
        for D in (1, 2, 4, 8):
            widths = ShardedScheduler._chunk_widths(G, D)
            assert sum(widths) == G
            assert len(widths) <= D
            if G > 1:
                assert all(w >= 2 for w in widths)


def test_chunk_widths_max_lanes():
    # max_lanes bounds widths (the peak-memory knob), may exceed n_dev
    # chunks (round-robin), and never violates the >= 2 floor.
    for G in (2, 5, 17, 40, 100):
        for D in (1, 2, 4):
            for ml in (2, 3, 8, 64):
                widths = ShardedScheduler._chunk_widths(G, D, ml)
                assert sum(widths) == G
                assert all(w >= 2 for w in widths)
                # widths exceed max_lanes only when the >= 2 floor wins
                assert all(w <= max(ml, 3) for w in widths)
    # None keeps the legacy one-chunk-per-device split
    assert (ShardedScheduler._chunk_widths(10, 4, None)
            == ShardedScheduler._chunk_widths(10, 4))
    # sharded results are unchanged by max_lanes (same jitted callable,
    # narrower stacks): rerun the tiny fedavg round with max_lanes=2
    sv, _ = _tiny_run("fedavg", VectorizedScheduler(min_group=1))
    ss, _ = _tiny_run("fedavg", ShardedScheduler(min_group=1, max_lanes=2))
    assert _trees_equal(sv, ss)


# ==========================================================================
# psum masked aggregation vs aggregate_masked (1-device mesh, in-process)
# ==========================================================================
def _random_tree(rng, scale=1.0):
    return {"a": rng.normal(size=(3, 4)).astype(np.float32) * scale,
            "b": {"w": rng.normal(size=(5,)).astype(np.float32) * scale}}


@pytest.mark.parametrize("seed", range(5))
def test_psum_partials_match_aggregate_masked(seed):
    rng = np.random.default_rng(seed)
    G = int(rng.integers(1, 6))
    glob = _random_tree(rng)
    locals_ = [_random_tree(rng) for _ in range(G)]
    # per-leaf {0,1} masks, shared across the group (the fedepth
    # contract: one decomposition -> one mask), incl. the all-zero leaf
    # case (nobody trained -> global passes through)
    mask = jax.tree.map(
        lambda x: np.float32(rng.integers(0, 2)) * np.ones_like(x), glob)
    w = rng.integers(1, 200, size=G).astype(np.float32)

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_data_mesh
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *locals_)
    mesh = make_data_mesh()               # the in-process 1-device mesh
    partial = jax.jit(jax.shard_map(
        lambda ls, ww, m: psum_masked_partials(ls, m, ww),
        mesh=mesh, in_specs=(P("data"), P("data"), P()),
        out_specs=P()))(stacked, jnp.asarray(w), mask)
    got = mesh_aggregate_masked(glob, [partial])

    want = aggregation.aggregate_masked(glob, locals_, list(w),
                                        [mask] * G)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_psum_partials_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def inner(seed):
        test_psum_partials_match_aggregate_masked(seed)

    inner()


# ==========================================================================
# multi-device mesh: the subprocess bitwise assertions (satellite d)
# ==========================================================================
_MESH_SCRIPT = textwrap.dedent("""
    import os
    from repro.launch.mesh import force_host_device_count
    force_host_device_count(4)
    import jax, numpy as np
    assert len(jax.devices()) == 4, jax.devices()
    from repro.configs.preresnet20 import reduced as rn_reduced
    from repro.fl.data import build_federated
    from repro.fl.engine import RoundEngine, SimConfig, build_context
    from repro.fl.registry import get_strategy
    from repro.fl.sampling import VectorizedScheduler
    from repro.fl.scale.executor import ShardedScheduler

    data = build_federated(num_clients=8, alpha=1.0, n_train=320, n_test=80,
                           image_size=16, seed=0)

    def run(method, scheduler, scenario, codec="none"):
        cfg = rn_reduced(num_classes=10, image_size=16)
        sim = SimConfig(rounds=2, participation=0.75, lr=0.05, local_steps=2,
                        batch_size=32, scenario=scenario, seed=0)
        eng = RoundEngine(get_strategy(method),
                          build_context(data, sim, model_cfg=cfg),
                          scheduler=scheduler, codec=codec)
        return eng.run(eval_every=2)

    # codec off AND on: channel math is host-side on the default path,
    # so the sharded fan-out stays bitwise either way
    for method, scen, codec in [("fedavg", "fair", "none"),
                                ("fedepth", "lack", "none"),
                                ("fedepth", "lack", "topk")]:
        sv, hv = run(method, VectorizedScheduler(min_group=1), scen, codec)
        ss, hs = run(method, ShardedScheduler(min_group=1), scen, codec)
        for a, b in zip(jax.tree.leaves(sv), jax.tree.leaves(ss)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                (method, scen, codec)
        assert [r.comm_bytes for r in hv] == [r.comm_bytes for r in hs]

    # fused on-mesh aggregation: tolerance across devices (psum
    # reassociates partial sums), bitwise is the 1-device contract
    sv, _ = run("fedepth", VectorizedScheduler(min_group=1), "lack")
    ss, _ = run("fedepth", ShardedScheduler(min_group=1, aggregate="mesh"),
                "lack")
    for a, b in zip(jax.tree.leaves(sv), jax.tree.leaves(ss)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    print("MESH-EQUIV-OK")
""")


def test_sharded_bitwise_on_forced_multi_device_mesh(multi_device_env):
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, timeout=560,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         env=multi_device_env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MESH-EQUIV-OK" in out.stdout


def test_force_host_device_count_sets_flag_before_init(multi_device_env):
    script = textwrap.dedent("""
        import os
        from repro.launch.mesh import force_host_device_count
        force_host_device_count(3)
        assert "--xla_force_host_platform_device_count=3" \\
            in os.environ["XLA_FLAGS"]
        import jax
        assert len(jax.devices()) == 3
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh()
        assert mesh.shape == {"data": 3}
        # calling again with the SAME n after init is a no-op...
        force_host_device_count(3)
        # ...but a different n after init must fail loudly, not silently
        try:
            force_host_device_count(8)
        except RuntimeError:
            print("FORCE-OK")
        else:
            raise SystemExit("expected RuntimeError after backend init")
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=240,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         env=multi_device_env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FORCE-OK" in out.stdout


# ==========================================================================
# SpillStore: round-trip, LRU bound, codec (satellite d)
# ==========================================================================
def test_spillstore_round_trip_and_lru_bound(tmp_path):
    with SpillStore(capacity=4, dir=str(tmp_path / "spill")) as store:
        values = {}
        rng = np.random.default_rng(0)
        for k in range(20):
            # the shapes EF actually stores: (tag, residual-pytree)
            values[k] = (("tag", k % 3),
                         {"w": rng.normal(size=(3, 2)).astype(np.float32),
                          "lst": [1, 2.5, None, "s"]})
            store[k] = values[k]
            assert store.resident() <= 4
        assert len(store) == 20
        assert store.spill_count >= 16
        for k in range(20):                       # reload everything
            got = store.get(k)
            assert got[0] == values[k][0]
            np.testing.assert_array_equal(got[1]["w"], values[k][1]["w"])
            assert got[1]["lst"] == values[k][1]["lst"]
            assert store.resident() <= 4
        assert store.load_count > 0
        # pop removes from disk too
        store.pop(0)
        assert 0 not in store and len(store) == 19
        store.clear()
        assert len(store) == 0


def test_spillstore_lru_bound_property(tmp_path):
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.tuples(st.sampled_from(["set", "get", "pop"]),
                              st.integers(0, 12)), max_size=60))
    def inner(ops):
        store = SpillStore(capacity=3, dir=str(tmp_path / "prop"))
        shadow = {}
        for op, k in ops:
            if op == "set":
                store[k] = {"v": np.full((2,), k, np.float32)}
                shadow[k] = k
            elif op == "get":
                got = store.get(k)
                if k in shadow:
                    np.testing.assert_array_equal(
                        got["v"], np.full((2,), shadow[k], np.float32))
                else:
                    assert got is None
            else:
                store.pop(k)
                shadow.pop(k, None)
            assert store.resident() <= 3
            assert len(store) == len(shadow)
        store.clear()

    inner()


def test_codec_round_trips_tuple_vs_list_structure():
    # tuple-vs-list is pytree STRUCTURE: trees_congruent must still
    # match after spill/load (the EF same-coordinates check depends on
    # it)
    from repro.fl.comm.codecs import trees_congruent
    tree = {"a": (np.ones((2, 2), np.float32), [np.zeros(3, np.int32)]),
            "b": None, "c": 7}
    got = loads(dumps(tree))
    assert trees_congruent(tree, got)
    assert isinstance(got["a"], tuple) and isinstance(got["a"][1], list)
    # pickle escape hatch: dataclass payloads survive
    res = ClientResult(np.ones(2, np.float32), 2.0, comm_bytes=8)
    got = loads(dumps(res))
    assert isinstance(got, ClientResult) and got.weight == 2.0


def test_prefixed_store_namespaces_do_not_collide():
    base = InMemoryStore()
    a, b = PrefixedStore(base, "ef"), PrefixedStore(base, "downlink")
    a[1] = "ra"
    b[1] = "rb"
    assert a.get(1) == "ra" and b.get(1) == "rb"
    assert len(base) == 2
    a.clear()
    assert a.get(1) is None and b.get(1) == "rb"


# ==========================================================================
# error feedback through a bounded store (satellite c)
# ==========================================================================
def test_error_feedback_residual_survives_spill_cycle(tmp_path):
    with SpillStore(capacity=1, dir=str(tmp_path / "ef")) as store:
        ef = ErrorFeedback(store=store)
        t0 = {"w": np.ones((2,), np.float32)}
        ef.update(0, t0, jax.tree.map(lambda x: 0.5 * x, t0), tag="a")
        ef.update(1, t0, jax.tree.map(lambda x: 0.25 * x, t0), tag="b")
        assert store.resident() == 1          # client 0 spilled to disk
        # reload across the spill boundary: residual AND tag intact
        corrected = ef.correct(0, t0, tag="a")
        np.testing.assert_allclose(corrected["w"], 1.5 * np.ones(2))
        # tag mismatch after a spill cycle still resets, never misapplies
        assert ef.correct(1, t0, tag="CHANGED")["w"] is t0["w"]
        assert ef.residual(1) is None
        ef.reset()
        assert len(store) == 0


def test_channel_state_store_routes_ef_and_downlink(tmp_path):
    with SpillStore(capacity=8, dir=str(tmp_path / "chan")) as store:
        chan = CommChannel("topk", downlink="delta", state_store=store)
        assert isinstance(chan.ef._residuals, PrefixedStore)
        assert chan.ef._residuals.store is store
        assert chan._last_sent.store is store
        # eviction/reset: residuals can be dropped wholesale
        chan.ef.update(3, {"w": np.ones(2, np.float32)},
                       {"w": np.zeros(2, np.float32)}, tag=None)
        assert len(store) == 1
        chan.ef.reset()
        assert len(store) == 0


# ==========================================================================
# lazy population traces (satellite d: determinism per seed)
# ==========================================================================
def test_population_determinism_is_positional_not_sequential():
    a = Population(num_clients=1_000_000, scenario="fair", seed=7)
    b = Population(num_clients=1_000_000, scenario="fair", seed=7)
    ids = np.asarray([0, 999_999, 123_456, 42])
    # query in different orders / batch shapes: same per-client trace
    np.testing.assert_array_equal(a.ratio(ids), b.ratio(ids[::-1])[::-1])
    np.testing.assert_array_equal(a.size(ids),
                                  np.concatenate([b.size(ids[:2]),
                                                  b.size(ids[2:])]))
    np.testing.assert_array_equal(a.labels(123_456), b.labels(123_456))
    np.testing.assert_array_equal(a.phase(ids), b.phase(ids))
    assert a.profile(999_999) is b.profile(999_999)
    # a different seed draws a different trace
    c = Population(num_clients=1_000_000, scenario="fair", seed=8)
    assert not np.array_equal(a.size(np.arange(64)), c.size(np.arange(64)))


def test_population_draws_follow_paper_protocol():
    pop = Population(num_clients=50_000, scenario="lack", seed=0)
    ids = np.arange(2000)
    from repro.fl.engine import SCENARIOS
    assert set(np.unique(pop.ratio(ids))) <= set(SCENARIOS["lack"])
    sizes = pop.size(ids)
    assert sizes.min() >= pop.size_range[0]
    assert sizes.max() <= pop.size_range[1]
    labs = pop.labels(17)
    assert len(set(labs.tolist())) == pop.labels_per_client
    up = pop.up(ids, t=0.0)
    assert 0.6 < up.mean() < 0.9                  # duty=0.75


def test_population_context_is_lazy_and_engine_compatible():
    pop = Population(num_clients=1_000_000, scenario="fair", seed=1)
    sim = SimConfig(rounds=1, participation=0.000004, lr=0.05,
                    local_steps=1, batch_size=16, seed=0)
    cfg = rn_reduced(num_classes=10, image_size=16)
    ctx = build_context(None, sim, population=pop, model_cfg=cfg)
    assert ctx.num_clients == 1_000_000
    assert len(ctx.sizes) == 1_000_000
    # decomps memoized per budget: <= 4 distinct objects for the scenario
    decs = {id(ctx.decomps[k]) for k in
            np.random.default_rng(0).integers(0, 1_000_000, size=50)}
    assert len(decs) <= 4
    # a full (tiny-cohort) round runs end to end on the lazy context
    engine = RoundEngine(get_strategy("fedepth"), ctx, scheduler="sharded",
                         sampler=PopulationSampler(availability=pop))
    state, hist = engine.run(eval_every=1)
    assert len(hist) == 1 and hist[0].accuracy is not None


def test_population_sampler_is_o_cohort_and_availability_aware():
    pop = Population(num_clients=1_000_000, seed=0, avail_duty=0.5)
    sim = SimConfig(participation=0.00001, seed=3)
    cfg = rn_reduced(num_classes=10, image_size=16)
    ctx = build_context(None, sim, population=pop, model_cfg=cfg)
    cohort = PopulationSampler(availability=pop).sample(ctx, round_idx=2)
    assert len(cohort) == 10 == len(set(cohort.tolist()))
    t = 2 * 60.0
    assert pop.up(cohort, t).all()                # all sampled clients up


def test_hashed_duty_cycle_matches_protocol():
    av = HashedDutyCycle(period_s=100.0, duty=0.3, seed=5)
    ids = np.arange(10_000)
    up = av.up(ids, 12.0)
    assert 0.25 < up.mean() < 0.35
    # deterministic + time-varying
    np.testing.assert_array_equal(up, HashedDutyCycle(100.0, 0.3,
                                                      seed=5).up(ids, 12.0))
    assert not np.array_equal(up, av.up(ids, 50.0))


def test_population_system_satisfies_async_engine_contract():
    pop = Population(num_clients=12_345, seed=0)
    system = population_system(pop)
    assert len(system.profiles) == 12_345
    assert system.profiles[77] is pop.profile(77)


# ==========================================================================
# streaming history sinks (satellite b)
# ==========================================================================
def test_round_engine_streams_records_to_sink(tmp_path):
    path = tmp_path / "hist.jsonl"
    data = build_federated(num_clients=4, alpha=1.0, n_train=80, n_test=40,
                           image_size=16, seed=0)
    sim = SimConfig(rounds=3, participation=0.5, local_steps=1,
                    batch_size=16, seed=0)
    cfg = rn_reduced(num_classes=10, image_size=16)
    with JsonlHistorySink(str(path)) as sink:
        engine = RoundEngine(get_strategy("fedavg"),
                             build_context(data, sim, model_cfg=cfg),
                             history_sink=sink)
        state, hist = engine.run(eval_every=1)
        assert hist == []                        # streamed, not retained
        assert sink.records == 3
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["round"] for r in rows] == [1, 2, 3]
    assert all(r["kind"] == "round" for r in rows)
    assert all(r["comm_bytes"] > 0 for r in rows)


def test_async_engine_streams_records_and_trace(tmp_path):
    from repro.fl.systime.engine import AsyncEngine
    path = tmp_path / "async.jsonl"
    pop = Population(num_clients=10_000, scenario="fair", seed=1)
    sim = SimConfig(rounds=2, participation=0.0008, lr=0.05, local_steps=1,
                    batch_size=16, seed=0)
    cfg = rn_reduced(num_classes=10, image_size=16)
    ctx = build_context(None, sim, population=pop, model_cfg=cfg)
    store = InMemoryStore()
    with JsonlHistorySink(str(path)) as sink:
        engine = AsyncEngine(get_strategy("fedepth"), ctx,
                             system=population_system(pop),
                             mode="async", concurrency=4,
                             history_sink=sink, state_store=store)
        state, hist = engine.run(eval_every=1)
        assert hist == [] and engine.trace == []     # both streamed
        assert sink.records >= 1 and sink.traces >= 1
    kinds = {json.loads(line)["kind"]
             for line in path.read_text().splitlines()}
    assert kinds == {"round", "trace"}
    # in-flight snapshots were parked in the store under ("inflight", ...)
    # keys; whatever is left belongs to updates still in flight at exit
    assert all(k[0] == "inflight" for k in store.keys())


def test_sink_default_behavior_unchanged_without_sink():
    data = build_federated(num_clients=4, alpha=1.0, n_train=80, n_test=40,
                           image_size=16, seed=0)
    sim = SimConfig(rounds=2, participation=0.5, local_steps=1,
                    batch_size=16, seed=0)
    cfg = rn_reduced(num_classes=10, image_size=16)
    engine = RoundEngine(get_strategy("fedavg"),
                         build_context(data, sim, model_cfg=cfg))
    _, hist = engine.run(eval_every=1)
    assert [r.round for r in hist] == [1, 2]      # the list API, as ever
