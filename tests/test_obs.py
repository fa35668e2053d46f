"""Telemetry layer (docs/observability.md): off-is-bitwise
contract, typed-event projection of the legacy trace, stable schema,
metrics registry semantics, exporters, the trace report, and the
JsonlHistorySink non-finite-JSON fix."""
import dataclasses
import json
import pathlib
import sys

import numpy as np
import pytest

from repro.configs.preresnet20 import reduced as rn_reduced
from repro.fl.data import build_federated
from repro.fl.engine import RoundEngine, SimConfig, build_context
from repro.fl.registry import get_strategy
from repro.fl.scale.history import JsonlHistorySink, sanitize
from repro.fl.scale.state_store import SpillStore
from repro.fl.systime import (ZERO_LATENCY, AsyncEngine, DeviceProfile,
                              SystemModel, mixed_profiles)
from repro.obs import (LEGACY_FIELDS, SYS_EVENT_KINDS, Obs, SysEvent,
                       Tracer, activate, active, make_obs, scope, span_if)

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "tools"))
import trace_report  # noqa: E402

import jax  # noqa: E402


def _data(n=8, seed=0):
    return build_federated(num_clients=n, alpha=1.0, n_train=40 * n,
                           n_test=160, image_size=16, seed=seed)


def _sim(**kw):
    base = dict(rounds=2, participation=0.5, lr=0.05, local_steps=1,
                batch_size=32, scenario="fair", seed=0)
    base.update(kw)
    return SimConfig(**base)


CFG = rn_reduced(num_classes=10, image_size=16)
DATA = _data()
MIX = {"iot": 0.25, "phone": 0.5, "workstation": 0.25}


def _ctx():
    return build_context(DATA, _sim(), model_cfg=CFG)


def _strip(history):
    """History minus the wall-clock ``seconds`` field (varies between
    any two runs regardless of telemetry)."""
    return [(r.round, r.accuracy, r.comm_bytes, r.sim_seconds,
             r.down_bytes) for r in history]


def _same_params(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------- schema
def test_sys_event_field_order():
    """The documented legacy field order IS the dataclass's leading
    field order, and the docs state it."""
    names = tuple(f.name for f in dataclasses.fields(SysEvent))[:5]
    assert names == LEGACY_FIELDS == ("kind", "t", "client", "version",
                                      "extra")
    doc = (pathlib.Path(__file__).resolve().parent.parent
           / "docs" / "system_model.md").read_text()
    assert "(kind, t, client, version, extra)" in doc
    for kind in SYS_EVENT_KINDS:        # incl. dispatch_forced and miss
        assert f"`{kind}`" in doc


def test_sys_event_legacy_projection_is_exact_tuple():
    ev = SysEvent("finish", 1.5, 3, 7, 0.25, wall_t=99.0,
                  attrs={"tier": "iot"})
    assert ev.legacy() == ("finish", 1.5, 3, 7, 0.25)
    assert type(ev.legacy()) is tuple


def test_tracer_span_nesting_and_clocks():
    t = [0.0]
    tr = Tracer(sim_clock=lambda: t[0])
    with tr.span("round", round=0) as outer:
        t[0] = 2.0
        with tr.span("client-update", client=1) as inner:
            t[0] = 5.0
        tr.event("mark")
    assert inner.parent_id == outer.span_id
    assert outer.sim_seconds == 5.0 and inner.sim_seconds == 3.0
    assert outer.wall_seconds >= inner.wall_seconds >= 0.0
    assert tr.events[0].span_id == outer.span_id


def test_activation_contextvar():
    assert active() is None
    obs = make_obs(True)
    with activate(obs):
        assert active() is obs
        with activate(None):            # explicit deactivation nests
            assert active() is None
        assert active() is obs
    assert active() is None
    assert make_obs(None) is None and make_obs("off") is None
    assert make_obs(obs) is obs
    with pytest.raises(ValueError):
        make_obs("loud")
    # span_if is a no-op without a capture
    with span_if(None, "x") as sp:
        assert sp is None


# --------------------------------------------------------------- metrics
def test_metrics_registry_semantics():
    obs = Obs()
    m = obs.metrics
    c = m.counter("hits", cache="group")
    c.inc()
    c.inc(2)
    assert m.counter("hits", cache="group") is c       # same identity
    assert m.value("hits", cache="group") == 3.0
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(TypeError):
        m.gauge("hits", cache="group")                 # type conflict
    g = m.gauge("bytes")
    g.set(5)
    g.add(2)
    assert m.value("bytes") == 7.0
    h = m.histogram("lat", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 3 and h.cumulative() == [1, 2, 3]
    assert h.mean == pytest.approx(55.5 / 3)
    snap = m.snapshot()
    assert [e["name"] for e in snap] == ["bytes", "hits", "lat"]
    json.dumps(snap)                                   # JSON-able


# ----------------------------------------- off == on, bitwise (tentpole)
@pytest.mark.parametrize("method,codec", [
    ("fedavg", "none"), ("fedavg", "qsgd_int8"),
    ("fedepth", "none"), ("fedepth", "qsgd_int8"),
])
def test_round_engine_obs_off_on_bitwise(method, codec):
    def run(obs):
        eng = RoundEngine(get_strategy(method), _ctx(),
                          scheduler="vectorized", codec=codec, obs=obs)
        state, hist = eng.run(eval_every=2)
        return eng, state, hist

    _, s0, h0 = run(None)
    e1, s1, h1 = run("on")
    assert repr(_strip(h0)) == repr(_strip(h1))
    _same_params(s0, s1)
    assert len(e1.obs.tracer.spans) > 0
    assert len(e1.obs.metrics) > 0


@pytest.mark.parametrize("method,codec", [
    ("fedavg", "none"), ("fedavg", "qsgd_int8"),
    ("fedepth", "none"), ("fedepth", "qsgd_int8"),
])
def test_async_engine_obs_off_on_bitwise(method, codec):
    def run(obs):
        eng = AsyncEngine(get_strategy(method), _ctx(),
                          system=SystemModel(
                              mixed_profiles(8, MIX, seed=0)),
                          mode="async", codec=codec, obs=obs)
        state, hist = eng.run(eval_every=2)
        return eng, state, hist

    e0, s0, h0 = run(None)
    e1, s1, h1 = run("on")
    assert repr(_strip(h0)) == repr(_strip(h1))
    _same_params(s0, s1)
    # the legacy trace is BYTE-identical with telemetry on...
    assert repr(e0.trace) == repr(e1.trace)
    # ...and is exactly the projection of the typed events
    assert [ev.legacy() for ev in e1.obs.tracer.sys_events] == e1.trace
    assert e1.obs.tracer.legacy_trace() == e1.trace


def test_sync_deadline_misses_recorded_with_metrics():
    slow = DeviceProfile("crawler", flops=float("inf"),
                         mem_bw=float("inf"), link_up=1.0,
                         link_down=float("inf"), mem_bytes=float("inf"))
    profiles = [slow if k < 4 else ZERO_LATENCY for k in range(8)]
    sim = _sim(participation=1.0)

    def run(obs):
        ctx = build_context(DATA, sim, model_cfg=CFG)
        eng = AsyncEngine(get_strategy("fedavg"), ctx,
                          system=SystemModel(profiles), mode="sync",
                          deadline_s=1.0, obs=obs)
        eng.run(eval_every=1)
        return eng

    e0, e1 = run(None), run("on")
    assert repr(e0.trace) == repr(e1.trace)
    misses = [t for t in e1.trace if t[0] == "miss"]
    assert misses
    assert e1.obs.metrics.value("deadline_misses",
                                tier="crawler") == len(misses)
    # the interval-opening events carry the phase split for the lanes
    opened = [ev for ev in e1.obs.tracer.sys_events
              if ev.kind in ("finish", "miss")]
    assert opened and all("start" in ev.attrs and "tier" in ev.attrs
                          and "compute" in ev.attrs for ev in opened)


def test_deep_sites_record_metrics():
    """One vectorized fedepth round records the jit-cache, prefix-cache,
    group, and codec metric families."""
    eng = RoundEngine(get_strategy("fedepth"), _ctx(),
                      scheduler="vectorized", codec="qsgd_int8", obs="on")
    eng.run(eval_every=2)
    names = {m["name"] for m in eng.obs.metrics.snapshot()}
    assert {"jit_cache_misses", "group_dispatches", "group_clients",
            "codec_encode_ratio", "codec_encoded_bytes",
            "ef_residual_norm", "engine_up_bytes"} <= names
    kinds = {s.kind for s in eng.obs.tracer.spans}
    assert {"round", "cohort-group", "eval"} <= kinds


def test_spill_store_metrics_only_when_active():
    store = SpillStore(capacity=2)
    store["a"] = 1
    store["b"] = 2
    store["c"] = 3                      # evicts "a"
    assert store.get("a") == 1          # disk load, no capture: no-op
    obs = Obs()
    with activate(obs):
        store["d"] = 4                  # evicts
        assert store.get("b") is not None
    assert obs.metrics.value("state_store_evictions", store="spill") >= 1
    loads = obs.metrics.value("state_store_disk_loads", store="spill",
                              default=0.0)
    hits = obs.metrics.value("state_store_hot_hits", store="spill",
                             default=0.0)
    assert loads + hits >= 1.0
    store.close()


# ------------------------------------------------------------- exporters
@pytest.fixture(scope="module")
def async_capture():
    eng = AsyncEngine(get_strategy("fedavg"), _ctx(),
                      system=SystemModel(mixed_profiles(8, MIX, seed=0)),
                      mode="async", obs="on")
    eng.run(eval_every=2)
    return eng


def test_chrome_trace_structure(async_capture, tmp_path):
    path = tmp_path / "trace.json"
    doc = async_capture.obs.export_chrome_trace(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == doc
    evs = doc["traceEvents"]
    # per-client sim-time lanes with tier-named metadata
    lanes = {e["tid"] for e in evs
             if e["ph"] == "X" and e["pid"] == 1 and e["tid"] > 0}
    assert lanes
    names = [e for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name" and e["pid"] == 1
             and e["tid"] in lanes]
    assert names and all("(" in e["args"]["name"] for e in names)
    # phase slices in wire-time order within an interval
    slices = [e for e in evs if e["ph"] == "X" and e["pid"] == 1
              and e["tid"] > 0]
    assert {e["name"] for e in slices} <= set(trace_report.PHASE_BUCKET)
    assert all(e["args"]["tier"] for e in slices)
    assert any(e["args"].get("interval_start") for e in slices)
    # aggregate instants on the server lane
    assert any(e["ph"] == "i" and e["name"] == "aggregate" for e in evs)
    # wall-clock spans normalized to the capture origin
    walls = [e for e in evs if e.get("pid") == 2 and e["ph"] == "X"]
    assert walls and min(e["ts"] for e in walls) == 0.0


def test_trace_report_per_tier_breakdown(async_capture, tmp_path):
    """Acceptance: the Chrome trace summarizes into non-zero per-tier
    compute vs comm breakdowns."""
    path = tmp_path / "trace.json"
    async_capture.obs.export_chrome_trace(str(path))
    report = trace_report.summarize(trace_report.load_events(str(path)))
    assert set(report["tiers"]) == set(MIX)
    for tier in report["tiers"].values():
        assert tier["total_s"] > 0.0 and tier["intervals"] > 0
        assert 0.0 < tier["compute_frac"] <= 1.0
    o = report["overall"]
    assert o["aggregates"] > 0 and o["sim_makespan_s"] > 0.0
    # the CLI renders and writes the JSON form
    out = tmp_path / "report.json"
    assert trace_report.main([str(path), "--json", str(out)]) == 0
    assert json.loads(out.read_text())["overall"]["intervals"] \
        == o["intervals"]


def test_jsonl_export_composes_with_history_sink(async_capture, tmp_path):
    path = tmp_path / "telemetry.jsonl"
    n = async_capture.obs.export_jsonl(str(path))
    lines = [json.loads(line) for line in
             path.read_text().splitlines()]
    assert len(lines) == n > 0
    kinds = {line["kind"] for line in lines}
    assert {"span", "sys_event", "metric"} <= kinds
    # and through an existing open sink, mixed with round records
    mixed = tmp_path / "mixed.jsonl"
    with JsonlHistorySink(str(mixed)) as sink:
        sink.write({"round": 1, "accuracy": 0.5})
        async_capture.obs.export_jsonl(sink)
    assert json.loads(mixed.read_text().splitlines()[0])["kind"] == "round"


def test_prometheus_snapshot_format(async_capture):
    text = async_capture.obs.export_prometheus()
    assert "# TYPE repro_staleness histogram" in text
    assert "repro_staleness_bucket" in text and "_count" in text
    for line in text.splitlines():
        assert line.startswith(("#", "repro_"))


# ------------------------------------------ JsonlHistorySink (satellite)
def test_sink_sanitizes_non_finite_to_null(tmp_path):
    path = tmp_path / "h.jsonl"
    with JsonlHistorySink(str(path)) as sink:
        sink.write({"round": 1, "accuracy": float("nan"),
                    "seconds": float("inf"),
                    "nested": [np.float32("-inf"), np.int64(3), 1.5]})
        sink.write_trace(("finish", float("nan"), 2, 0, 0.5))
    lines = path.read_text().splitlines()
    # spec-compliant JSON: parseable with a strict parser
    rec = json.loads(lines[0], parse_constant=lambda s: pytest.fail(
        f"bare {s} token in output"))
    assert rec["accuracy"] is None and rec["seconds"] is None
    assert rec["nested"] == [None, 3, 1.5]
    tr = json.loads(lines[1])
    assert tr["event"] == ["finish", None, 2, 0, 0.5]
    assert sanitize((np.float64(2.0), {"x": np.bool_(True)})) \
        == [2.0, {"x": True}]


def test_engine_owns_path_sinks_and_flushes_user_sinks(tmp_path):
    path = tmp_path / "hist.jsonl"
    eng = RoundEngine(get_strategy("fedavg"), _ctx(),
                      history_sink=str(path))
    assert eng._owns_sink
    _, hist = eng.run(eval_every=2)
    assert hist == []                       # the stream IS the history
    assert eng.history_sink._f is None      # closed on completion
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert recs and all(r["kind"] == "round" for r in recs)

    user = JsonlHistorySink(str(tmp_path / "u.jsonl"))
    eng2 = AsyncEngine(get_strategy("fedavg"), _ctx(),
                       mode="sync", history_sink=user)
    assert not eng2._owns_sink
    eng2.run(eval_every=2)
    assert user._f is not None              # caller's sink stays open
    user.close()
    assert (tmp_path / "u.jsonl").read_text()
