"""The reduction from a profiler trace to the per-layer metrics: exact on
a hand-made trace, and consistent on traces recorded from chip runs of
the cells (``testdata/``)."""
import math
from types import SimpleNamespace

import pytest

import harness
import xtrace
from smallcells import BENCH, CELLS

MS = 1_000_000   # ns


def hand_trace():
    """Window [0, 100) ms.  Device: program A [10, 30) with ops [10, 20)
    and [15, 30); program B [50, 60) with one op; a kernel op [70, 75)
    in program C [70, 80).  Host: a round over the window, a client
    update [0, 65), a batch draw [0, 8) inside it, an aggregate [65, 95)."""
    ev = lambda n, s, e: [n, s * MS, (e - s) * MS]  # noqa: E731
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ev("jit_step(11)", 10, 30), ev("jit_fwd(12)", 50, 60),
                ev("jit_step(13)", 70, 80), ev("jit_step(14)", 95, 105)]},
            {"name": "XLA Ops", "events": [
                ev("fusion.1", 10, 20), ev("fusion.2", 15, 30),
                ev("copy.3", 50, 60), ev("_ssd_kernel", 70, 75),
                ev("fusion.4", 75, 80), ev("fusion.5", 95, 105)]},
            {"name": "Steps", "events": [ev("1", 0, 100)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ev("bench.window", 0, 100), ev("bench.round", 0, 100),
                ev("bench.client_update", 0, 65), ev("bench.batch", 0, 8),
                ev("bench.aggregate", 65, 95),
                ev("PjitFunction(step)", 9, 11)]}]}]}


def test_hand_trace_reduces_exactly():
    red = xtrace.Reduced(hand_trace())
    assert red.window_s == pytest.approx(0.1)
    # the program at [95, 105) crosses the window's end: left out
    assert len(red.modules()) == 3
    assert red.program_seconds() == pytest.approx(
        {"jit_step": 0.030, "jit_fwd": 0.010})
    assert red.busy_s() == pytest.approx(0.020 + 0.010 + 0.010)
    assert len(red.op_events("_ssd_kernel")) == 1
    # gaps: [0,10) batch, [30,50) client update, [60,70) aggregate
    # ([60,65) is the client update's, but the gap's middle is at 65),
    # [80,100): middle 90 -> aggregate
    idle = red.idle_by_span()
    assert idle == pytest.approx({"batch": 0.010, "client_update": 0.020,
                                  "aggregate": 0.030})
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s())
    b = xtrace.breakdown(red)
    assert b["device_ops"][0] == ["jit_step", pytest.approx(0.030)]
    assert [k for k, _ in b["idle_gaps"]] == ["aggregate", "client_update",
                                              "batch"]


def test_trace_needs_exactly_one_window():
    space = hand_trace()
    space["planes"][1]["lines"][0]["events"].append(
        ["bench.window", 200 * MS, MS])
    with pytest.raises(ValueError):
        xtrace.Reduced(space)


def test_trim_keeps_a_consistent_prefix():
    red = xtrace.Reduced(xtrace.trim(hand_trace(), 0.055))
    assert red.window_s == pytest.approx(0.055)
    assert red.program_seconds() == pytest.approx({"jit_step": 0.020})
    assert sum(red.idle_by_span().values()) == pytest.approx(0.035)


@pytest.fixture(params=CELLS)
def recorded(request):
    path = BENCH / "testdata" / f"{request.param}.trace.json.gz"
    return harness.load_cell(request.param), xtrace.Reduced(
        xtrace.load(str(path)))


def test_recorded_trace_is_consistent(recorded):
    _, red = recorded
    busy, window = red.busy_s(), red.window_s
    assert 0 < busy < window
    # programs do not overlap on one chip: their time bounds the ops'
    assert sum(red.program_seconds().values()) >= busy * 0.999
    idle = red.idle_by_span()
    assert sum(idle.values()) == pytest.approx(window - busy, rel=1e-6)
    assert "none" not in idle or idle["none"] < 0.01 * window


def test_readers_on_recorded_trace(recorded):
    cell, red = recorded
    tier = cell.traffic["tiers"][0]
    view = SimpleNamespace(
        trace=red, rounds=1, window_s=red.window_s, sizes=cell.sizes,
        traffic=cell.traffic, flops=cell.flops,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        cohorts=[[harness.tier_blocks(cell.traffic)[tier]]])
    got = {}
    for m in cell.per_layer:
        got[m["name"]] = harness.load_module(
            BENCH / "metrics" / f"{m['name']}.py").read(view)
    assert got["dispatches_per_round"] == len(red.modules())
    assert 0 < got["device_idle_share"] < 100
    assert got["block_train_ms"] > 0
    assert 0 < got["round_mfu"] < 100
    for k in ("ssd_roofline", "ce_roofline"):
        if k in got and got[k] is not None:
            assert 0 < got[k] <= 100
    assert all(v is None or math.isfinite(v) for v in got.values())
