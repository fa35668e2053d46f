"""Host self time of the program's ``repro.*`` spans (``hostspans.py``)
and the readers of the host-time metrics: exact on a hand-made trace,
a partition of the spans a round writes, no change to what the existing
readers read, and consistent on a trace recorded from a chip run of
``preresnet20.partial``."""
from types import SimpleNamespace

import pytest

import harness
import hostspans
import xtrace
from smallcells import BENCH, kernel_force, small

MS = 1_000_000   # ns
HOST_MS = ("engine_host_ms", "block_setup_host_ms", "block_dispatch_host_ms",
           "prefix_host_ms", "aggregate_host_ms")
SIX = HOST_MS + ("host_to_device_mb",)
#: every span the program writes inside a round (docs/observability.md)
ROUND_SPANS = {"round", "sample", "batch", "client-update", "cohort-group",
               "block", "block.setup", "block.steps", "block.merge",
               "prefix", "payload", "comm", "aggregate", "aggregate.finite"}
RECORDED = BENCH / "testdata" / "preresnet20.partial.spans.trace.json.gz"
OLD = BENCH / "testdata" / "preresnet20.partial.trace.json.gz"


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def hand_trace():
    """Window [0, 100) ms.  Thread "python": a round [2, 98) holding a
    sample, a client update (inside the harness's own
    ``bench.client_update``) with a batch draw, one block (prefix,
    set-up, steps, merge) and a payload, the wire and an aggregation
    with its finiteness check; a second round from 99 ms, cut by the
    window's end.  Thread "worker": block steps [30, 40) on their own."""
    ev = lambda n, s, e, **st: [n, s * MS, (e - s) * MS] + (  # noqa: E731
        [st] if n.startswith("repro.") else [])
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [ev("jit_step(1)", 20, 30)]},
            {"name": "XLA Ops", "events": [ev("fusion.1", 20, 30)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ev("bench.window", 0, 100), ev("bench.round", 0, 100),
                ev("repro.round", 2, 98, round=0),
                ev("repro.sample", 2, 4),
                ev("bench.client_update", 5, 60),
                ev("repro.client-update", 6, 60, client=3),
                ev("repro.batch", 6, 8, client=3),
                ev("repro.block", 10, 50, lo=3, hi=4, j=0),
                ev("repro.prefix", 10, 14, mode="buffer", host_bytes=100),
                ev("repro.block.setup", 14, 20),
                ev("repro.block.steps", 20, 45, steps=4, host_bytes=1000),
                ev("repro.block.merge", 45, 47),
                ev("repro.payload", 52, 53),
                ev("repro.comm", 60, 62),
                ev("repro.aggregate", 62, 80, clients=1),
                ev("repro.aggregate.finite", 63, 70, clients=1),
                ev("repro.round", 99, 120, round=1),
                ev("repro.sample", 99, 101)]},
            {"name": "worker", "events": [
                ev("repro.block.steps", 30, 40, steps=1, host_bytes=7)]}]}]}


def _view(red, rounds=1):
    return SimpleNamespace(trace=red, rounds=rounds, window_s=red.window_s)


def test_self_time_on_a_hand_trace():
    red = hostspans.reduce(hand_trace())
    got = {}
    for sp in red.program_spans:
        got[(sp.name, sp.thread)] = got.get((sp.name, sp.thread), 0) \
            + sp.self_ns / MS
    assert got == {
        # 96 less sample, client update, wire, aggregation; the cut
        # round keeps [99, 100), all of it its sample's
        ("repro.round", "python"): 20 + 0,
        ("repro.sample", "python"): 2 + 1,
        # the bench span between round and client update is ignored
        ("repro.client-update", "python"): 54 - 2 - 40 - 1,
        ("repro.batch", "python"): 2,
        ("repro.block", "python"): 40 - 4 - 6 - 25 - 2,
        ("repro.prefix", "python"): 4, ("repro.block.setup", "python"): 6,
        ("repro.block.steps", "python"): 25,
        ("repro.block.merge", "python"): 2, ("repro.payload", "python"): 1,
        ("repro.comm", "python"): 2, ("repro.aggregate", "python"): 18 - 7,
        ("repro.aggregate.finite", "python"): 7,
        # nesting is per thread: nothing encloses the worker's span
        ("repro.block.steps", "worker"): 10}
    values = {m: reader(m).read(_view(red)) for m in SIX}
    assert values == pytest.approx({
        "engine_host_ms": 20 + 3 + 2 + 11, "block_setup_host_ms": 3 + 6 + 2,
        "block_dispatch_host_ms": 25 + 10, "prefix_host_ms": 4,
        "aggregate_host_ms": 1 + 2 + 11 + 7,
        "host_to_device_mb": (100 + 1000 + 7) / 1e6})
    # the five partition the program's time: both threads, clipped
    assert sum(values[m] for m in HOST_MS) == pytest.approx(96 + 1 + 10)
    # per round
    assert reader("prefix_host_ms").read(_view(red, rounds=2)) == 2


def test_trim_keeps_program_spans_with_their_stats():
    red = hostspans.reduce(hostspans.trim(hand_trace(), 0.055))
    assert red.window_s == pytest.approx(0.055)
    total = sum(sp.self_ns for sp in red.program_spans) / MS
    assert total == pytest.approx((55 - 2) + 10)
    steps = [sp for sp in red.program_spans
             if sp.name == "repro.block.steps" and sp.thread == "python"]
    assert steps[0].stats == {"steps": 4, "host_bytes": 1000}
    assert not any(sp.name == "repro.aggregate" for sp in red.program_spans)


def test_host_readers_partition_the_round_spans():
    kinds = [set(reader(m).KINDS) for m in HOST_MS]
    assert sum(len(k) for k in kinds) == len(set().union(*kinds))
    assert set().union(*kinds) == ROUND_SPANS
    assert set(reader("host_to_device_mb").KINDS) == {"block.steps",
                                                      "prefix"}


def test_a_round_writes_only_spans_the_readers_read(tmp_path):
    """One round of the cell at reduced size on the CPU, under the
    profiler: every span it writes is read by one of the five, and all
    but the vectorized scheduler's group span are there."""
    import jax
    cell = small(harness.load_cell("preresnet20.partial"))
    bench = harness.Bench(cell, 2**33 + 7, kernel_force=kernel_force(cell))
    bench.warm_up()
    jax.profiler.start_trace(str(tmp_path))
    try:
        bench.run_round()
    finally:
        jax.profiler.stop_trace()
    names = {ev[0][len(hostspans.PREFIX):]
             for evs in hostspans.program_events(str(tmp_path)).values()
             for ev in evs}
    assert names <= ROUND_SPANS
    assert ROUND_SPANS - names == {"cohort-group"}


@pytest.fixture(params=[OLD, RECORDED], ids=["old", "spans"])
def space(request):
    return xtrace.load(str(request.param))


def test_existing_readers_read_the_same(space):
    """The program's spans change nothing that the benchmark's existing
    readers and ``breakdown`` read."""
    cell = harness.load_cell("preresnet20.partial")
    plain, spanned = xtrace.Reduced(space), hostspans.reduce(space)
    tier = cell.traffic["tiers"][0]

    def view(red):
        return SimpleNamespace(
            trace=red, rounds=1, window_s=red.window_s, sizes=cell.sizes,
            traffic=cell.traffic, flops=cell.flops,
            peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            cohorts=[[harness.tier_blocks(cell.traffic)[tier]]])

    for m in cell.per_layer:
        r = reader(m["name"])
        assert r.read(view(plain)) == r.read(view(spanned))
    assert xtrace.breakdown(plain) == xtrace.breakdown(spanned)
    assert plain.spans == spanned.spans
    assert plain.idle_by_span() == spanned.idle_by_span()


def test_six_readers_on_the_recorded_trace():
    cell = harness.load_cell("preresnet20.partial")
    red = hostspans.reduce(xtrace.load(str(RECORDED)))
    view = _view(red)
    got = {m: reader(m).read(view) for m in SIX}
    assert all(v is not None and v > 0 for v in got.values())
    rounds_ms = sum(sp.end - sp.start for sp in red.program_spans
                    if sp.name == "repro.round") / MS
    assert sum(got[m] for m in HOST_MS) == pytest.approx(rounds_ms, rel=0.01)
    # host bytes: each step and each buffering prefix forward copies one
    # 64-image batch from the host; the advances read device buffers
    t, s = cell.traffic, cell.sizes
    batch = t["batch_size"] * (s["image_size"] ** 2 * s["in_channels"] * 4
                               + 4)
    assert batch == 786_688
    n_batches = t["samples_per_client"] // t["batch_size"]
    steps = [sp for sp in red.program_spans
             if sp.name == "repro.block.steps"]
    prefix = [sp for sp in red.program_spans if sp.name == "repro.prefix"]
    assert steps and prefix
    for sp in steps:
        assert sp.stats["steps"] == t["local_steps"] * n_batches
        assert sp.stats["host_bytes"] == sp.stats["steps"] * batch
    for sp in prefix:
        assert sp.stats["host_bytes"] == (
            n_batches * batch if sp.stats["mode"] == "buffer" else 0)
    # a whole round: 10 clients x (5 blocks x 12 steps + 6 prefix
    # forwards) batches, what the chip run's ``host_to_device_mb`` reads
    blocks = len(harness.tier_blocks(t)[t["tiers"][0]])
    per_client = (blocks * t["local_steps"] * n_batches + n_batches) * batch
    assert per_client * harness.cohort_size(t) == 519_214_080


def test_readers_return_nothing_without_program_spans():
    """A trace of a program that writes no ``repro.*`` spans, read with
    or without ``hostspans``."""
    space = xtrace.load(str(OLD))
    for red in (xtrace.Reduced(space), hostspans.reduce(space)):
        assert all(reader(m).read(_view(red)) is None for m in SIX)
