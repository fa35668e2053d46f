"""Population-scale execution: sharded cohort fan-out, mesh-resident
aggregation, and O(cohort) host memory (``repro.fl.scale``,
docs/scale.md).

Three questions the scale subsystem must answer with numbers:

* **Equivalence** — on a forced 4-device CPU mesh, does
  ``RoundEngine(scheduler="sharded")`` produce BIT-IDENTICAL aggregated
  params to ``"vectorized"`` (fedavg and fedepth, ``codec="none"``),
  and what does the fan-out cost per round at toy scale?  The bitwise
  check is deterministic, so it is asserted hard, not floored.

* **O(cohort) memory** — with the cohort FIXED (100 clients/round) and
  the population swept over {10k, 100k, 1M}, peak host RSS must stay
  flat: the lazy population views + streaming history sink keep
  resident state proportional to the cohort, not the population.

* **Headline** — the ISSUE row: 1M-client population, 10k clients per
  round, fedepth masked aggregation FUSED on-mesh
  (``aggregate="mesh"``, ``max_lanes`` bounding stacked replicas).
  Reports round wall time, peak host RSS, and uplink bytes/round.

Every row runs in a FRESH subprocess: the forced multi-device mesh
needs ``XLA_FLAGS`` set before backend init (docs/scale.md §Testing on
a forced mesh), and ``ru_maxrss`` is per-process — sharing one
interpreter would let an early fat row mask a later lean one.  The
children are pinned to ``JAX_PLATFORMS=cpu`` (the forced mesh is a
host-platform device count), so every time here is a host-CPU time, on
any machine; each row records the ``device`` it ran on to say so.

Emits ``BENCH_scale.json`` via :func:`bench_lib.write_json`; CI runs
the quick tier as a smoke (headline off) and uploads the report.  The
RSS-flatness and round-time floors are enforced only under
``REPRO_BENCH_STRICT=1`` (RSS has allocator noise), with a loud warning
otherwise.  ``REPRO_BENCH_SCALE=med|full`` (or
``REPRO_BENCH_HEADLINE=1``) adds the headline row.
"""
import argparse
import json
import os
import subprocess
import sys
import time

from benchmarks.bench_lib import csv_row, write_json

DEVICES = 4
MARK = "SCALE-ROW-JSON:"


def _maxrss_mb() -> float:
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


# ==========================================================================
# row bodies (run inside the child process, forced mesh already set)
# ==========================================================================
def _row_equiv(spec: dict) -> dict:
    """Sharded vs vectorized on the forced mesh: bitwise + wall time."""
    import jax
    import numpy as np
    from repro.configs.preresnet20 import reduced as rn_reduced
    from repro.fl.data import build_federated
    from repro.fl.engine import RoundEngine, SimConfig, build_context
    from repro.fl.registry import get_strategy
    from repro.fl.sampling import VectorizedScheduler
    from repro.fl.scale import ShardedScheduler

    assert jax.device_count() == DEVICES
    data = build_federated(num_clients=8, alpha=1.0, n_train=320,
                           n_test=120, image_size=16, seed=0)
    cfg = rn_reduced(num_classes=10, image_size=16)
    n_rounds = spec["rounds"]

    def run(scheduler):
        sim = SimConfig(rounds=n_rounds, participation=1.0, lr=0.05,
                        local_steps=1, batch_size=32,
                        scenario=spec["scenario"], seed=0)
        eng = RoundEngine(get_strategy(spec["method"]),
                          build_context(data, sim, model_cfg=cfg),
                          scheduler=scheduler)
        t0 = time.perf_counter()
        state, hist = eng.run(eval_every=n_rounds)
        return state, hist, time.perf_counter() - t0

    sv, hv, tv = run(VectorizedScheduler(min_group=1))
    ss, hs, ts = run(ShardedScheduler(min_group=1))
    lv, ls = jax.tree.leaves(sv), jax.tree.leaves(ss)
    bitwise = all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(lv, ls))
    # deterministic contract, not a floor: fail the row outright
    assert bitwise, f"sharded != vectorized for {spec['method']}"
    assert [h.comm_bytes for h in hv] == [h.comm_bytes for h in hs]
    return {"bitwise_equal": True, "rounds": n_rounds,
            "vectorized_s_per_round": tv / n_rounds,
            "sharded_s_per_round": ts / n_rounds,
            "comm_bytes_per_round": hv[-1].comm_bytes // n_rounds,
            "peak_rss_mb": _maxrss_mb()}


def _build_population(spec: dict):
    from repro.configs.preresnet20 import reduced as rn_reduced
    from repro.fl.engine import SimConfig, build_context
    from repro.fl.scale import Population, PopulationSampler

    pop = Population(num_clients=spec["num_clients"],
                     scenario=spec["scenario"], seed=1,
                     image_size=spec["image_size"])
    sim = SimConfig(rounds=spec["rounds"],
                    participation=spec["cohort"] / spec["num_clients"],
                    lr=0.05, local_steps=1, batch_size=spec["batch_size"],
                    scenario=spec["scenario"], seed=0)
    cfg = rn_reduced(num_classes=10, image_size=spec["image_size"])
    ctx = build_context(None, sim, population=pop, model_cfg=cfg)
    return pop, ctx, PopulationSampler(availability=pop)


def _row_population(spec: dict) -> dict:
    """Fixed cohort over a growing population: RSS must stay flat."""
    import tempfile

    import jax
    from repro.fl.engine import RoundEngine
    from repro.fl.registry import get_strategy
    from repro.fl.scale import JsonlHistorySink, ShardedScheduler

    assert jax.device_count() == DEVICES
    pop, ctx, sampler = _build_population(spec)
    with tempfile.NamedTemporaryFile("w+", suffix=".jsonl") as f:
        sink = JsonlHistorySink(f.file)
        eng = RoundEngine(get_strategy("fedepth"), ctx,
                          scheduler=ShardedScheduler(), sampler=sampler,
                          history_sink=sink)
        t0 = time.perf_counter()
        state, hist = eng.run(eval_every=1)
        wall = time.perf_counter() - t0
        assert hist == [] and sink.records == spec["rounds"]
        f.seek(0)
        recs = [json.loads(line) for line in f]
    return {"num_clients": spec["num_clients"], "cohort": spec["cohort"],
            "rounds": spec["rounds"], "s_per_round": wall / spec["rounds"],
            "comm_bytes_per_round":
                sum(r["comm_bytes"] for r in recs) // spec["rounds"],
            "final_accuracy": recs[-1]["accuracy"],
            "peak_rss_mb": _maxrss_mb()}


def _row_headline(spec: dict) -> dict:
    """1M clients, 10k/round, fused on-mesh masked aggregation.

    The trace-driven loader draws a FIXED number of local batches per
    client (vs the protocol's |D_k|/B, exercised by the population
    rows): a uniform batch signature lets whole budget groups stack
    into mesh dispatches instead of shattering into per-|D_k|
    sub-cohorts, which is how a real population trace would be bucketed
    anyway."""
    import jax
    from repro.fl.engine import RoundEngine
    from repro.fl.scale import ShardedScheduler
    from repro.fl.strategies.fedepth import FedepthStrategy

    assert jax.device_count() == DEVICES
    pop, ctx, sampler = _build_population(spec)
    # masked_aggregation exposes group_mask, the fused-path eligibility
    # gate; get_strategy("fedepth") builds the unmasked default
    strat = FedepthStrategy(masked_aggregation=True)
    sched = ShardedScheduler(aggregate="mesh",
                             max_lanes=spec["max_lanes"])
    eng = RoundEngine(strat, ctx, scheduler=sched, sampler=sampler)
    data = ctx.data

    def batch_fn(k):
        return [data.client_batch(k, spec["batch_size"], ctx.rng)
                for _ in range(spec["local_batches"])]

    t0 = time.perf_counter()
    state, hist = eng.run(eval_every=1, batch_fn=batch_fn)
    wall = time.perf_counter() - t0
    per_round = [h.seconds for h in hist]
    return {"num_clients": spec["num_clients"], "cohort": spec["cohort"],
            "rounds": spec["rounds"], "max_lanes": spec["max_lanes"],
            "wall_s": wall, "s_per_round": per_round,
            "comm_bytes_per_round": hist[-1].comm_bytes,
            "final_accuracy": hist[-1].accuracy,
            "peak_rss_mb": _maxrss_mb()}


ROW_KINDS = {"equiv": _row_equiv, "population": _row_population,
             "headline": _row_headline}


def _child(spec_json: str) -> None:
    """Child entry: force the mesh BEFORE any jax backend touch, run the
    row, print the result behind a parse marker."""
    spec = json.loads(spec_json)
    from repro.launch.mesh import force_host_device_count
    force_host_device_count(DEVICES)
    out = ROW_KINDS[spec["kind"]](spec)
    import jax
    dev = jax.devices()[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": jax.device_count()}
    print(MARK + json.dumps(out))


# ==========================================================================
# parent harness
# ==========================================================================
def _rows() -> list:
    tier = os.environ.get("REPRO_BENCH_SCALE", "quick")
    pop_rounds = {"quick": 2, "med": 3, "full": 5}.get(tier, 2)
    rows = [
        ("equiv/fedavg", {"kind": "equiv", "method": "fedavg",
                          "scenario": "fair", "rounds": 3}),
        ("equiv/fedepth", {"kind": "equiv", "method": "fedepth",
                           "scenario": "lack", "rounds": 3}),
    ]
    for n in (10_000, 100_000, 1_000_000):
        rows.append((f"population/{n}", {
            "kind": "population", "num_clients": n, "cohort": 100,
            "rounds": pop_rounds, "scenario": "lack",
            "image_size": 8, "batch_size": 16}))
    if tier in ("med", "full") or os.environ.get("REPRO_BENCH_HEADLINE"):
        rows.append(("headline/1M_10k", {
            "kind": "headline", "num_clients": 1_000_000, "cohort": 10_000,
            "rounds": 2, "scenario": "lack", "image_size": 8,
            "batch_size": 16, "local_batches": 2, "max_lanes": 32}))
    return rows


def _run_row(name: str, spec: dict) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.scale", "--row",
         json.dumps(spec)],
        capture_output=True, text=True, env=env, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(f"row {name} failed:\n{proc.stdout[-2000:]}\n"
                           f"{proc.stderr[-2000:]}")
    line = next(l for l in proc.stdout.splitlines() if l.startswith(MARK))
    return json.loads(line[len(MARK):])


def main() -> None:
    t0 = time.time()
    results = {}
    for name, spec in _rows():
        print(f"  [scale] {name} ...", flush=True)
        results[name] = _run_row(name, spec)
        r = results[name]
        extra = (f" [{r['device']['platform']}]"
                 f" rss={r['peak_rss_mb']:.0f}MB")
        if "s_per_round" in r:
            spr = r["s_per_round"]
            spr = spr[-1] if isinstance(spr, list) else spr
            extra += f" {spr:.2f}s/round"
        print(f"  [scale] {name}:{extra}")

    payload = {"config": {"devices": DEVICES,
                          "tier": os.environ.get("REPRO_BENCH_SCALE",
                                                 "quick")},
               "rows": results}
    write_json("scale", payload)

    # acceptance floors: equivalence is asserted inside the rows (hard,
    # deterministic); RSS flatness is host-allocator-noisy, so it is a
    # strict-mode floor — peak RSS at a 1M population must stay within
    # 1.5x of the 10k one for the SAME fixed cohort (O(cohort), not
    # O(population)).
    rss_lo = results["population/10000"]["peak_rss_mb"]
    rss_hi = results["population/1000000"]["peak_rss_mb"]
    ratio = rss_hi / rss_lo
    msgs = []
    if ratio > 1.5:
        msgs.append(f"RSS grows with population: 1M/10k = {ratio:.2f}x "
                    f"({rss_lo:.0f} -> {rss_hi:.0f} MB), floor 1.5x")
    if msgs:
        msg = "; ".join(msgs)
        if os.environ.get("REPRO_BENCH_STRICT"):
            raise AssertionError(msg)
        print(f"WARNING: {msg} (rerun with REPRO_BENCH_STRICT=1 "
              f"to enforce)")
    us = (time.time() - t0) * 1e6
    head = results.get("headline/1M_10k")
    tail = (f"headline_s_per_round={head['s_per_round'][-1]:.1f}"
            if head else "headline=skipped")
    print(csv_row("scale", us, f"rss_1M_over_10k={ratio:.2f}x;{tail}"))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--row", default=None, help="internal: run one row "
                    "spec (JSON) in this process and print its result")
    args = ap.parse_args()
    if args.row:
        _child(args.row)
    else:
        main()
