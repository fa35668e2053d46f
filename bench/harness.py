"""The benchmark harness: builds one cell of ``BENCHMARK.json`` from its
files, times whole FeDepth rounds of the program, and decides ``correct``.

A cell names a configuration (``configs/<config>.json`` with its plain
reference ``configs/<config>.py`` and FLOP count ``flops/<config>.py``)
and a traffic mix (``traffic/<traffic>.json``); its limits are
``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<metric>.py``.  Nothing here names a cell.

Order of a run:
1. set-up: the program's data and context from the seed, the tiers of
   the traffic mix priced and decomposed by the program's own memory
   model (each tier's blocks held to the mix's table), the weights
   drawn on the device in one jitted call, a warm-up of one client of
   every tier and one aggregation of the cohort's size (on a copy of the
   context's random stream), then the first ``compare_rounds`` rounds
   through ``RoundEngine.run_round``;
2. the window: whole rounds of the same engine until ``--seconds`` have
   passed, each ended by ``block_until_ready``;
3. after the window, with the program's state freed: the reference
   runs each compared round from the program's params at that round's
   start, on the inputs it draws from the seed's stream, each client on
   its tier's blocks from the mix's table, and the numbers of
   ``compare.py`` are held to the cell's limits.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from fractions import Fraction
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileClock:
    """Seconds and events of JAX tracing, lowering and compiling, summed
    from its own monitoring events.  A persistent-cache hit skips the
    backend compile but not the trace."""

    def __init__(self):
        import jax
        self.seconds, self.events = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def use_checkout_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    where the program's ``enable_compile_cache`` puts it; a directory
    from the environment outside the checkout is not used.  Every
    program is cached, however quick its compile, so that only a cell's
    first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = Path(enable_compile_cache()).resolve()
    if ROOT.resolve() not in where.parents:
        where = ROOT / ".jax_cache"
        jax.config.update("jax_compilation_cache_dir", str(where))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(where)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path):
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    config: str
    chips: int
    sizes: dict
    traffic: dict
    limits: dict
    per_layer: List[dict]
    end_to_end: List[dict]
    model: object          # configs/<config>.py
    flops: object          # flops/<config>.py


def load_cell(workload: str) -> Cell:
    """The cell named ``workload`` in ``BENCHMARK.json``."""
    spec = _read_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = by_name[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    return Cell(
        name=workload, config=w["config"], chips=int(w["chips"]),
        sizes=_read_json(ROOT / conf["file"]),
        traffic=_read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(BENCH / "limits" / f"{workload}.json"),
        per_layer=[m for m in spec["per_layer"] if applies(m)],
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        model=load_module(BENCH / "configs" / f"{w['config']}.py"),
        flops=load_module(BENCH / "flops" / f"{w['config']}.py"))


def check_device(chips: int, require_tpu: bool = True):
    import jax
    dev = jax.devices()
    if require_tpu and dev[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {dev[0].platform!r})")
    if len(dev) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(dev)}")
    return dev[0]


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def tier_context(ctx, traffic: dict, seed: int):
    """Give the clients the traffic mix's memory tiers, spread evenly and
    shuffled by the seed, priced and decomposed by the program's own
    memory model (``scenario_budgets`` / ``decompose``).  Returns the
    context and each client's tier, as the mix names it."""
    from repro.core.decomposition import decompose
    from repro.fl.engine import scenario_budgets

    n = ctx.num_clients
    tiers = np.tile(np.array(traffic["tiers"]),
                    math.ceil(n / len(traffic["tiers"])))[:n]
    np.random.default_rng([seed, 1]).shuffle(tiers)
    ratios = np.array([float(Fraction(t)) for t in tiers])
    budgets = scenario_budgets(ctx.mem, ratios)
    by_budget: Dict[int, object] = {}
    decomps = [by_budget.setdefault(int(b), decompose(ctx.mem, int(b)))
               for b in budgets]
    ctx = dataclasses.replace(ctx, ratios=ratios, budgets=budgets,
                              decomps=decomps,
                              surplus=np.where(ratios >= 2.0, 2, 1))
    return ctx, [str(t) for t in tiers]


def tier_blocks(traffic: dict) -> Dict[str, tuple]:
    """Each tier's blocks as the traffic mix states them: Algorithm 1's
    decomposition for the tier's budget, written out by hand."""
    return {t: tuple(tuple(b) for b in blocks)
            for t, blocks in traffic["decompositions"].items()}


def cohort_size(traffic: dict) -> int:
    n = traffic["num_clients"]
    return min(n, max(1, int(math.ceil(traffic["participation"] * n))))


def host(tree):
    import jax
    return jax.tree.map(np.asarray, jax.device_get(tree))


class Bench:
    """One cell's program, built from a seed: the engine, its state and
    its feed.  The same object runs the compared rounds and the window."""

    def __init__(self, cell: Cell, seed: int, *, trace: bool = False,
                 kernel_force: Optional[str] = None,
                 caches: Optional[dict] = None):
        import jax
        from repro.fl import RoundEngine
        from repro.fl.sampling import UniformSampler
        from repro.fl.strategies.fedepth import FedepthStrategy
        from spans import SpannedSampler, SpannedStrategy

        self.cell, self.seed, self.trace = cell, seed, trace
        ctx = cell.model.program_context(cell.sizes, cell.traffic, seed,
                                         kernel_force=kernel_force)
        ctx, self.tiers = tier_context(ctx, cell.traffic, seed)
        self.blocks = tier_blocks(cell.traffic)
        if caches is not None:
            ctx = dataclasses.replace(ctx, caches=caches)
        strategy = FedepthStrategy()
        strategy.setup(ctx)
        init = jax.jit(functools.partial(cell.model.init_params,
                                         sizes=cell.sizes))
        self.state = jax.block_until_ready(init(seed_key(seed)))
        if trace:
            strategy = SpannedStrategy(strategy)
        self.engine = RoundEngine(
            strategy, ctx,
            sampler=SpannedSampler(UniformSampler()) if trace else None)
        self.ctx = self.engine.ctx
        self.round = 0
        self.cohorts: List[List[str]] = []
        self._feed = self.engine.default_batch_fn()

    def feed(self, k: int):
        """The program's own batch draw for client ``k``; in traced runs
        spanned, and the client's tier recorded for the FLOP count."""
        if not self.trace:
            return self._feed(k)
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.batch"):
            batches = self._feed(k)
        self.cohorts[-1].append(self.tiers[k])
        return batches

    def decomposition_mismatch(self) -> int:
        """Tiers whose decomposition in the program differs from the one
        the traffic mix states."""
        return len({t for t, dec in zip(self.tiers, self.ctx.decomps)
                    if tuple(map(tuple, dec.blocks)) != self.blocks[t]})

    def warm_up(self):
        """One client of every tier and one aggregation of the cohort's
        size, on a copy of the context's random stream: every program the
        rounds use is compiled, and the rounds' draws are untouched."""
        import jax
        ctx = dataclasses.replace(self.ctx, rng=copy.deepcopy(self.ctx.rng))
        from repro.fl.engine import default_batch_fn
        firsts: Dict[tuple, int] = {}
        for k, dec in enumerate(ctx.decomps):
            firsts.setdefault((dec.blocks, dec.skipped_prefix), k)
        clients = list(firsts.values())
        strategy = self.engine.strategy
        results = self.engine.scheduler.run(ctx, strategy, self.state,
                                            clients, default_batch_fn(ctx))
        n = cohort_size(self.cell.traffic)
        cohort = [results[i % len(results)] for i in range(n)]
        jax.block_until_ready(strategy.aggregate(ctx, self.state, cohort))

    def run_round(self):
        import jax
        self.cohorts.append([])
        self.state, _, _ = self.engine.run_round(self.state, self.round,
                                                 self.feed)
        jax.block_until_ready(self.state)
        self.round += 1

    def compared_rounds(self, n: int):
        """The first ``n`` rounds.  Returns the params at every round
        boundary, ``[init, after round 1, ..., after round n]`` (on the
        host) and, per round, (blocks, batches, weight) of each client in
        cohort order: the inputs the seed gives those rounds, drawn again
        by the reference from a copy of the random stream as the paper's
        protocol draws them, with each client's blocks from its tier's
        row of the traffic mix, whatever the program did with its own
        draws."""
        from fedepth_ref import draw_rounds
        stream = copy.deepcopy(self.ctx.rng)
        params = [host(self.state)]
        for _ in range(n):
            self.run_round()
            params.append(host(self.state))
        data, t = self.ctx.data, self.cell.traffic
        drawn = draw_rounds(stream, data.client_indices, n,
                            cohort_size(t), t["batch_size"],
                            functools.partial(self.cell.model.ref_batch,
                                              data))
        rounds = [[(self.blocks[self.tiers[k]], batches,
                    float(len(data.client_indices[k])))
                   for k, batches in clients] for clients in drawn]
        return params, rounds

    def finite(self) -> bool:
        import jax
        import jax.numpy as jnp
        return bool(jax.jit(lambda t: jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(t)])))(
                self.state))

    def close(self):
        self.engine = self.state = self.ctx = None
        gc.collect()


def reference_params(cell: Cell, starts, rounds, **variant):
    """The reference's params after each of ``rounds`` (host), round
    ``r`` run from ``starts[r]``: the program's params at its start."""
    from fedepth_ref import Reference

    t = cell.traffic
    ref = Reference(cell.model, cell.sizes, lr=t["lr"],
                    momentum=t["momentum"], local_steps=t["local_steps"],
                    **variant)
    return [host(ref.round(start, clients))
            for start, clients in zip(starts, rounds)]


def judge(cell: Cell, params, refs):
    """``params``: the program's at every round boundary; ``refs``: the
    reference's after each round.  Returns name -> (value, limit, worst
    round and leaf), each round's numbers, and whether every value is
    within its limit."""
    from compare import by_round, worst
    rounds = by_round(cell.model.leaves, params[:-1], params[1:], refs)
    checks = {k: (v, cell.limits[k], leaf)
              for k, (v, leaf) in worst(rounds).items()}
    ok = all(math.isfinite(v) and v <= lim for v, lim, _ in checks.values())
    return checks, rounds, ok


def per_layer(cell: Cell, red, rounds: int, cohorts,
              device_kind: str) -> Dict[str, float]:
    """Every per-layer metric of the cell that its reader finds.
    ``cohorts``: the tier of each client of each round in the window."""
    from peaks import peak
    blocks = tier_blocks(cell.traffic)
    view = SimpleNamespace(
        trace=red, rounds=rounds, window_s=red.window_s, sizes=cell.sizes,
        traffic=cell.traffic, flops=cell.flops, peak=peak(device_kind),
        cohorts=[[blocks[t] for t in c] for c in cohorts])
    out = {}
    for m in cell.per_layer:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, require_tpu: bool = True, kernel_force: Optional[str] = None):
    """One run of one cell.  Returns the result line's dict."""
    import jax
    device = check_device(cell.chips, require_tpu)
    clock = CompileClock()
    bench = Bench(cell, seed, trace=trace, kernel_force=kernel_force)
    bench.warm_up()
    mismatch = bench.decomposition_mismatch()
    params, recorded = bench.compared_rounds(cell.traffic["compare_rounds"])
    bench.cohorts = []

    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(logdir)
    compiles0, setup_compile_s = clock.events, clock.seconds
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    rounds = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.round"):
                bench.run_round()
            rounds += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    compiles = clock.events - compiles0
    if trace:
        jax.profiler.stop_trace()
    finite = bench.finite()
    memory_peak = peak_bytes(device)
    cohorts = bench.cohorts
    bench.close()
    del bench

    t_ref = time.perf_counter()
    refs = reference_params(cell, params[:-1], recorded)
    print(f"phases: setup_s {setup_s:.3f} (compile {setup_compile_s:.3f}) "
          f"window_s {window_s:.3f} rounds {rounds} compiles_in_window "
          f"{compiles} reference_s {time.perf_counter() - t_ref:.3f}",
          file=sys.stderr)
    checks, per_round, ok = judge(cell, params, refs)

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(ok and finite and compiles == 0
                              and mismatch == 0),
              "attempted": rounds, "failed": 0 if finite else rounds}
    if trace:
        import xtrace
        red = xtrace.Reduced(xtrace.load_logdir(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        metrics = per_layer(cell, red, rounds, cohorts, device.device_kind)
        dev.update(busy_s=red.busy_s(), window_s=red.window_s)
        result.update(metrics=metrics, device=dev,
                      breakdown=xtrace.breakdown(red))
    else:
        names = {m["name"] for m in cell.end_to_end}
        values = {"round_s": (window_s / rounds, "s"), "setup_s": (setup_s, "s")}
        result.update(metrics={k: {"value": v, "unit": u}
                               for k, (v, u) in values.items() if k in names},
                      device=dev)
    for k, (v, lim, leaf) in checks.items():
        print(f"{k} by round: "
              + " ".join(f"{rd[k][0]:.6g}" for rd in per_round)
              + f"; worst: {leaf}", file=sys.stderr)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim, _) in checks.items()}
    result["checks"]["compiles_in_window"] = {"value": compiles, "limit": 0}
    result["checks"]["decomposition_mismatch"] = {"value": mismatch,
                                                  "limit": 0}
    return result
