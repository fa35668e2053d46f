"""The single FL round engine — shared by ALL methods.

One loop owns what the pre-registry per-method monolith and
``core.fedepth.FedepthServer`` used to duplicate: cohort sampling
(pluggable, :mod:`repro.fl.sampling`), the paper's budget / decomposition
assignment, per-experiment jit/step caches, eval cadence, and a
structured history of ``RoundRecord(round, accuracy, seconds,
comm_bytes)``.

Methods plug in as :class:`repro.fl.strategy.FLStrategy` instances; the
engine never branches on the method name.

Budget protocol (paper §Memory budgets): client memory budgets are the
width-ratio-equivalent training footprints of PreResNet at batch 128,
r uniformly distributed over the scenario's tuple:
    Fair    r = {1/6, 1/3, 1/2, 1}
    Lack    r = {1/8, 1/6, 1/2, 1}     (partial training kicks in)
    Surplus r = {1/6, 1/3, 1/2, 2}     (MKD clients)
The full protocol — where ``SCENARIOS`` / ``BUDGET_SLACK`` /
``width_equivalent_budget`` / the decomposition floor come from and how
they map onto the paper's Table 1 — is specified in
``docs/budget_protocol.md``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import numpy as np

from repro.configs.preresnet20 import ResNetConfig
from repro.core.decomposition import decompose, width_equivalent_budget
from repro.core.memory_model import resnet_memory
from repro.fl.comm import CommChannel
from repro.fl.sampling import (CohortSampler, ClientScheduler,
                               SequentialScheduler, UniformSampler,
                               VectorizedScheduler, make_scheduler)
from repro.fl.strategy import (ClientResult, Context, FLStrategy,
                               wire_bytes)
from repro.obs import annotate, make_obs, scope, span_if

SCENARIOS: Dict[str, Tuple[float, ...]] = {
    "fair": (1 / 6, 1 / 3, 1 / 2, 1.0),
    "lack": (1 / 8, 1 / 6, 1 / 2, 1.0),
    "surplus": (1 / 6, 1 / 3, 1 / 2, 2.0),
}

# decomposition slack: the paper's own Table 1 prices x1/6 (19.34) just
# UNDER B1-3 (20.02) yet trains B1 alone, i.e. its protocol carries
# implicit headroom; our coarser constants need ~20%.
BUDGET_SLACK = 1.20


@dataclasses.dataclass
class SimConfig:
    rounds: int = 20
    participation: float = 0.1
    lr: float = 0.05
    momentum: float = 0.9
    local_steps: int = 2
    batch_size: int = 64
    mem_batch: int = 128          # batch used to price memory (paper: 128)
    scenario: str = "fair"
    seed: int = 0


class RoundRecord(NamedTuple):
    """One history entry.  Index-compatible with the legacy ``(round,
    acc)`` tuples (``rec[0]``/``rec[1]``); ``seconds`` and ``comm_bytes``
    accumulate wall-clock and client-upload traffic since the previous
    record.  ``sim_seconds`` is the ABSOLUTE simulated time of the record
    under a system-time engine (:mod:`repro.fl.systime`); the wall-clock
    ``RoundEngine`` has no virtual clock and stamps 0.0.

    ``comm_bytes`` counts the UPLINK as it actually crossed the wire —
    the exact encoded ``WirePayload`` size when a lossy codec is active,
    raw float32 payload bytes under ``codec="none"`` (identical to the
    pre-channel accounting).  ``down_bytes`` is the matching DOWNLINK
    accumulator: full-model broadcast bytes by default, or the
    sliced/delta wire size when the engine's ``downlink`` knob is set
    (see ``docs/comm.md``)."""
    round: int
    accuracy: Optional[float]
    seconds: float
    comm_bytes: int
    sim_seconds: float = 0.0
    down_bytes: int = 0


def client_ratios(num_clients: int, scenario: str,
                  seed: int = 0) -> np.ndarray:
    """Distribute the scenario's ratios over clients: uniform multiset
    (counts differ by at most one), assignment seeded-shuffled so client
    id never correlates with memory tier (client 0 is not always the
    poorest device across every experiment)."""
    rs = SCENARIOS[scenario]
    reps = int(np.ceil(num_clients / len(rs)))
    arr = np.tile(np.asarray(rs), reps)[:num_clients]
    np.random.default_rng(seed).shuffle(arr)
    return arr


def scenario_budgets(mem, ratios) -> np.ndarray:
    """Width-equivalent byte budgets for the scenario's ratio vector."""
    # every client can at least train the finest unit + head (the paper's
    # implicit assumption "all blocks can be trained after decomposition")
    floor = min(mem.block_train_bytes(i, i + 1)
                for i in range(len(mem.units)))
    return np.array([max(width_equivalent_budget(mem, min(r, 1.0))
                         * BUDGET_SLACK, floor) for r in ratios])


def build_context(data, sim: SimConfig, *,
                  model_cfg: Optional[ResNetConfig] = None,
                  population=None) -> Context:
    """Precompute the per-experiment context for the paper's image
    protocol: ratios, byte budgets, FeDepth decompositions, MKD flags.

    With ``population=`` (a ``repro.fl.scale.population.Population``),
    the per-client arrays become LAZY hash-drawn views and ``data`` may
    be ``None`` (synthesized on demand) — nothing O(num_clients) is
    materialized; see docs/scale.md."""
    if population is not None:
        from repro.fl.scale.population import population_context
        return population_context(population, sim, model_cfg=model_cfg,
                                  data=data)
    num_clients = len(data.client_indices)
    cfg = model_cfg or ResNetConfig(num_classes=data.num_classes,
                                    image_size=data.x.shape[1])
    ratios = client_ratios(num_clients, sim.scenario, sim.seed)
    mem = resnet_memory(cfg, sim.mem_batch)
    budgets = scenario_budgets(mem, ratios)
    return Context(
        sim=sim, num_clients=num_clients, sizes=data.client_sizes(),
        rng=np.random.default_rng(sim.seed),
        key=jax.random.PRNGKey(sim.seed), model_cfg=cfg, mem=mem,
        ratios=ratios, budgets=budgets,
        decomps=[decompose(mem, int(b)) for b in budgets],
        surplus=np.where(ratios >= 2.0, 2, 1), data=data)


def default_batch_fn(ctx: Context) -> Callable[[int], list]:
    """The paper's per-round local loader: |D_k|/B fresh batches, drawn
    from the shared simulation stream.  ONE definition for every engine
    (RoundEngine and the systime engines) — the loader formula is part of
    the cross-engine equivalence contract."""
    data, sim = ctx.data, ctx.sim

    def batch_fn(k: int) -> list:
        with annotate("batch", client=k):
            return [data.client_batch(k, sim.batch_size, ctx.rng)
                    for _ in range(max(1, len(data.client_indices[k])
                                       // sim.batch_size))]
    return batch_fn


def eval_state(strategy: FLStrategy, ctx: Context, state,
               eval_fn: Optional[Callable]) -> Optional[float]:
    """Shared eval fallback chain: explicit ``eval_fn`` > the strategy's
    own eval on the context's test split > ``None`` (no eval source)."""
    if eval_fn is not None:
        return eval_fn(state)
    if ctx.data is not None:
        return strategy.eval_model(ctx, state, ctx.data.x_test,
                                   ctx.data.y_test)
    return None


def _resolve_prefix_cache(spec) -> bool:
    """"on"/"off" (or a plain bool) -> the Context's boolean flag."""
    if isinstance(spec, bool):
        return spec
    if spec not in ("on", "off"):
        raise ValueError(f"prefix_cache must be 'on' or 'off', got {spec!r}")
    return spec == "on"


def resolve_history_sink(spec, mode: str = "w") -> Tuple[object, bool]:
    """Resolve an engine's ``history_sink`` knob: ``None`` and sink
    instances pass through caller-owned; a PATH becomes an engine-owned
    ``JsonlHistorySink`` the engine closes when ``run()`` completes
    (the deterministic flush+close contract — a caller-supplied instance
    is only flushed, never closed, so it can outlive the run).  Returns
    ``(sink, engine_owns_it)``.  ``mode="a"`` appends instead of
    truncating — the checkpoint-resume path, where the stream already
    holds the pre-crash records."""
    if spec is None or hasattr(spec, "write"):
        return spec, False
    from repro.fl.scale.history import JsonlHistorySink
    return JsonlHistorySink(spec, mode=mode), True


def resolve_faults(faults, resilience):
    """Resolve the engines' ``faults=``/``resilience=`` knobs into one
    ``FaultRuntime`` (or ``None`` when both are off — the single check
    every fault-aware branch guards on, keeping ``faults=None`` bitwise
    identical to the pre-robustness engines)."""
    if faults is None and resilience is None:
        return None
    from repro.fl.faults import FaultRuntime
    return FaultRuntime(faults, resilience)


def resolve_checkpointing(every, ckpt_dir, keep, resume):
    """Resolve the engines' checkpoint/resume knobs into
    ``(EngineCheckpointer | None, resume_dir | None)``."""
    if every is not None and ckpt_dir is None:
        raise ValueError("checkpoint_every requires checkpoint_dir")
    resume_dir = None
    if resume:
        resume_dir = resume if isinstance(resume, str) else ckpt_dir
        if resume_dir is None:
            raise ValueError("resume=True requires checkpoint_dir "
                             "(or pass the directory as resume=)")
    if every is None and resume_dir is None:
        return None, None
    from repro.fl.faults import EngineCheckpointer
    ckpt = EngineCheckpointer(ckpt_dir, every, keep=keep) \
        if every is not None else None
    return ckpt, resume_dir


def load_resume(resume_dir):
    """Load the newest usable checkpoint pair from ``resume_dir`` —
    ``(round_idx, server_state, aux)`` or ``None`` (fresh start when
    the directory is empty: the very first run of a
    checkpoint-and-restart loop needs no special casing)."""
    from repro.fl.faults import EngineCheckpointer
    return EngineCheckpointer(resume_dir, every=1).load_latest()


def apply_prefix_cache(ctx: Context, spec) -> Context:
    """Resolve a ``prefix_cache`` knob onto a context.  Returns ``ctx``
    unchanged when the contract already matches, else a SHALLOW COPY
    with the flag flipped — a caller-shared context is never mutated, so
    two engines over one context keep their own execution contracts
    (rng / caches / data stay shared by reference)."""
    resolved = _resolve_prefix_cache(spec)
    if resolved == ctx.prefix_cache:
        return ctx
    return dataclasses.replace(ctx, prefix_cache=resolved)


class RoundEngine:
    """Runs communication rounds of ONE strategy over a client
    population.  Generic over the strategy, the cohort sampler, and the
    client scheduler — new methods and new scenarios never touch it."""

    def __init__(self, strategy: FLStrategy, ctx: Context, *,
                 sampler: Optional[CohortSampler] = None,
                 scheduler: Union[ClientScheduler, str, None] = None,
                 prefix_cache: str = "on",
                 codec: Union[str, object, None] = "none",
                 downlink: str = "full",
                 channel: Optional[CommChannel] = None,
                 history_sink=None, obs=None,
                 faults=None, resilience=None,
                 checkpoint_every: Optional[int] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_keep: int = 3,
                 resume: Union[bool, str, None] = None):
        """``scheduler`` is an instance or a name from
        ``repro.fl.sampling.SCHEDULERS`` ("sequential" — the default — or
        "vectorized").  The vectorized scheduler stacks clients that share
        an execution signature into single vmap dispatches; its per-group
        compiled updates live in ``ctx.caches`` so they are shared across
        rounds (see README "Choosing a scheduler").

        ``prefix_cache`` ("on", the default, or "off") selects the
        depth-wise execution contract for strategies that run
        ``core.blockwise`` updates: "on" buffers the frozen-prefix
        activation z_{lo-1} once per distinct batch per subproblem and
        advances it incrementally — the paper's prefix-once claim; "off"
        replays the prefix inside every SGD step.  Both produce the same
        aggregated params up to float tolerance (asserted in
        tests/test_prefix_cache.py; see docs/prefix_cache.md).

        ``codec`` (a name from ``repro.fl.comm.CODECS`` or a configured
        codec instance) and ``downlink`` ("full"/"sliced"/"delta")
        configure the wire: lossy uplink codecs run behind per-client
        error feedback and history switches to exact encoded bytes;
        ``codec="none"`` (default) is a strict no-op that reproduces the
        channel-free engine bitwise.  Pass a prebuilt ``channel`` to
        share/ablate one (e.g. ``CommChannel(error_feedback=False)``);
        it wins over the two knobs.  See docs/comm.md.

        ``history_sink`` (a ``repro.fl.scale.JsonlHistorySink``, or a
        PATH the engine opens one at — then owned and closed when
        ``run`` completes) streams each :class:`RoundRecord` to disk as
        it is produced instead of accumulating the in-memory list;
        ``run`` then returns an empty history (the stream IS the
        history).  Default ``None`` keeps today's list behavior.

        ``obs`` ("on"/"off"/bool, or a shared ``repro.obs.Obs``) enables
        the telemetry layer: span tracing + the metrics registry,
        activated for the dynamic extent of ``run``/``run_round`` so
        every instrumented subsystem underneath (scheduler groups, jit
        caches, the comm channel, PrefixCache, SpillStore) records into
        it.  Default off = the pre-telemetry code path, bitwise
        (docs/observability.md).

        ``faults`` (a ``repro.fl.faults.FaultPlan``) injects seeded
        client faults into every dispatch; ``resilience`` (a
        ``ResiliencePolicy``) turns on retry-with-backoff, update
        quarantine and cohort-shortfall degradation.  Both default
        ``None`` = every pre-existing code path bitwise identical.
        ``checkpoint_every``/``checkpoint_dir`` write a crash-safe
        checkpoint pair every N rounds (server state + rng/EF/history
        aux); ``resume`` (``True`` = from ``checkpoint_dir``, or an
        explicit directory) continues a killed run bitwise — see
        docs/robustness.md."""
        self.strategy = strategy
        self.ctx = apply_prefix_cache(ctx, prefix_cache)
        self.sampler = sampler or UniformSampler()
        self.scheduler = make_scheduler(scheduler)
        self.channel = channel or CommChannel(codec, downlink)
        self._faultrt = resolve_faults(faults, resilience)
        self._ckpt, self._resume_dir = resolve_checkpointing(
            checkpoint_every, checkpoint_dir, checkpoint_keep, resume)
        self.history_sink, self._owns_sink = resolve_history_sink(
            history_sink, mode="a" if self._resume_dir else "w")
        self.obs = make_obs(obs)
        if self.obs is not None:
            # attach the diagnostics layer (memory auditor / dynamics
            # analyzer) to this experiment — a no-op on plain captures
            self.obs.bind(self.ctx)

    # ------------------------------------------------------------------
    def default_batch_fn(self) -> Callable[[int], list]:
        """The paper's per-round local loader (module-level
        :func:`default_batch_fn` bound to this engine's context)."""
        return default_batch_fn(self.ctx)

    def run_round(self, state, round_idx: int,
                  batch_fn: Callable[[int], list]):
        """One communication round: broadcast (downlink accounting) ->
        sample -> local updates -> uplink encode -> decode ->
        aggregate.  Returns (new_state, up_bytes, down_bytes).

        The round runs inside a ``round`` span (the profiler's
        ``repro.round``).  With ``obs`` enabled this is also the
        telemetry activation boundary for direct callers (benchmarks
        drive ``run_round`` without ``run``): the capture is active and
        the engine's byte counters accumulate."""
        inner = self._run_round if self._faultrt is None \
            else self._run_round_resilient
        with scope(self.obs), span_if(self.obs, "round", round=round_idx,
                                      engine="round"):
            state, comm, down = inner(state, round_idx, batch_fn)
        if self.obs is None:
            return state, comm, down
        m = self.obs.metrics
        m.counter("engine_rounds", engine="round").inc()
        m.counter("engine_up_bytes", engine="round").inc(comm)
        m.counter("engine_down_bytes", engine="round").inc(down)
        return state, comm, down

    def _run_round(self, state, round_idx: int,
                   batch_fn: Callable[[int], list]):
        ctx, chan = self.ctx, self.channel
        with annotate("sample"):
            cohort = self.sampler.sample(ctx, round_idx)
        with annotate("comm"):
            down = sum(chan.downlink_bytes(self.strategy, ctx, state,
                                           int(k)) for k in cohort)
        # fused on-mesh execution+aggregation (ShardedScheduler with
        # aggregate="mesh"): only under the strict no-op codec — a lossy
        # channel needs per-client payloads on the host for
        # encode/error-feedback, the very round trip fusion removes.
        # NotImplemented falls through to the standard path (probed
        # before any batch is drawn, so the rng stream never double-
        # advances).
        fused = getattr(self.scheduler, "run_fused", None)
        if fused is not None and chan.codec.name == "none":
            out = fused(ctx, self.strategy, state, cohort, batch_fn)
            if out is not NotImplemented:
                new_state, comm = out
                return new_state, comm, down
        results = self.scheduler.run(ctx, self.strategy, state,
                                     cohort, batch_fn)
        with annotate("comm"):
            results = [chan.encode_result(self.strategy, ctx, state,
                                          int(k), r)
                       for k, r in zip(cohort, results)]
            comm = sum(r.comm_bytes if r.comm_bytes is not None
                       else wire_bytes(r.payload) for r in results)
            results = [chan.decode_result(r) for r in results]
        with span_if(self.obs, "aggregate", clients=len(results)):
            new_state = self.strategy.aggregate(ctx, state, results)
        if self.obs is not None and self.obs.dynamics is not None:
            self.obs.dynamics.record_round(
                round_idx, state, results, new_state,
                clients=[int(k) for k in cohort], engine="round")
        return new_state, comm, down

    def _run_round_resilient(self, state, round_idx: int,
                             batch_fn: Callable[[int], list]):
        """The fault-aware round (taken only when ``faults=`` or
        ``resilience=`` is set — ``_run_round`` stays the bitwise
        pre-robustness path).  Per client: local update -> fault
        resolution (payload damage / retry loop / give up) -> EF
        snapshot -> encode -> decode -> quarantine validation (rejected
        updates roll the EF residual back, so their transmitted mass is
        retransmitted later) -> aggregate the survivors.  Cohort
        shortfall is handled by the policy's degradation mode
        (docs/robustness.md §Policies); an empty surviving set leaves
        the state untouched (a no-op round, never a crash)."""
        ctx, chan, rt = self.ctx, self.channel, self._faultrt
        cohort = [int(k) for k in self.sampler.sample(ctx, round_idx)]
        target = len(cohort)
        cohort = rt.overprovision(ctx, cohort)
        down = sum(chan.downlink_bytes(self.strategy, ctx, state, k)
                   for k in cohort)
        comm = 0
        kept: List[ClientResult] = []

        def process(clients) -> int:
            nonlocal comm
            delivered = 0
            results = self.scheduler.run(ctx, self.strategy, state,
                                         clients, batch_fn)
            for k, res in zip(clients, results):
                res.client_id = k
                outcome = rt.resolve(
                    round_idx, k, res,
                    lambda k=k: self.strategy.client_update(
                        ctx, state, k, batch_fn(k)))
                if not outcome.delivered:
                    continue
                ef_snap = chan.snapshot_uplink(k)
                enc = chan.encode_result(self.strategy, ctx, state, k,
                                         outcome.result)
                up = enc.comm_bytes if enc.comm_bytes is not None \
                    else wire_bytes(enc.payload)
                dec = chan.decode_result(enc)
                verdict = rt.validate_one(dec.payload, state)
                if verdict is not None:
                    # the garbage DID cross the wire — its bytes count;
                    # its mass must not vanish from the EF residual
                    chan.rollback_uplink(k, ef_snap)
                    rt.record_quarantine(k, verdict)
                    if self.obs is not None \
                            and self.obs.dynamics is not None:
                        self.obs.dynamics.record_rejection(
                            round_idx, k, verdict.reason, engine="round")
                    comm += up
                    continue
                comm += up
                kept.append(dec)
                delivered += 1
            return delivered

        delivered = process(cohort)
        missing = target - delivered
        if missing > 0:
            rt.record_shortfall(missing)
            extra = rt.resample(ctx, cohort, missing)
            if extra:
                down += sum(chan.downlink_bytes(self.strategy, ctx,
                                                state, k) for k in extra)
                process(extra)
        if kept:
            new_state = self.strategy.aggregate(ctx, state, kept)
            if self.obs is not None and self.obs.dynamics is not None:
                self.obs.dynamics.record_round(round_idx, state, kept,
                                               new_state, engine="round")
            state = new_state
        return state, comm, down

    def run(self, *, initial_state=None,
            batch_fn: Optional[Callable[[int], list]] = None,
            eval_fn: Optional[Callable] = None,
            eval_every: int = 5) -> Tuple[object, List[RoundRecord]]:
        """Run ``sim.rounds`` rounds.  Evaluates every ``eval_every``
        rounds and always on the last; ``eval_fn(state)`` overrides the
        strategy's own eval (the generic-runner path has no test split in
        the context).  ``initial_state`` (strategy-defined state type)
        skips ``init_state`` but NOT the strategy's optional ``setup``
        hook.  Returns (final_state, history).

        History contract: one :class:`RoundRecord` per eval checkpoint
        (every ``eval_every`` rounds plus the final round), NEVER fewer —
        when no eval is possible (``ctx.data is None`` and no ``eval_fn``)
        the record is still appended with ``accuracy=None``, so
        ``seconds`` / ``comm_bytes`` accounting is complete and
        ``history[-1]`` always covers round ``sim.rounds``.  ``seconds``
        and ``comm_bytes`` accumulate since the previous record.

        With a ``history_sink``, each record streams to the sink as it
        is produced and the returned history list stays EMPTY — bounded
        memory however many rounds run (docs/scale.md §History).

        With ``resume=`` set and a usable checkpoint present, the run
        CONTINUES from it: server state, rng stream, channel state and
        history-so-far restore to the values of the checkpointed round
        and the loop picks up at the next one, reproducing the
        uninterrupted run bitwise (docs/robustness.md §Resume)."""
        ctx = self.ctx
        setup = getattr(self.strategy, "setup", None)
        if setup is not None:
            setup(ctx)
        resumed = load_resume(self._resume_dir) \
            if self._resume_dir is not None else None
        history: List[RoundRecord] = []
        start_rd, bytes_acc, down_acc = 0, 0, 0
        if resumed is not None:
            rd0, state, aux = resumed
            start_rd = rd0 + 1
            bytes_acc = int(aux.get("bytes_acc", 0))
            down_acc = int(aux.get("down_acc", 0))
            if self.history_sink is None:
                history = [RoundRecord(*r) for r in aux.get("history", [])]
            self._import_aux(aux)
        else:
            state = initial_state if initial_state is not None \
                else self.strategy.init_state(ctx)
        batch_fn = batch_fn or self.default_batch_fn()
        t_last = time.perf_counter()
        try:
            with scope(self.obs):
                for rd in range(start_rd, ctx.sim.rounds):
                    state, comm, down = self.run_round(state, rd, batch_fn)
                    bytes_acc += comm
                    down_acc += down
                    if (rd + 1) % eval_every == 0 \
                            or rd == ctx.sim.rounds - 1:
                        # eval_state keeps the record even with no
                        # eval source
                        with span_if(self.obs, "eval", round=rd + 1):
                            acc = eval_state(self.strategy, ctx, state,
                                             eval_fn)
                        now = time.perf_counter()
                        rec = RoundRecord(rd + 1, acc, now - t_last,
                                          bytes_acc, 0.0, down_acc)
                        if self.history_sink is not None:
                            self.history_sink.write(rec)
                        else:
                            history.append(rec)
                        t_last, bytes_acc, down_acc = now, 0, 0
                    if self._ckpt is not None and self._ckpt.due(rd):
                        self._ckpt.save(rd, state, self._export_aux(
                            history, bytes_acc, down_acc))
        finally:
            # deterministic completion: engine-owned (path) sinks close,
            # caller-supplied ones only flush — they may outlive the run
            if self.history_sink is not None:
                if self._owns_sink:
                    self.history_sink.close()
                elif hasattr(self.history_sink, "flush"):
                    self.history_sink.flush()
        return state, history

    # ------------------------------------------------ checkpoint/resume
    def _export_aux(self, history, bytes_acc: int, down_acc: int) -> dict:
        """Everything bitwise continuation needs beyond the server
        state itself (docs/robustness.md §Resume): the shared rng
        stream, the channel's EF residuals + downlink tracker, the
        validator's norm calibration, and the history accumulated so
        far (rows stay on disk when a sink streams them)."""
        return {
            "kind": "round",
            "rng": self.ctx.rng.bit_generator.state,
            "channel": self.channel.export_state(),
            "faultrt": self._faultrt.export_state()
            if self._faultrt is not None else None,
            "history": [list(r) for r in history]
            if self.history_sink is None else [],
            "bytes_acc": int(bytes_acc), "down_acc": int(down_acc),
        }

    def _import_aux(self, aux: dict) -> None:
        self.ctx.rng.bit_generator.state = aux["rng"]
        self.channel.import_state(aux.get("channel") or {})
        if self._faultrt is not None and aux.get("faultrt"):
            self._faultrt.import_state(aux["faultrt"])
