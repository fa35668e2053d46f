"""Pluggable cohort samplers and client schedulers for the round engine.

Scenario diversity (client availability traces, stragglers, future
batched/async execution) lives HERE, decoupled from method code: a new
deployment scenario swaps a sampler/scheduler, never a strategy.

``UniformSampler`` reproduces the paper's protocol (participation-fraction
uniform without replacement).  ``AvailabilityTraceSampler`` and
``StragglerSampler`` are the first scenario extensions: minimal but
functional implementations with tests, ready to grow into trace-driven
simulations.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Protocol, Sequence

import numpy as np

from repro.fl.strategy import ClientResult, Context, FLStrategy
from repro.obs import active as obs_active, span_if


class CohortSampler(Protocol):
    def sample(self, ctx: Context, round_idx: int) -> np.ndarray:
        """Client ids participating in ``round_idx``."""
        ...


def _cohort_size(ctx: Context, population: int) -> int:
    k = max(1, int(np.ceil(ctx.sim.participation * ctx.num_clients)))
    return min(k, population)


class UniformSampler:
    """The paper's sampler: ceil(participation * N) uniform w/o
    replacement, drawn from the shared simulation stream."""

    def sample(self, ctx: Context, round_idx: int) -> np.ndarray:
        k = _cohort_size(ctx, ctx.num_clients)
        return ctx.rng.choice(ctx.num_clients, size=k, replace=False)


class AvailabilityTraceSampler:
    """Sample only among clients listed available for the round.

    ``trace`` is a sequence of per-round available-id collections, cycled
    when rounds outrun the trace (device up/down patterns repeat).  An
    empty round falls back to the full population rather than stalling.
    """

    def __init__(self, trace: Sequence[Sequence[int]]):
        if not len(trace):
            raise ValueError("availability trace must cover >= 1 round")
        self.trace = [np.asarray(t, dtype=np.int64) for t in trace]

    def sample(self, ctx: Context, round_idx: int) -> np.ndarray:
        avail = self.trace[round_idx % len(self.trace)]
        if avail.size == 0:
            avail = np.arange(ctx.num_clients)
        k = _cohort_size(ctx, len(avail))
        return ctx.rng.choice(avail, size=k, replace=False)


class StragglerSampler:
    """Wrap another sampler and drop each selected client with probability
    ``drop_prob`` (device went slow/offline after selection), always
    keeping at least one so the round makes progress."""

    def __init__(self, drop_prob: float = 0.3,
                 base: Optional[CohortSampler] = None):
        if not 0.0 <= drop_prob < 1.0:
            raise ValueError("drop_prob must be in [0, 1)")
        self.drop_prob = drop_prob
        self.base = base or UniformSampler()

    def sample(self, ctx: Context, round_idx: int) -> np.ndarray:
        cohort = np.asarray(self.base.sample(ctx, round_idx))
        keep = ctx.rng.random(len(cohort)) >= self.drop_prob
        if not keep.any():
            keep[int(ctx.rng.integers(len(cohort)))] = True
        return cohort[keep]


class ClientScheduler(Protocol):
    def run(self, ctx: Context, strategy: FLStrategy, state,
            cohort: Sequence[int],
            batch_fn: Callable[[int], list]) -> List[ClientResult]:
        """Execute the cohort's local updates, in scheduler-defined
        order/parallelism, returning one ClientResult per client."""
        ...


class SequentialScheduler:
    """Run clients one after another: one ``client_update`` (and thus one
    chain of jit dispatches) per client.  The reference execution model —
    always correct, never fast."""

    def run(self, ctx, strategy, state, cohort, batch_fn):
        obs = obs_active()
        results = []
        for k in cohort:
            with span_if(obs, "client-update", client=int(k)):
                results.append(strategy.client_update(ctx, state, int(k),
                                                      batch_fn(int(k))))
        return results


class VectorizedScheduler:
    """Stack clients that run the SAME computation and execute each group
    as one vmap-over-clients update (see ``docs/architecture.md``).

    Grouping key = the strategy's ``client_group_key`` (e.g. FeDepth's
    decomposition signature + surplus/MKD flag).  A group goes through the
    strategy's ``client_update_batched`` when it has at least ``min_group``
    clients, a non-``None`` key, and stackable batch lists (equal count /
    shapes / dtypes); otherwise those clients fall back to the sequential
    per-client path.  Strategies without the
    :class:`repro.fl.strategy.BatchableFLStrategy` hooks are delegated to
    :class:`SequentialScheduler` wholesale, preserving their exact
    rng-draw interleaving (splitmix draws from ``ctx.rng`` inside
    ``client_update``).

    Determinism contract: every client's batches are drawn up-front in
    cohort order, so the shared simulation stream advances exactly as
    under the sequential scheduler and results are returned in cohort
    order — scheduler choice changes wall-clock, not the experiment.
    """

    def __init__(self, min_group: int = 2):
        self.min_group = max(1, int(min_group))
        self.fallback = SequentialScheduler()

    def run(self, ctx, strategy, state, cohort, batch_fn):
        update_batched = getattr(strategy, "client_update_batched", None)
        group_key = getattr(strategy, "client_group_key", None)
        if update_batched is None or group_key is None:
            return self.fallback.run(ctx, strategy, state, cohort, batch_fn)

        from repro.core.blockwise import stackable

        ids = [int(k) for k in cohort]
        batches = [batch_fn(k) for k in ids]       # cohort-order rng draws
        groups: dict = {}
        for pos, cid in enumerate(ids):
            groups.setdefault(group_key(ctx, cid), []).append(pos)

        obs = obs_active()
        results: List[Optional[ClientResult]] = [None] * len(ids)
        for key, positions in groups.items():
            group_batches = [batches[p] for p in positions]
            if (key is None or len(positions) < self.min_group
                    or not stackable(group_batches)):
                for p in positions:
                    with span_if(obs, "client-update", client=ids[p],
                                 fallback=True):
                        results[p] = strategy.client_update(
                            ctx, state, ids[p], batches[p])
                if obs is not None:
                    obs.metrics.counter("scheduler_fallback_clients",
                                        scheduler="vectorized",
                                        ).inc(len(positions))
                continue
            # one span per stacked vmap dispatch: the host's enqueue,
            # not the group's device time, which the profiler's trace
            # holds under the group update's program
            with span_if(obs, "cohort-group", size=len(positions),
                         signature=str(key)):
                outs = update_batched(ctx, state,
                                      [ids[p] for p in positions],
                                      group_batches)
            if obs is not None:
                obs.metrics.counter("group_dispatches",
                                    scheduler="vectorized").inc()
                obs.metrics.counter("group_clients",
                                    scheduler="vectorized",
                                    ).inc(len(positions))
            for p, res in zip(positions, outs):
                results[p] = res
        return results


# "module:Class" string entries resolve lazily in make_scheduler — the
# sharded scheduler lives in fl/scale (which imports this module), so a
# direct class reference here would be a circular import
SCHEDULERS = {
    "sequential": SequentialScheduler,
    "vectorized": VectorizedScheduler,
    "sharded": "repro.fl.scale.executor:ShardedScheduler",
}


def make_scheduler(spec=None) -> ClientScheduler:
    """Resolve a scheduler spec: ``None`` -> sequential default, a name
    from ``SCHEDULERS`` ("sequential", "vectorized", "sharded"), or a
    ready instance passed through."""
    if spec is None:
        return SequentialScheduler()
    if isinstance(spec, str):
        if spec not in SCHEDULERS:
            raise ValueError(f"unknown scheduler {spec!r}; "
                             f"available: {sorted(SCHEDULERS)}")
        entry = SCHEDULERS[spec]
        if isinstance(entry, str):
            import importlib
            mod, _, cls = entry.partition(":")
            entry = getattr(importlib.import_module(mod), cls)
            SCHEDULERS[spec] = entry
        return entry()
    return spec
