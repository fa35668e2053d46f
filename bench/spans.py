"""Thin wrappers that record host spans around the harness's calls into
each layer of the program, as ``jax.profiler.TraceAnnotation``s on the
profiler's own clock.  Used in traced runs only.  Every attribute the
wrappers do not span is forwarded, so the engine and the scheduler see
the same strategy and sampler."""
from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation

STRATEGY_SPANS = {"client_update": "bench.client_update",
                  "client_update_batched": "bench.client_update",
                  "aggregate": "bench.aggregate"}


def spanned(fn, name: str):
    @functools.wraps(fn)
    def call(*args, **kwargs):
        with TraceAnnotation(name):
            return fn(*args, **kwargs)
    return call


class SpannedStrategy:
    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        span = STRATEGY_SPANS.get(name)
        return spanned(attr, span) if span else attr


class SpannedSampler:
    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def sample(self, ctx, round_idx):
        with TraceAnnotation("bench.sample"):
            return self._inner.sample(ctx, round_idx)
