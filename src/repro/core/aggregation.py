"""Server-side aggregation (paper Algorithm 1, line 7).

FeDepth clients return FULL-SIZE models, so aggregation is plain weighted
FedAvg over the sampled cohort — this is exactly the paper's robustness
argument (contribution 3): no width-matching, no nested slicing, no
dependence on the largest-memory clients being present.

Partial-training clients (paper §Extreme Memory) never touched their
skipped prefix: their returned prefix equals the broadcast global prefix,
so plain averaging silently no-ops those coordinates for them; we also
provide ``aggregate_masked`` that reweights per-parameter by who actually
trained it (a beyond-paper refinement, off by default to stay faithful).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.obs import active as _obs_active, annotate as _annotate

# NOTE on buffer donation (core/jit_utils.py): the aggregation jits are
# deliberately NOT donated.  Client payloads are not private buffers:
# partial-training FeDepth clients pass the untouched prefix through
# ``merge`` BY REFERENCE (the same Array objects as the server state the
# round was broadcast from), async FedBuff merges retain payloads whose
# leaves alias an OLDER state across aggregation calls, and the async
# anchor paths put the live state itself into the client-tree tuple.
# Donating any of those invalidates a buffer someone still holds
# (gpu/tpu raises "Array has been deleted").  The hot-path donation win
# lives where buffers are private BY CONSTRUCTION: the per-step
# (train, vel) carries and the broadcast stacked params of the group
# updates (see core/blockwise.py and docs/prefix_cache.md).


@jax.jit
def _fedavg_jit(trees, w):
    # jit's own cache keys on the pytree structure (cohort size included),
    # so varying cohorts re-specialize without evicting older compiles
    w = w / w.sum()
    return jax.tree.map(
        lambda *xs: sum(wi * x.astype(jnp.float32)
                        for wi, x in zip(w, xs)).astype(xs[0].dtype),
        *trees)


def _decoded(client_params: Sequence) -> tuple:
    """Decode-at-aggregate: accept wire-encoded client payloads (any
    object exposing ``.decode()`` — ``repro.fl.comm.WireUpdate``) next
    to plain pytrees, so callers outside the engines can hand codec
    outputs straight to the aggregators.  The engines normally decode
    just before invoking the strategy, making this a no-op there.
    Duck-typed on purpose: core must not import the fl layer."""
    return tuple(p.decode() if hasattr(p, "decode") else p
                 for p in client_params)


@jax.jit
def _all_finite_jit(tree):
    flags = [jnp.all(jnp.isfinite(leaf)) for leaf in jax.tree.leaves(tree)
             if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)]
    if not flags:
        return jnp.bool_(True)
    return jnp.all(jnp.stack(flags))


def _finite_filter(client_params: tuple, *aligned: Sequence):
    """The default non-finite guard at the aggregate boundary: one
    NaN/Inf client payload used to poison the whole round's average
    (NaN propagates through the weighted sum into every coordinate of
    the new server state — from which no later round recovers).  Drop
    non-finite payloads, keeping ``aligned`` sequences (weights, masks)
    in step; when EVERY payload is non-finite the full set passes
    through unchanged (nothing sane to average — the caller sees the
    legacy behavior).  One jitted finiteness reduction per client; the
    all-finite path returns the inputs untouched, so healthy rounds are
    bitwise identical to the unguarded aggregator."""
    with _annotate("aggregate.finite", clients=len(client_params)):
        flags = [bool(_all_finite_jit(p)) for p in client_params]
    if all(flags):
        return (client_params,) + aligned
    obs = _obs_active()
    if obs is not None:
        obs.metrics.counter("aggregate_nonfinite_dropped").inc(
            sum(1 for f in flags if not f))
    keep = [i for i, f in enumerate(flags) if f]
    if not keep:
        return (client_params,) + aligned
    return tuple(tuple(seq[i] for i in keep)
                 for seq in (client_params,) + tuple(aligned))


def fedavg(client_params: Sequence, weights: Sequence[float],
           guard: bool = True):
    """Weighted average of client pytrees.  weights ~ p_k, renormalized
    over the sampled cohort.  Jitted: the whole tree-wide weighted sum is
    one dispatch, not one per (leaf, client).  Accepts wire-encoded
    payloads (see :func:`_decoded`).  ``guard`` (default on) drops
    non-finite client payloads before averaging (:func:`_finite_filter`
    — a single diverged client no longer poisons the round)."""
    params = _decoded(client_params)
    weights = tuple(weights)
    if guard:
        params, weights = _finite_filter(params, weights)
    return _fedavg_jit(params, jnp.asarray(weights, jnp.float32))


def fedavg_delta(global_params, client_params: Sequence,
                 weights: Sequence[float], server_lr: float = 1.0):
    """Server update in delta form (supports server learning rates /
    FedAdam-style extensions): W <- W + lr * avg(W_k - W)."""
    avg = fedavg(client_params, weights)
    return jax.tree.map(
        lambda g, a: (g.astype(jnp.float32)
                      + server_lr * (a.astype(jnp.float32)
                                     - g.astype(jnp.float32))).astype(g.dtype),
        global_params, avg)


@jax.jit
def _masked_jit(global_params, trees, masks, w):
    # not donated — see the module NOTE on buffer donation
    n = len(trees)                      # static at trace time

    def combine(g, *pairs):
        xs = pairs[:n]
        ms = pairs[n:]
        num = sum(wi * mi * x.astype(jnp.float32)
                  for wi, x, mi in zip(w, xs, ms))
        den = sum(wi * mi for wi, mi in zip(w, ms))
        den = jnp.maximum(den, 1e-12)
        out = num / den
        any_trained = sum(ms) > 0
        return jnp.where(any_trained, out,
                         g.astype(jnp.float32)).astype(g.dtype)

    return jax.tree.map(combine, global_params, *trees, *masks)


def aggregate_masked(global_params, client_params: Sequence,
                     weights: Sequence[float],
                     trained_masks: Sequence,
                     guard: bool = True) -> object:
    """Per-parameter reweighting by who actually trained each leaf.

    ``trained_masks[k]`` is a pytree of {0,1} scalars (or arrays) marking
    which leaves client k trained (partial-training clients skip a
    prefix).  Leaves nobody trained keep the global value.  Jitted (one
    dispatch per round).  Accepts wire-encoded payloads (see
    :func:`_decoded`).  ``guard`` (default on) drops non-finite client
    payloads — with their weights and masks — before merging
    (:func:`_finite_filter`).
    """
    params = _decoded(client_params)
    weights, masks = tuple(weights), tuple(trained_masks)
    if guard:
        params, weights, masks = _finite_filter(params, weights, masks)
    return _masked_jit(global_params, params, masks,
                       jnp.asarray(weights, jnp.float32))


def trained_mask_for(params, dec, runner) -> object:
    """Mask pytree: 1 for leaves in any trained block of ``dec``, plus the
    head; 0 for the skipped prefix."""
    mask = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    for (lo, hi) in dec.blocks:
        train = runner.split(mask, lo, hi)
        ones = jax.tree.map(jnp.ones_like, train)
        mask = runner.merge(mask, ones, lo=lo, hi=hi)
    return mask
