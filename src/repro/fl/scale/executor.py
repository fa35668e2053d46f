"""``ShardedScheduler`` — cohort fan-out across the mesh's data axis
(docs/scale.md §Executor).

``VectorizedScheduler`` stacks a group of clients sharing one execution
signature into a single vmap dispatch — on ONE device.
``ShardedScheduler`` is its mesh peer behind the same
``RoundEngine(scheduler=...)`` knob: the stacked client axis is split
into per-device chunks along the mesh's ``"data"`` axis, each device
runs the strategy's existing jitted group update
(:class:`~repro.fl.strategy.ShardableFLStrategy.group_update_fn` — the
very callable the vectorized path compiles) over its chunk, and the
per-client locals come back in cohort order.

**Why chunked dispatch of the SAME callable, not ``shard_map``, on the
default path.**  Wrapping the group update in ``shard_map`` re-lowers
its body inside a partitioned module, and XLA:CPU fuses that module
differently — lanes come back 1-2 ulp off the vectorized scheduler's
(measured on the skipped-prefix FeDepth decomposition).  Dispatching
the strategy's own jitted callable per device reuses the identical HLO,
so lanes are BITWISE equal to the vectorized path (asserted in
tests/test_scale.py on a forced multi-device CPU mesh) — scheduler
choice changes wall-clock, never the experiment, the same contract the
vectorized scheduler documents.  One empirical guard: XLA lowers a
SINGLETON client axis differently from any wider stack, so chunks keep
width >= 2 (widths >= 2 are mutually bit-identical; a singleton group
stays one singleton dispatch).  ``shard_map`` remains the engine of the
fused on-mesh aggregation path below, whose contract is tolerance-level
across devices.

Strategies without the shardable hooks — and groups that are too small
/ unstackable / ``None``-keyed — delegate to the vectorized scheduler's
exact fallback chain.

**On-mesh aggregation** (``aggregate="mesh"``): for masked depth-wise
strategies, the round can additionally FUSE aggregation into the mesh
dispatch — each device folds its local lanes into (masked-sum, count)
partials mirroring ``aggregation._masked_jit``'s exact op order and a
``psum`` over ``"data"`` reduces them in place, so per-client full-size
locals never round-trip through the host (uplink accounting keeps
pricing them: simulation moves the bytes it charges for, not the other
way around).  On a 1-device mesh with a single cohort group the fused
result is BITWISE equal to ``aggregate_masked`` (same fold order, psum
is identity); across devices/groups partial sums reassociate and
equality holds to float tolerance.  ``RoundEngine`` probes
``run_fused`` only under ``codec="none"`` — a lossy channel needs the
per-client payloads on the host for encode/error-feedback, which is
exactly the round trip this mode removes.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.blockwise import (batch_signature, broadcast_tree,
                                  stack_batches, stackable, unstack_tree)
from repro.fl.sampling import VectorizedScheduler
from repro.fl.strategy import ClientResult, wire_bytes
from repro.obs import active as obs_active, span_if
from repro.launch.mesh import make_data_mesh


# --------------------------------------------------------------------------
# on-mesh masked aggregation primitives
# --------------------------------------------------------------------------
# above this lane count the per-shard fold switches from the bitwise
# Python-sum (mirroring ``aggregation._masked_jit``'s op order exactly)
# to an axis reduction: a 10k-lane Python fold would explode the trace,
# and at that scale the fused path's contract is tolerance-level anyway
# (the host aggregators cannot even compile a 10k-client cohort).
FOLD_LANES_EXACT = 64


def psum_masked_partials(locals_stacked, mask, weights, axis_name="data"):
    """Per-shard masked partials, reduced across ``axis_name``.

    Inside a ``shard_map`` body: fold the local lanes of
    ``locals_stacked`` into elementwise ``num = Σ_i (w_i·m)·x_i`` and
    ``den = Σ_i w_i·m`` — for up to :data:`FOLD_LANES_EXACT` lanes the
    SAME Python-sum fold and multiply order as
    ``aggregation._masked_jit``, a stacked axis-sum beyond — then
    ``psum`` both over the mesh axis.  ``mask`` is the group's shared
    trained-mask pytree (replicated); zero-weight lanes (padding)
    contribute exact-zero terms."""
    lanes = jax.tree.leaves(locals_stacked)[0].shape[0]
    if lanes <= FOLD_LANES_EXACT:
        num = jax.tree.map(
            lambda m, x: sum((weights[i] * m) * x[i].astype(jnp.float32)
                             for i in range(lanes)),
            mask, locals_stacked)
        den = jax.tree.map(
            lambda m: sum(weights[i] * m for i in range(lanes)), mask)
    else:
        def lane_sum(m, x):
            w = weights.reshape((lanes,) + (1,) * (x.ndim - 1))
            return ((w * m) * x.astype(jnp.float32)).sum(axis=0)

        num = jax.tree.map(lambda m, x: lane_sum(m, x),
                           mask, locals_stacked)
        den = jax.tree.map(lambda m: weights.sum() * m, mask)
    return jax.lax.psum((num, den), axis_name)


@jax.jit
def _combine_partials(global_params, nums, dens):
    # mirrors _masked_jit's tail: num / max(den, 1e-12), untouched leaves
    # keep the global value.  den > 0 <=> any_trained (weights are |D_k|
    # >= 1 and masks are {0,1}), so the predicate is equivalent.
    def one(g, *nd):
        n = len(nd) // 2
        num = sum(nd[:n])
        den = sum(nd[n:])
        out = num / jnp.maximum(den, 1e-12)
        return jnp.where(den > 0, out, g.astype(jnp.float32)).astype(g.dtype)

    return jax.tree.map(one, global_params, *nums, *dens)


def mesh_aggregate_masked(global_params, partials):
    """Combine per-group ``(num, den)`` partial trees (already psummed
    on-mesh) into the next server state.  Bitwise-equal to
    ``aggregation.aggregate_masked`` for a single group on a 1-device
    mesh; float-tolerance otherwise (cross-group/device reassociation).
    """
    nums = tuple(p[0] for p in partials)
    dens = tuple(p[1] for p in partials)
    return _combine_partials(global_params, nums, dens)


@jax.jit
def _host_masked_partial(locals_, mask, w):
    """Host-side fallback partial for a group the mesh cannot stack —
    identical fold ops, so it composes with mesh partials."""
    num = jax.tree.map(
        lambda m, *xs: sum((wi * m) * x.astype(jnp.float32)
                           for wi, x in zip(w, xs)),
        mask, *locals_)
    den = jax.tree.map(lambda m: sum(wi * m for wi in w), mask)
    return num, den


# --------------------------------------------------------------------------
# the scheduler
# --------------------------------------------------------------------------
class ShardedScheduler:
    """Mesh peer of :class:`~repro.fl.sampling.VectorizedScheduler`
    (``RoundEngine(scheduler="sharded")``).

    ``mesh`` defaults to a lazily-built 1-D ``"data"`` mesh over all
    visible devices (``launch.mesh.make_data_mesh``) — lazy so that
    constructing the scheduler never initializes jax device state (the
    ``force_host_device_count`` import-order constraint).
    ``aggregate="mesh"`` opts into the fused on-mesh aggregation path
    (see module docstring); ``"host"`` (default) keeps the strategy's
    own ``aggregate`` and is bit-identical to the vectorized scheduler.

    ``max_lanes`` caps the stacked client lanes PER DEVICE in any single
    dispatch — the peak-memory knob for population-scale cohorts, where
    stacking all of a 10k-client group at once would materialize 10k
    model replicas.  Chunks beyond the device count round-robin; on the
    fused path oversized groups split into sub-dispatches whose
    (num, den) partials compose by construction.  ``None`` (default)
    keeps one chunk per device.
    """

    def __init__(self, min_group: int = 2, *, mesh=None,
                 aggregate: str = "host",
                 max_lanes: Optional[int] = None):
        if aggregate not in ("host", "mesh"):
            raise ValueError(f"aggregate must be 'host' or 'mesh', "
                             f"got {aggregate!r}")
        self.min_group = max(1, int(min_group))
        self.aggregate = aggregate
        self.max_lanes = None if max_lanes is None else max(2, int(max_lanes))
        self._mesh = mesh
        self.fallback = VectorizedScheduler(min_group)
        # device the next group's first chunk goes to: groups narrower
        # than the mesh would otherwise all land on device 0
        self._next_device = 0

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = make_data_mesh()
        return self._mesh

    # ------------------------------------------------------------ default
    def run(self, ctx, strategy, state, cohort, batch_fn):
        group_fn = getattr(strategy, "group_update_fn", None)
        group_results = getattr(strategy, "group_results", None)
        group_key = getattr(strategy, "client_group_key", None)
        if group_fn is None or group_results is None or group_key is None:
            return self.fallback.run(ctx, strategy, state, cohort, batch_fn)

        ids = [int(k) for k in cohort]
        batches = [batch_fn(k) for k in ids]   # cohort-order rng draws
        groups: dict = {}
        for pos, cid in enumerate(ids):
            groups.setdefault(group_key(ctx, cid), []).append(pos)

        results: List[Optional[ClientResult]] = [None] * len(ids)
        for key, positions in groups.items():
            group_batches = [batches[p] for p in positions]
            if (key is None or len(positions) < self.min_group
                    or not stackable(group_batches)):
                for p in positions:
                    results[p] = strategy.client_update(
                        ctx, state, ids[p], batches[p])
                continue
            gids = [ids[p] for p in positions]
            locals_ = self._run_group(ctx, strategy, state, gids,
                                      group_batches)
            for p, res in zip(positions,
                              group_results(ctx, state, gids, locals_)):
                results[p] = res
        return results

    @staticmethod
    def _chunk_widths(G: int, n_dev: int,
                      max_lanes: Optional[int] = None) -> List[int]:
        """Split a G-client group into dispatch chunks: as even as
        possible, every chunk width >= 2 (see module docstring — a
        singleton chunk lowers differently and breaks lanewise bitwise
        equality with the host reference), no padding lanes ever.  At
        most ``n_dev`` chunks unless ``max_lanes`` forces more (then the
        extras round-robin the devices)."""
        if G == 1:
            return [1]
        d = min(n_dev, G // 2) if n_dev > 1 else 1
        if max_lanes is not None:
            d = min(max(d, -(-G // max_lanes)), G // 2)
        base, extra = divmod(G, d)
        return [base + (i < extra) for i in range(d)]

    def _run_group(self, ctx, strategy, state, gids, gbatches):
        """One group's locals, fanned out chunk-per-device.  Dispatch is
        async — every device's chunk is in flight before the first
        result is unstacked.  Chunks take devices round-robin, carried
        across groups, so a round of small groups still spreads over the
        mesh.  ``fn`` donates its stacked-params argument; each chunk
        passes a fresh broadcast buffer that nothing else holds, so
        donation never invalidates a live array."""
        fn = strategy.group_update_fn(ctx, gids)
        devices = list(self.mesh.devices.flat)
        G = len(gids)
        outs = []
        start = 0
        widths = self._chunk_widths(G, len(devices), self.max_lanes)
        for w in widths:
            dev = devices[self._next_device % len(devices)]
            self._next_device += 1
            chunk = gbatches[start:start + w]
            start += w
            outs.append((w, fn(
                jax.device_put(broadcast_tree(state, w), dev),
                jax.device_put(stack_batches(chunk), dev))))
        # host aggregation jits reject mixed-device args — land every
        # chunk's locals on the mesh's first device (a transfer, never a
        # recompute: bits are preserved)
        d0 = devices[0]
        return [jax.device_put(t, d0)
                for w, out in outs for t in unstack_tree(out, w)]

    # -------------------------------------------------------------- fused
    def run_fused(self, ctx, strategy, state, cohort, batch_fn):
        """On-mesh round: local updates AND masked aggregation fused in
        the mesh dispatch.  Returns ``(new_state, comm_bytes)`` or
        ``NotImplemented`` when ineligible — probed by ``RoundEngine``
        BEFORE any batch is drawn, so a fall-through never double-draws
        from the shared rng stream.  Eligibility: ``aggregate="mesh"``,
        a shardable strategy with masked aggregation (``group_mask`` is
        non-``None``), and no sequential-only (``None``-keyed) clients.

        Uplink accounting: the fused path never materializes per-client
        payloads on the host, but each client's upload still crossed the
        simulated wire — priced as ``wire_bytes(state)`` per client,
        exact for the state-congruent full-model payloads masked
        depth-wise strategies send."""
        if self.aggregate != "mesh":
            return NotImplemented
        group_fn = getattr(strategy, "group_update_fn", None)
        mask_fn = getattr(strategy, "group_mask", None)
        group_key = getattr(strategy, "client_group_key", None)
        if group_fn is None or mask_fn is None or group_key is None:
            return NotImplemented

        ids = [int(k) for k in cohort]
        keys = {cid: group_key(ctx, cid) for cid in ids}
        if any(v is None for v in keys.values()):
            return NotImplemented
        if mask_fn(ctx, state, ids[0]) is None:   # unmasked aggregation
            return NotImplemented

        batches = [batch_fn(k) for k in ids]   # cohort-order rng draws
        groups: dict = {}
        for pos, cid in enumerate(ids):
            groups.setdefault(keys[cid], []).append(pos)

        # max_lanes bounds lanes-per-device in one dispatch, so a group
        # may split into several sub-dispatches — their (num, den)
        # partials compose exactly (the combine is a sum over partials).
        cap = (None if self.max_lanes is None
               else self.max_lanes * self.mesh.devices.size)
        partials = []
        for key, positions in groups.items():
            gids = [ids[p] for p in positions]
            gbatches = [batches[p] for p in positions]
            mask = mask_fn(ctx, state, gids[0])
            w = np.asarray([float(ctx.sizes[c]) for c in gids], np.float32)
            # population batch counts track |D_k|, so one budget group
            # holds several stackable sub-cohorts — split by per-client
            # batch signature instead of host-folding the whole group;
            # only singleton signatures stay host-side
            by_sig: dict = {}
            for i, b in enumerate(gbatches):
                by_sig.setdefault(batch_signature(b), []).append(i)
            obs = obs_active()
            for idxs in by_sig.values():
                s_ids = [gids[i] for i in idxs]
                s_b = [gbatches[i] for i in idxs]
                s_w = w[idxs]
                if len(idxs) < 2:
                    if obs is not None:
                        obs.metrics.counter("scheduler_fallback_clients",
                                            scheduler="sharded",
                                            ).inc(len(idxs))
                    partials.append(self._host_partial(
                        ctx, strategy, state, s_ids, s_b, mask, s_w))
                    continue
                step = cap or len(s_ids)
                for s in range(0, len(s_ids), step):
                    with span_if(obs, "cohort-group",
                                 size=len(s_ids[s:s + step]),
                                 signature=str(key), scheduler="sharded"):
                        partials.append(self._mesh_partial(
                            ctx, strategy, state, s_ids[s:s + step],
                            s_b[s:s + step], mask, s_w[s:s + step]))
                    if obs is not None:
                        obs.metrics.counter("group_dispatches",
                                            scheduler="sharded").inc()
        comm = len(ids) * wire_bytes(state)
        return mesh_aggregate_masked(state, partials), comm

    def _mesh_partial(self, ctx, strategy, state, gids, gbatches, mask, w):
        fn = strategy.group_update_fn(ctx, gids)
        mesh = self.mesh
        n_dev = mesh.devices.size
        G = len(gids)
        pad = (-G) % n_dev
        padded = gbatches + [gbatches[-1]] * pad
        w_pad = jnp.asarray(np.concatenate([w, np.zeros(pad, np.float32)]))
        cache = ctx.caches.setdefault("sharded_dispatch", {})
        key = ("psum", fn, mesh)
        if key not in cache:
            def body(p_stack, b_stack, w_stack, mask_):
                locals_ = fn(p_stack, b_stack)
                return psum_masked_partials(locals_, mask_, w_stack)

            cache[key] = jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(P("data"), P("data"), P("data"), P()),
                out_specs=P()))
        spec = NamedSharding(mesh, P("data"))
        return cache[key](
            jax.device_put(broadcast_tree(state, G + pad), spec),
            jax.device_put(stack_batches(padded), spec), w_pad, mask)

    def _host_partial(self, ctx, strategy, state, gids, gbatches, mask, w):
        """Unstackable group: per-client sequential updates, host fold
        with the same ops — composes with the mesh partials.  The fold
        jits in chunks of ``FOLD_LANES_EXACT`` clients: one giant
        Python-sum over a 10k cohort would explode the trace (the very
        failure mode the fused path exists to avoid)."""
        locals_ = []
        for cid, b in zip(gids, gbatches):
            res = strategy.client_update(ctx, state, cid, b)
            payload = res.payload
            locals_.append(payload[0] if isinstance(payload, tuple)
                           else payload)
        num = den = None
        for s in range(0, len(locals_), FOLD_LANES_EXACT):
            n_, d_ = _host_masked_partial(
                tuple(locals_[s:s + FOLD_LANES_EXACT]), mask,
                jnp.asarray(w[s:s + FOLD_LANES_EXACT]))
            if num is None:
                num, den = n_, d_
            else:
                num = jax.tree.map(jnp.add, num, n_)
                den = jax.tree.map(jnp.add, den, d_)
        return num, den
