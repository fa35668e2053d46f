"""FeDepth / m-FeDepth (paper Algorithm 1) as an FLStrategy.

Composes: memory model -> per-client decomposition (precomputed in the
engine context) -> depth-wise sequential ClientUpdate -> plain FedAvg.
Variants:
  * ``head="skip"``  -> FeDepth   (skip-connection classifier)
  * ``head="aux"``   -> m-FeDepth (auxiliary classifiers)
  * surplus clients (r >= 2)      -> MKD local update (core.mkd)
  * clients below the finest block -> partial training (skip prefix)

The same class backs BOTH the registered image-protocol strategies and
``core.fedepth.FedepthServer``'s model-agnostic path: pass an explicit
``runner`` (any BlockRunner) to bypass the ResNet defaults, optional
``mkd_fns=(logits_fn, task_loss_fn)`` for surplus clients, and
``masked_aggregation=True`` for the beyond-paper per-leaf reweighting.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import aggregation, blockwise, mkd
from repro.core.blockwise import BlockRunner
from repro.fl.baselines import _ce
from repro.fl.comm.payload import WireSpec
from repro.fl.registry import register
from repro.fl.strategy import ClientResult, wire_bytes
from repro.fl.strategies import common
from repro.models import resnet
from repro.obs import annotate


@register("fedepth")
class FedepthStrategy:
    def __init__(self, head: str = "skip", *,
                 runner: Optional[BlockRunner] = None,
                 mkd_fns: Optional[Tuple[Callable, Callable]] = None,
                 masked_aggregation: bool = False, prox_mu: float = 0.0):
        self.head = head
        self.runner = runner
        self.mkd_fns = mkd_fns
        self.masked_aggregation = masked_aggregation
        self.prox_mu = prox_mu

    def setup(self, ctx):
        if self.runner is None:
            if isinstance(ctx.model_cfg, ModelConfig):
                from repro.models import build
                self.runner = blockwise.lm_runner(
                    build(ctx.model_cfg), head=self.head,
                    kernel_force=ctx.kernel_force)
            else:
                self.runner = blockwise.resnet_runner(ctx.model_cfg,
                                                      head=self.head)

    def init_state(self, ctx):
        if isinstance(ctx.model_cfg, ModelConfig):
            from repro.models import build
            lm = build(ctx.model_cfg)
            params = lm.init(ctx.key)
            if self.head == "aux":
                # m-FeDepth on LM families: per-block auxiliary rms-norm
                # scales feeding the shared head (blockwise.lm_runner's
                # head_loss selects aux_norms[block_idx])
                params["aux_norms"] = jnp.ones(
                    (lm.num_depth_units, ctx.model_cfg.d_model),
                    jnp.float32)
            return params
        params = resnet.init(ctx.key, ctx.model_cfg)
        if self.head == "aux":
            params["aux_heads"] = init_aux_heads(ctx.model_cfg, ctx.key)
        return params

    def _mkd_available(self, ctx) -> bool:
        """A surplus client needs an MKD implementation to exploit M > 1:
        explicit ``mkd_fns`` (generic runner) or the jitted ResNet path.
        LM configs have neither (the jitted path applies ``resnet.apply``
        to image batches), so they degrade to the plain depth-wise
        update — never silently mis-routed."""
        return (self.mkd_fns is not None
                or (ctx.model_cfg is not None
                    and not isinstance(ctx.model_cfg, ModelConfig)))

    def client_update(self, ctx, state, client_id, batches):
        M = 1 if ctx.surplus is None else int(ctx.surplus[client_id])
        if M > 1 and self._mkd_available(ctx):
            local = self._mkd_update(ctx, state, batches, M)
        else:
            local = blockwise.client_update(
                self.runner, state, ctx.decomps[client_id], batches,
                lr=ctx.sim.lr, momentum=ctx.sim.momentum,
                local_steps=ctx.sim.local_steps, prox_mu=self.prox_mu,
                step_cache=ctx.caches.setdefault("fedepth_step", {}),
                prefix_cache=ctx.prefix_cache)
        with annotate("payload"):
            result = ClientResult(local, float(ctx.sizes[client_id]))
            if self.masked_aggregation:
                mask = aggregation.trained_mask_for(
                    state, ctx.decomps[client_id], self.runner)
                # only the trained model crosses the wire; the mask is
                # derivable server-side from the client's decomposition
                result.payload = (local, mask)
                result.comm_bytes = wire_bytes(local)
        return result

    # ---------------------------------------------- batched capability
    def client_group_key(self, ctx, client_id):
        """Clients sharing a decomposition run the same depth-wise
        computation and stack; MKD surplus clients (M > 1 with an MKD
        implementation available) keep the sequential path."""
        M = 1 if ctx.surplus is None else int(ctx.surplus[client_id])
        if M > 1 and self._mkd_available(ctx):
            return None
        dec = ctx.decomps[client_id]
        return (dec.blocks, dec.skipped_prefix)

    def client_update_batched(self, ctx, state, client_ids,
                              batches_per_client):
        """One vmap+scan dispatch for the whole group (partial-training
        prefix skips and aux heads ride along: both live in the shared
        decomposition / param tree, not in per-client control flow)."""
        update = self.group_update_fn(ctx, client_ids)
        group = len(batches_per_client)
        locals_ = blockwise.unstack_tree(
            update(blockwise.broadcast_tree(state, group),
                   blockwise.stack_batches(batches_per_client)), group)
        return self.group_results(ctx, state, client_ids, locals_)

    # --------------------------------------------- shardable capability
    def group_update_fn(self, ctx, client_ids):
        """The cached jitted group update for this group's shared
        decomposition — the same callable ``client_update_batched``
        dispatches, handed to mesh executors for ``shard_map`` wrapping
        (``ShardableFLStrategy``)."""
        return blockwise.group_update_for(
            self.runner, ctx.decomps[client_ids[0]], lr=ctx.sim.lr,
            momentum=ctx.sim.momentum, local_steps=ctx.sim.local_steps,
            prox_mu=self.prox_mu,
            step_cache=ctx.caches.setdefault("fedepth_group_step", {}),
            prefix_cache=ctx.prefix_cache)

    def group_results(self, ctx, state, client_ids, locals_):
        """Result shaping for a group's updated trees (the other half of
        ``client_update_batched``): weight ~ |D_k|; under masked
        aggregation the shared trained-mask rides in the payload and the
        wire is priced as the trained model alone."""
        mask = self.group_mask(ctx, state, client_ids[0])
        results = []
        for cid, local in zip(client_ids, locals_):
            res = ClientResult(local, float(ctx.sizes[cid]))
            if self.masked_aggregation:
                res.payload = (local, mask)
                res.comm_bytes = wire_bytes(local)
            results.append(res)
        return results

    def group_mask(self, ctx, state, client_id):
        """Trained-mask for the client's decomposition under masked
        aggregation (cached per decomposition signature — the mask
        depends only on it), ``None`` when aggregating unmasked."""
        if not self.masked_aggregation:
            return None
        dec = ctx.decomps[client_id]
        cache = ctx.caches.setdefault("fedepth_group_masks", {})
        key = (dec.blocks, dec.skipped_prefix)
        if key not in cache:
            cache[key] = aggregation.trained_mask_for(state, dec,
                                                      self.runner)
        return cache[key]

    # ------------------------------------------------- wire contract
    def wire_parts(self, ctx, state, result):
        """Lossy uplink codecs encode the client's DELTA against the
        broadcast state: a partial-training client's untouched prefix
        and an MKD client's carried leaves delta to exact zeros, which
        sparsifying codecs then skip for free.  Under masked
        aggregation the trained-mask aux rides along unencoded (it is
        server-derivable from the client's decomposition)."""
        if self.masked_aggregation:
            local, tm = result.payload
            return WireSpec(local, ref=state,
                            rebuild=lambda t, _tm=tm: (t, _tm))
        return WireSpec(result.payload, ref=state)

    def downlink_tree(self, ctx, state, client_id):
        """Depth-wise downlink slice.  Subproblem j needs only
        ``embed + units[0, hi_j) + head``, so a round's staged downloads
        TELESCOPE to ``embed + units[0, hi_last) + head`` — and FeDepth
        decompositions always cover to the last unit (partial-training
        clients still forward through their skipped prefix), so the
        union is the full model.  FeDepth's downlink savings therefore
        come from the channel's "delta" mode: repeat participants
        receive only the coordinates that changed since their last-seen
        version.  Fixed-depth prefixes DO slice — see
        ``DepthFLStrategy.downlink_tree``."""
        return state

    def aggregate(self, ctx, state, results):
        ws = [r.weight for r in results]
        if self.masked_aggregation:
            return aggregation.aggregate_masked(
                state, [r.payload[0] for r in results], ws,
                [r.payload[1] for r in results])
        return aggregation.fedavg([r.payload for r in results], ws)

    def aggregate_async(self, ctx, state, results, stalenesses, *,
                        alpha=0.5):
        """PER-BLOCK staleness merge: a FeDepth payload is a full model,
        but only the leaves inside the client's trained blocks carry
        fresh gradient information — the rest is the stale broadcast copy
        riding along.  Discount the two differently via soft masks:
        trained leaves by ``s(tau_k)``, carried leaves by ``s(2 tau_k)``
        (the raw copy is charged double — it IS the stale params, not an
        update computed on them; under ``masked_aggregation`` carried
        leaves are excluded outright, matching the sync path).  The lost
        weight mass anchors on the current global params.  All-zero
        staleness reduces every factor to 1 (or the binary mask) and the
        anchor to 0 — i.e. exactly ``aggregate``, to float tolerance.

        Falls back to the weight-discount default when results carry no
        ``client_id`` / the context has no decompositions."""
        from repro.fl.systime.staleness import (default_aggregate_async,
                                                polynomial_discount)
        if ctx.decomps is None or any(r.client_id is None for r in results):
            return default_aggregate_async(self, ctx, state, results,
                                           stalenesses, alpha=alpha)
        mask_cache = ctx.caches.setdefault("fedepth_async_masks", {})
        locals_, masks, weights = [], [], []
        anchor = 0.0
        for r, tau in zip(results, stalenesses):
            s = polynomial_discount(tau, alpha)
            if self.masked_aggregation:
                local, tm = r.payload
                soft = jax.tree.map(lambda m, _s=s: m * _s, tm)
            else:
                local = r.payload
                dec = ctx.decomps[r.client_id]
                key = (dec.blocks, dec.skipped_prefix)
                if key not in mask_cache:   # mask depends only on dec
                    mask_cache[key] = aggregation.trained_mask_for(
                        state, dec, self.runner)
                tm = mask_cache[key]
                s2 = polynomial_discount(2 * tau, alpha)
                soft = jax.tree.map(
                    lambda m, _s=s, _s2=s2: m * _s + (1.0 - m) * _s2, tm)
            locals_.append(local)
            masks.append(soft)
            weights.append(r.weight)
            anchor += r.weight * (1.0 - s)
        if anchor > 0.0:
            # the live state rides in the client-tree tuple — one reason
            # aggregation inputs are never donated (core/aggregation.py)
            locals_.append(state)
            masks.append(jax.tree.map(jnp.ones_like, state))
            weights.append(anchor)
        return aggregation.aggregate_masked(state, locals_, weights, masks)

    def eval_model(self, ctx, state, x, y):
        if isinstance(ctx.model_cfg, ModelConfig):
            return common.lm_accuracy(ctx.model_cfg, state, x, y,
                                      kernel_force=ctx.kernel_force)
        return common.resnet_accuracy(ctx.model_cfg, state, x, y)

    # ---------------------------------------------------------- MKD local
    def _mkd_update(self, ctx, state, batches, M: int):
        """Surplus clients train M models with mutual KD and upload one."""
        if self.mkd_fns is not None:       # model-agnostic (server) path
            logits_fn, task_fn = self.mkd_fns
            plist = mkd.mkd_local_update(
                logits_fn, task_fn, [state] * M, batches, lr=ctx.sim.lr,
                momentum=ctx.sim.momentum, local_steps=ctx.sim.local_steps)
            return plist[0]
        # jitted ResNet path (aux heads ride along untouched)
        model_params = {k: v for k, v in state.items() if k != "aux_heads"}
        step = _mkd_step(ctx.model_cfg, M, ctx.sim.lr, ctx.sim.momentum)
        plist = [model_params] * M
        vels = jax.tree.map(jnp.zeros_like, plist)
        for _ in range(ctx.sim.local_steps):
            for b in batches:
                plist, vels = step(plist, vels, b)
        out = dict(state)
        out.update(plist[0])
        return out


register("m-fedepth")(functools.partial(FedepthStrategy, head="aux"))


def init_aux_heads(cfg, key):
    """m-FeDepth: one tiny linear classifier per block exit."""
    from repro.models.resnet import block_channels
    aux = {}
    for i, (cin, cout, _) in enumerate(block_channels(cfg)):
        k = jax.random.fold_in(key, 100 + i)
        aux[f"b{i}"] = {
            "w": (jax.random.normal(k, (cout, cfg.num_classes))
                  / np.sqrt(cout)).astype(jnp.float32),
            "b": jnp.zeros((cfg.num_classes,), jnp.float32)}
    return aux


@functools.lru_cache(maxsize=16)
def _mkd_step(cfg, M: int, lr: float, momentum: float):
    def logits_fn(p, b):
        return resnet.apply(p, cfg, b["images"])

    def task_fn(p, b):
        return _ce(logits_fn(p, b), b["labels"])

    def loss(plist, batch):
        return mkd.mkd_loss(logits_fn, plist, batch, task_fn)

    # its own program name, ``jit_mkd_step``: ``jit_step`` is the
    # buffered block step alone
    @jax.jit
    def mkd_step(plist, vels, batch):
        grads = jax.grad(loss)(plist, batch)
        vels = jax.tree.map(lambda v, g: momentum * v + g, vels, grads)
        plist = jax.tree.map(lambda p, v: p - lr * v, plist, vels)
        return plist, vels

    return mkd_step
