"""Expert-parallel MoE forward with EXPLICIT all-to-all collectives
(shard_map) — the manual-collective alternative to the GSPMD-scheduled
scatter/gather path in ``moe.forward``.

Motivation (EXPERIMENTS.md §Perf pair 1/2): GSPMD's operand-choice
heuristics cannot be steered into token-routing; this path pins the
schedule by construction:

  per device:  route local tokens to the shard owning their expert
               (all_to_all of (M, C, D) token buckets — activations, not
               weights) → local expert FFN on resident weight shards →
               all_to_all back → weighted combine.

Capacity is per (src, dst) pair: C = ceil(cf * N_loc * K / M); overflow
tokens are dropped exactly like the portable path.  Requires
E % mesh_model == 0 and x batch-sharded on "data".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import common


def _local_moe(cfg: ModelConfig, M: int, capacity_factor: float):
    E, K = cfg.num_experts, cfg.experts_per_token
    E_loc = E // M

    def fn(x, router, wg, wu, wd):
        # x: (B_loc, T, D) local tokens; wg/wu/wd: (E_loc, D, F) local experts
        B, T, D = x.shape
        N = B * T
        xt = x.reshape(N, D)
        C = max(1, int(capacity_factor * N * K / M))   # slots per dst shard

        logits = xt @ router
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        topk_p, topk_i = jax.lax.top_k(probs, K)
        topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

        flat_e = topk_i.reshape(-1)                     # (N*K,) global expert
        dst = flat_e // E_loc                           # destination shard
        e_loc = flat_e % E_loc                          # expert on that shard
        onehot_dst = jax.nn.one_hot(dst, M, dtype=jnp.int32)
        pos = ((jnp.cumsum(onehot_dst, 0) - 1) * onehot_dst).max(-1)
        keep = pos < C
        slot = jnp.where(keep, dst * C + pos, 0)

        keepf = keep[:, None].astype(xt.dtype)
        xr = jnp.repeat(xt, K, axis=0) * keepf
        send_x = jnp.zeros((M * C, D), xt.dtype).at[slot].add(xr)
        send_e = jnp.zeros((M * C,), jnp.int32).at[slot].add(
            jnp.where(keep, e_loc + 1, 0))              # 0 = empty slot

        # --- the explicit collective: token buckets to expert shards ----
        recv_x = jax.lax.all_to_all(send_x.reshape(M, C, D), "model", 0, 0,
                                    tiled=False).reshape(M * C, D)
        recv_e = jax.lax.all_to_all(send_e.reshape(M, C), "model", 0, 0,
                                    tiled=False).reshape(M * C)

        # local second-level dispatch into (E_loc, M*C) queues (every recv
        # token belongs to exactly one local expert; empty slots -> e=0
        # contribute zeros)
        valid = recv_e > 0
        eidx = jnp.maximum(recv_e - 1, 0)
        oh = jax.nn.one_hot(eidx, E_loc, dtype=recv_x.dtype) \
            * valid[:, None].astype(recv_x.dtype)       # (M*C, E_loc)
        expert_in = jnp.einsum("ne,nd->end", oh, recv_x)  # (E_loc, M*C, D)
        h = jax.nn.silu(jnp.einsum("end,edf->enf", expert_in, wg)) \
            * jnp.einsum("end,edf->enf", expert_in, wu)
        expert_out = jnp.einsum("enf,efd->end", h, wd)   # (E_loc, M*C, D)
        out_tokens = jnp.einsum("ne,end->nd", oh, expert_out)

        # --- route results back to the source shards --------------------
        back = jax.lax.all_to_all(out_tokens.reshape(M, C, D), "model",
                                  0, 0, tiled=False).reshape(M * C, D)

        gathered = back[slot] * keepf                   # (N*K, D)
        w = (topk_p.reshape(-1)).astype(xt.dtype)[:, None]
        out = (gathered * w).reshape(N, K, D).sum(1).reshape(B, T, D)

        # load-balance aux (local estimate; mean of local aux == global)
        onehot_first = jax.nn.one_hot(topk_i[..., 0], E)
        aux = E * jnp.sum(onehot_first.mean(0) * probs.mean(0))
        return out, aux.astype(jnp.float32)

    return fn


def forward_ep(p, cfg: ModelConfig, x, mesh, *,
               capacity_factor: float = 1.25):
    """Drop-in for ``moe.forward`` under an active mesh with a "model"
    axis dividing num_experts.  x must be batch-sharded on "data"."""
    M = mesh.shape["model"]
    assert cfg.num_experts % M == 0, (cfg.num_experts, M)
    fn = _local_moe(cfg, M, capacity_factor)

    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    xspec = P(data_axes if len(data_axes) > 1 else
              (data_axes[0] if data_axes else None), None, None)
    specs = dict(
        mesh=mesh,
        in_specs=(xspec, P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(xspec, P()))
    mapped = jax.shard_map(fn, check_vma=False, **specs)
    out = mapped(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out
