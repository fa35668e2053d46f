"""Shared building blocks for all model families (pure JAX, no flax).

Parameters are plain nested dicts of jnp arrays.  Repeating layers are
*stacked* on a leading axis and executed with ``jax.lax.scan`` so the HLO
stays O(1) in depth (essential for compiling 94-layer MoEs on a 512-device
host mesh).
"""
from __future__ import annotations

import contextlib
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# When True (dry-run costing mode), model depth scans unroll so XLA
# cost_analysis sees every layer body (it counts while-loop bodies once).
_SCAN_UNROLL = False


@contextlib.contextmanager
def unroll_scans():
    global _SCAN_UNROLL
    old = _SCAN_UNROLL
    _SCAN_UNROLL = True
    try:
        yield
    finally:
        _SCAN_UNROLL = old


def scan(body, init, xs, length=None):
    """jax.lax.scan that honors the costing unroll context."""
    return jax.lax.scan(body, init, xs, length=length,
                        unroll=True if _SCAN_UNROLL else 1)


# Perf knob: disable per-unit rematerialization (trades HBM for ~25% less
# backward compute — viable when the step's live set is far under HBM,
# e.g. FeDepth block steps).
_NO_REMAT = False


@contextlib.contextmanager
def disable_remat():
    global _NO_REMAT
    old = _NO_REMAT
    _NO_REMAT = True
    try:
        yield
    finally:
        _NO_REMAT = old


def maybe_checkpoint(body, remat: bool):
    return jax.checkpoint(body) if (remat and not _NO_REMAT) else body


# Weight-stationary decode (beyond-paper §Perf): at decode the batch is
# tiny and FSDP-sharded weights dominate — GSPMD's default resolves the
# batch-on-data / weight-dim-on-data conflict by ALL-GATHERING WEIGHTS
# (~100 GB/step for llama4).  This mode constrains decode activations to
# be replicated over the data axis at the matmuls (gathering ~MBs of
# activations + psum of partials instead), resharding to batch-on-data
# only around the KV-cache ops.
_WEIGHT_STATIONARY = False


@contextlib.contextmanager
def weight_stationary_decode():
    global _WEIGHT_STATIONARY
    old = _WEIGHT_STATIONARY
    _WEIGHT_STATIONARY = True
    try:
        yield
    finally:
        _WEIGHT_STATIONARY = old


def ws_replicate(x):
    """Pin x replicated (across every mesh axis) in WS-decode mode."""
    if not _WEIGHT_STATIONARY:
        return x
    return shard_hint(x, *([None] * x.ndim))


# Explicit expert-parallel all-to-all MoE (shard_map) — see moe_ep.py.
_EP_MOE = False


@contextlib.contextmanager
def ep_moe():
    global _EP_MOE
    old = _EP_MOE
    _EP_MOE = True
    try:
        yield
    finally:
        _EP_MOE = old


def ws_batch_sharded(x, bdim: int = 0):
    """Pin x's batch dim back onto 'data' in WS-decode mode."""
    if not _WEIGHT_STATIONARY:
        return x
    axes = [None] * x.ndim
    axes[bdim] = "data"
    return shard_hint(x, *axes)


def _context_mesh():
    """The mesh set by ``jax.set_mesh``, or ``None`` outside one."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def shard_hint(x, *axes):
    """with_sharding_constraint that is a no-op outside a mesh context or
    when a named axis doesn't divide the dim.  Pins GSPMD decisions for
    internals whose layout must be deterministic (MoE expert buffers)."""
    try:
        mesh = _context_mesh()
        if mesh is None:
            return x
        spec = []
        for dim, ax in zip(x.shape, axes):
            if ax is None or ax not in mesh.axis_names or                     dim % mesh.shape[ax] != 0:
                spec.append(None)
            else:
                spec.append(ax)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*spec))
    except Exception:
        return x

DEFAULT_DTYPE = jnp.float32
PARAM_SCALE = 0.02


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------
def dense_init(key, shape, scale: Optional[float] = None, dtype=DEFAULT_DTYPE):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key, shape, dtype=DEFAULT_DTYPE):
    return (jax.random.normal(key, shape) * PARAM_SCALE).astype(dtype)


def stacked(key, n: int, init_fn, *args, **kwargs):
    """Stack n independent inits on a new leading axis."""
    keys = jax.random.split(key, n)
    return jnp.stack([init_fn(k, *args, **kwargs) for k in keys])


# --------------------------------------------------------------------------
# norms / activations
# --------------------------------------------------------------------------
def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight + bias).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    g = x @ w_gate
    u = x @ w_up
    return (jax.nn.silu(g) * u) @ w_down


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, D); positions: (B, T) int32."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)                            # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,T,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, ...]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.  positions: (3, B, T) — temporal/height/
    width position ids; ``sections`` partitions the D/2 rotary frequencies
    among the three axes (sum(sections) == D/2)."""
    D = x.shape[-1]
    assert sum(sections) == D // 2, (sections, D)
    freqs = rope_freqs(D, theta)                            # (D/2,)
    # each frequency slot uses the position id of its section's axis
    axis_of_slot = jnp.concatenate([
        jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)])
    # gather per-slot positions: (B, T, D/2)
    pos_bt3 = jnp.moveaxis(positions, 0, -1).astype(jnp.float32)  # (B,T,3)
    slot_pos = jnp.take(pos_bt3, axis_of_slot, axis=-1)      # (B,T,D/2)
    angles = slot_pos * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# misc
# --------------------------------------------------------------------------
def causal_positions(batch: int, seq: int, offset: int = 0) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32) + offset,
                            (batch, seq))


def param_count(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def tree_cast(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)
