"""RWKV6 (Finch) WKV recurrence as a Pallas TPU kernel.

TPU-native design: the recurrence S_t = diag(d_t) S_{t-1} + k_t v_t^T has a
per-head (D x D) fp32 state that lives in VMEM scratch for the whole
sequence; the grid is (batch*heads, T/block_t) with the time axis as the
sequential ("arbitrary") innermost dimension, so r/k/v/w tiles of shape
(block_t, D) are staged HBM->VMEM once per chunk and the state never
round-trips to HBM.  With D=64 the state is 16 KB — the VMEM working set is
4*block_t*D*4B + 16KB, far under the 16 MB/core budget even at block_t=512.

The in-chunk step is elementwise VPU work (outer product + decay) plus a
(1,D)x(D,D) matvec; a fully-parallel chunked formulation (cumprod-of-decay
attention form) trades numerical safety for MXU utilization — we keep the
numerically-exact sequential-in-chunk form as the shipped kernel and note
the chunked variant in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                  y_ref, sT_ref, state_ref, *, block_t, seq_len):
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        state_ref[...] = s0_ref[0]

    u = u_ref[0].astype(jnp.float32)        # (1, D)

    # rows are read from and written to the refs one time step at a
    # time: Mosaic slices refs at a dynamic sublane offset, but not values
    def step(i, carry):
        t_global = ti * block_t + i
        r_t = r_ref[0, pl.ds(i, 1), :].astype(jnp.float32)   # (1, D)
        k_t = k_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        v_t = v_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        d_t = jnp.exp(-jnp.exp(w_ref[0, pl.ds(i, 1), :].astype(jnp.float32)))
        S = state_ref[...]                                   # (D, D)
        kv = k_t.T @ v_t                                     # (D, D) outer
        y_t = r_t @ (S + u.T * kv)                           # (1, D)
        # ragged tail: don't advance state past seq_len
        advance = t_global < seq_len
        state_ref[...] = jnp.where(advance, d_t.T * S + kv, S)
        y_ref[0, pl.ds(i, 1), :] = y_t.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_t, step, 0)

    @pl.when(ti == nt - 1)
    def _emit_state():
        sT_ref[0] = state_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_scan(
    r: jax.Array,   # (B, T, H, D)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,   # decay logits; decay = exp(-exp(w))
    u: jax.Array,   # (H, D)
    initial_state: jax.Array | None = None,   # (B, H, D, D) fp32
    *,
    block_t: int = 128,
    interpret: bool = False,
):
    """Returns (y: (B,T,H,D) in r.dtype, final_state: (B,H,D,D) fp32)."""
    B, T, H, D = r.shape
    BH = B * H
    block_t = min(block_t, T)

    def fold(x):  # (B,T,H,D) -> (BH, T, D)
        return jnp.swapaxes(x, 1, 2).reshape(BH, T, D)

    rf, kf, vf, wf = map(fold, (r, k, v, w))
    # (BH, 1, D) so the (1, 1, D) block spans the array's last two dims,
    # as the TPU tiling rule requires of a block narrower than (8, 128)
    uf = jnp.broadcast_to(u[None], (B, H, D)).reshape(BH, 1, D)
    if initial_state is None:
        initial_state = jnp.zeros((B, H, D, D), jnp.float32)
    s0 = initial_state.reshape(BH, D, D).astype(jnp.float32)

    nt = pl.cdiv(T, block_t)
    grid = (BH, nt)

    kernel = functools.partial(_rwkv6_kernel, block_t=block_t, seq_len=T)
    y, sT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, D), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, D, D), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, D, D), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), r.dtype),
            jax.ShapeDtypeStruct((BH, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)

    y = jnp.swapaxes(y.reshape(B, H, T, D), 1, 2)
    return y, sT.reshape(B, H, D, D)
