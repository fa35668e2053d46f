"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

TPU-native design: unlike the RWKV6 per-channel decay, Mamba2's decay is a
single scalar per head per step (exp(A*dt_t)), which makes the *chunked*
SSD formulation numerically safe (all exponents are differences of a
monotone cumulative sum, hence <= 0) and MXU-dominated:

  within a chunk of length Cn (cs = cumsum(A*dt)):
    M[t,i]   = (C_t . B_i) * exp(cs_t - cs_i) * dt_i      (i <= t, causal)
    Y_intra  = M @ X                                      (Cn,Cn)@(Cn,P)
    Y_inter  = (C * exp(cs)) @ h_prev^T                   (Cn,N)@(N,P)
    h_new    = exp(cs_last) h_prev
               + (X * (exp(cs_last - cs)*dt))^T @ B       (P,Cn)@(Cn,N)

All three are 128-aligned matmuls; the (P,N) fp32 state lives in VMEM
scratch across the sequential time grid axis. Grid = (B, H, T/block_t);
B/C projections are shared across heads so their tiles are re-fetched per
head (they are small: block_t x N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                y_ref, hT_ref, state_ref, *, block_t, seq_len):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0]

    x = x_ref[0, 0].astype(jnp.float32)          # (Cn, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Cn, 1)
    A = a_ref[0, 0]                              # scalar (1,1) fp32
    Bm = b_ref[0].astype(jnp.float32)            # (Cn, N)
    Cm = c_ref[0].astype(jnp.float32)            # (Cn, N)
    D = d_ref[0, 0]                              # scalar

    # ragged tail: zero dt AND the padded operand rows beyond seq_len
    # (out-of-bounds block reads are undefined — a NaN there would poison
    # valid rows through the intra-chunk matmuls, since NaN * 0 = NaN)
    t_global = ti * block_t + jax.lax.broadcasted_iota(
        jnp.int32, dt.shape, 0)
    valid = t_global < seq_len
    dt = jnp.where(valid, dt, 0.0)
    x = jnp.where(valid, x, 0.0)
    Bm = jnp.where(valid, Bm, 0.0)
    Cm = jnp.where(valid, Cm, 0.0)

    l = A * dt                                   # (Cn,1) <= 0
    # inclusive cumsum as a masked lane reduction: Mosaic has no cumsum
    cn = l.shape[0]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (cn, cn), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (cn, cn), 1))
    cs = jnp.sum(jnp.where(causal, l.T, 0.0), axis=1, keepdims=True)

    # intra-chunk "attention" matrix, strictly causal in i<=t
    rel = cs - cs.T                              # (Cn,Cn) cs_t - cs_i
    decay = jnp.where(causal, jnp.exp(rel), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    M = scores * decay * dt.T                    # (Cn,Cn) * dt_i broadcast
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of carried-in state
    h = state_ref[...]                           # (P, N)
    y += jax.lax.dot_general(Cm * jnp.exp(cs), h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update
    cs_last = cs[-1:, :]                          # (1,1)
    wgt = jnp.exp(cs_last - cs) * dt              # (Cn,1)
    h_new = jnp.exp(cs_last[0, 0]) * h + jax.lax.dot_general(
        x * wgt, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_ref[...] = h_new

    y_ref[0, 0] = (y + D * x).astype(y_ref.dtype)

    @pl.when(ti == nt - 1)
    def _emit():
        hT_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def mamba2_scan(
    x: jax.Array,    # (B, T, H, P)
    dt: jax.Array,   # (B, T, H)  positive step sizes
    A: jax.Array,    # (H,)       negative decay rates
    Bm: jax.Array,   # (B, T, N)
    Cm: jax.Array,   # (B, T, N)
    D: jax.Array,    # (H,)
    initial_state: jax.Array | None = None,  # (B, H, P, N) fp32
    *,
    block_t: int = 128,
    interpret: bool = False,
):
    """Returns (y: (B,T,H,P) in x.dtype, final_state: (B,H,P,N) fp32)."""
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    block_t = min(block_t, T)

    xt = jnp.swapaxes(x, 1, 2)                       # (B,H,T,P)
    dtt = jnp.swapaxes(dt, 1, 2)[..., None]          # (B,H,T,1)
    Af = A.astype(jnp.float32).reshape(H, 1, 1)      # (H,1,1)
    Df = D.astype(jnp.float32).reshape(H, 1, 1)
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), jnp.float32)

    nt = pl.cdiv(T, block_t)
    grid = (B, H, nt)
    kernel = functools.partial(_ssd_kernel, block_t=block_t, seq_len=T)

    y, hT = pl.pallas_call(
        kernel,
        name="_ssd_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_t, P), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_t, 1), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, t: (h, 0, 0)),
            pl.BlockSpec((1, block_t, N), lambda b, h, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, N), lambda b, h, t: (b, t, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, t: (h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_t, P), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, Af, Bm, Cm, Df, initial_state.astype(jnp.float32))

    return jnp.swapaxes(y, 1, 2), hT
