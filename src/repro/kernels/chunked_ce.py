"""Chunked cross-entropy over large vocabularies as a Pallas TPU kernel.

The paper's core observation is that *activations*, not parameters, bound
training memory.  For the assigned LLM architectures the single largest
activation is the logits tensor: qwen2-7b at train_4k materializes
(256*4096, 152064) fp32 logits = 638 GB globally.  This kernel computes
token NLL with an online logsumexp over vocab tiles so the full logits
matrix never exists in HBM — the live working set is one
(block_t, block_v) tile in VMEM.

grid = (T/block_t, V/block_v), vocab innermost; scratch carries the
running max/sum-exp and the gathered gold logit per token row.  The
requested tiles are shrunk (:func:`fit_tiles`) until the double-buffered
hidden and weight tiles plus the f32 logit temporaries fit v5e's 16 MiB
default scoped VMEM: at D=1024 the default (1024, 2048) f32 weight tile
alone would take 16 MiB double-buffered, which the TPU compiler refuses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# VMEM working-set budget per kernel instance, kept under the 16 MiB
# default scoped-VMEM limit of TPU v5e with room for Mosaic's own scratch
VMEM_BUDGET = 12 * 2**20


def fit_tiles(BT: int, D: int, V: int, block_t: int, block_v: int,
              itemsize: int = 4):
    """Clamp ``(block_t, block_v)`` to the array and halve them (vocab
    first) until the working set fits :data:`VMEM_BUDGET`: the
    double-buffered ``(block_t, D)`` hidden and ``(D, block_v)`` weight
    tiles plus ~4 f32 ``(block_t, block_v)`` logit temporaries.  Halving
    stops at TPU tiling granules (8 rows, 128 lanes)."""
    block_t, block_v = min(block_t, BT), min(block_v, V)

    def working_set(bt, bv):
        return 2 * itemsize * D * (bt + bv) + 4 * 4 * bt * bv

    while working_set(block_t, block_v) > VMEM_BUDGET and \
            block_v % 256 == 0:
        block_v //= 2
    while working_set(block_t, block_v) > VMEM_BUDGET and \
            block_t % 16 == 0:
        block_t //= 2
    return block_t, block_v


def _ce_kernel(h_ref, w_ref, lbl_ref, nll_ref, m_ref, l_ref, g_ref, *,
               block_t, block_v, vocab_size):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    h = h_ref[...].astype(jnp.float32)              # (bt, D)
    w = w_ref[...].astype(jnp.float32)              # (D, bv)
    s = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    v_pos = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_v), 1)
    s = jnp.where(v_pos < vocab_size, s, NEG_INF)

    labels = lbl_ref[...]                           # (bt, 1) int32
    g_ref[...] += jnp.sum(jnp.where(v_pos == labels, s, 0.0),
                          axis=1, keepdims=True)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(vi == nv - 1)
    def _finish():
        logz = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        nll = logz - g_ref[...]
        # ignored labels (<0) contribute 0
        nll_ref[...] = jnp.where(labels >= 0, nll, 0.0).astype(nll_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_v", "interpret"))
def chunked_cross_entropy(
    hidden: jax.Array,    # (B, T, D)
    lm_head: jax.Array,   # (D, V)
    labels: jax.Array,    # (B, T) int32, -100 = ignore
    *,
    block_t: int = 256,
    block_v: int = 2048,
    interpret: bool = False,
):
    """Returns (mean_nll over valid labels, n_valid)."""
    B, T, D = hidden.shape
    V = lm_head.shape[1]
    BT = B * T
    block_t, block_v = fit_tiles(BT, D, V, block_t, block_v,
                                 max(hidden.dtype.itemsize,
                                     lm_head.dtype.itemsize))

    h = hidden.reshape(BT, D)
    lbl = labels.reshape(BT, 1).astype(jnp.int32)

    grid = (pl.cdiv(BT, block_t), pl.cdiv(V, block_v))
    kernel = functools.partial(_ce_kernel, block_t=block_t,
                               block_v=block_v, vocab_size=V)
    nll = pl.pallas_call(
        kernel,
        name="_ce_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, D), lambda t, v: (t, 0)),
            pl.BlockSpec((D, block_v), lambda t, v: (0, v)),
            pl.BlockSpec((block_t, 1), lambda t, v: (t, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, 1), lambda t, v: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((BT, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),  # m
            pltpu.VMEM((block_t, 1), jnp.float32),  # l
            pltpu.VMEM((block_t, 1), jnp.float32),  # gold
        ],
        interpret=interpret,
    )(h, lm_head, lbl)

    valid = (lbl >= 0)
    n = jnp.maximum(valid.sum(), 1)
    return nll.sum() / n, n
