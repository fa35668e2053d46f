"""mamba2-370m for the benchmark: how the program is set up for it, the
weights the benchmark draws, and a plain float32 reference of one FeDepth
subproblem.

The reference is written from the layer's equations in plain
``jax.numpy`` and imports nothing of the program.  Per layer (residual):

    h = rms_norm(x) ; [z | xs | B | C | dt] = h @ in_proj
    xs = silu(causal_depthwise_conv4(xs)) ; B, C = silu(B), silu(C)
    dt = softplus(dt + dt_bias) ; A = -exp(A_log)
    state_t = exp(A dt_t) state_{t-1} + dt_t (xs_t outer B_t)
    y_t = C_t . state_t + D xs_t
    out = (y * silu(z)) @ out_proj

The scan is computed in the SSD chunked form (exact up to rounding:
within a chunk the decays are differences of one cumulative sum).  The
head is the tied embedding: logits = rms_norm(x) @ embed.T, mean
cross-entropy over every token.  Parameters use the program's layout:

    {"embed": (V, d), "final_norm": (d,), "layers": {"norm", "in_proj",
     "conv_w", "conv_b", "dt_bias", "A_log", "D", "out_proj"}}

with a leading axis of ``num_layers`` on every layer array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SSD_CHUNK = 128
LAYER_KEYS = ("norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log",
              "D", "out_proj")


def n_units(sizes) -> int:
    return sizes["num_layers"]


def _d_inner(sizes) -> int:
    return sizes["ssm_expand"] * sizes["d_model"]


# ----------------------------------------------------------- the program
def program_config(sizes):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name="mamba2-370m", family="ssm", ssm_kind="mamba2",
        source="arXiv:2405.21060", num_layers=sizes["num_layers"],
        d_model=sizes["d_model"], num_heads=0, num_kv_heads=0, d_ff=0,
        vocab_size=sizes["vocab_size"],
        ssm_state_dim=sizes["ssm_state_dim"],
        ssm_head_dim=sizes["ssm_head_dim"],
        ssm_num_heads=sizes["ssm_num_heads"],
        ssm_expand=sizes["ssm_expand"],
        tie_embeddings=sizes["tie_embeddings"], norm_eps=sizes["norm_eps"])


def program_context(sizes, traffic, seed: int, kernel_force=None):
    """The program's own token data and LM context, as a user builds
    them: ``build_seq_data`` + ``build_lm_context``."""
    from repro.fl import SimConfig
    from repro.fl.seq import build_lm_context, build_seq_data

    data = build_seq_data(
        traffic["num_clients"], n_per_client=traffic["samples_per_client"],
        n_test=traffic["test_samples"], vocab_size=sizes["vocab_size"],
        seq_len=traffic["seq_len"], noise=traffic["noise"], seed=seed)
    sim = SimConfig(participation=traffic["participation"],
                    lr=traffic["lr"], momentum=traffic["momentum"],
                    local_steps=traffic["local_steps"],
                    batch_size=traffic["batch_size"],
                    mem_batch=traffic["mem_batch"], seed=seed)
    return build_lm_context(data, sim, program_config(sizes),
                            kernel_force=kernel_force)


# ---------------------------------------------------------------- weights
def init_params(key, sizes):
    """Drawn in one traced call from ``key``: normal embedding (0.02),
    1/sqrt(fan_in) projections, conv taps N(0, 0.1), unit norms and D,
    zero conv bias, dt bias and A_log."""
    L, d, V = sizes["num_layers"], sizes["d_model"], sizes["vocab_size"]
    din, N, H = _d_inner(sizes), sizes["ssm_state_dim"], \
        sizes["ssm_num_heads"]
    K = sizes["conv_kernel"]
    k = jax.random.split(key, 4)
    f32 = jnp.float32
    layers = {
        "norm": jnp.ones((L, d), f32),
        "in_proj": jax.random.normal(k[0], (L, d, 2 * din + 2 * N + H), f32)
        / np.sqrt(d),
        "conv_w": jax.random.normal(k[1], (L, K, din), f32) * 0.1,
        "conv_b": jnp.zeros((L, din), f32),
        "dt_bias": jnp.zeros((L, H), f32),
        "A_log": jnp.zeros((L, H), f32),
        "D": jnp.ones((L, H), f32),
        "out_proj": jax.random.normal(k[2], (L, din, d), f32) / np.sqrt(din),
    }
    return {"embed": jax.random.normal(k[3], (V, d), f32) * 0.02,
            "layers": layers, "final_norm": jnp.ones((d,), f32)}


# --------------------------------------------------------------- reference
def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def ssd(x, dt, A, Bm, Cm, D, chunk: int = SSD_CHUNK):
    """y_t = C_t . state_t + D x_t with state_t = exp(A dt_t) state_{t-1}
    + dt_t x_t B_t^T, state_0 = 0.  x: (B,T,H,P); dt: (B,T,H); A, D: (H,);
    Bm, Cm: (B,T,N)."""
    b, T, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, T)
    c = T // Q
    xc = x.reshape(b, c, Q, H, P)
    dtc = dt.reshape(b, c, Q, H)
    Bc = Bm.reshape(b, c, Q, N)
    Cc = Cm.reshape(b, c, Q, N)
    cs = jnp.cumsum(dtc * A.astype(x.dtype), axis=2)       # (b,c,Q,H)
    cst = jnp.moveaxis(cs, 3, 2)                            # (b,c,H,Q)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    seg = cst[..., :, None] - cst[..., None, :]             # (b,c,H,Q,Q)
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    scores = jnp.einsum("bcln,bcsn->bcls", Cc, Bc)
    mix = scores[:, :, None] * decay * jnp.moveaxis(dtc, 3, 2)[..., None, :]
    y = jnp.einsum("bchls,bcshp->bclhp", mix, xc)
    # state each chunk hands on, and the state it starts from
    to_end = jnp.exp(cs[:, :, -1:, :] - cs) * dtc           # (b,c,Q,H)
    states = jnp.einsum("bcsn,bcshp->bchpn", Bc, xc * to_end[..., None])
    chunk_decay = jnp.exp(cs[:, :, -1, :])                  # (b,c,H)

    def carry(h, inp):
        dec, st = inp
        return dec[:, :, None, None] * h + st, h

    _, h_in = jax.lax.scan(
        carry, jnp.zeros((b, H, P, N), x.dtype),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                         # (b,c,H,P,N)
    y = y + jnp.einsum("bcln,bchpn->bclhp", Cc, h_in) * jnp.exp(cs)[..., None]
    y = y + xc * D.astype(x.dtype)[:, None]
    return y.reshape(b, T, H, P)


def _layer(sizes, lp, x):
    b, T, d = x.shape
    din, N, H = _d_inner(sizes), sizes["ssm_state_dim"], \
        sizes["ssm_num_heads"]
    P = sizes["ssm_head_dim"]
    h = _rms_norm(x, lp["norm"], sizes["norm_eps"])
    proj = h @ lp["in_proj"].astype(x.dtype)
    z, xs = proj[..., :din], proj[..., din:2 * din]
    Bm, Cm = proj[..., 2 * din:2 * din + N], proj[..., 2 * din + N:2 * din + 2 * N]
    dt = proj[..., 2 * din + 2 * N:]
    K = lp["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((b, K - 1, din), x.dtype), xs], axis=1)
    xs = sum(xp[:, i:i + T] * lp["conv_w"][i].astype(x.dtype)
             for i in range(K)) + lp["conv_b"].astype(x.dtype)
    xs, Bm, Cm = _silu(xs), _silu(Bm), _silu(Cm)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(x.dtype))
    A = -jnp.exp(lp["A_log"].astype(x.dtype))
    y = ssd(xs.reshape(b, T, H, P), dt, A, Bm, Cm, lp["D"])
    y = y.reshape(b, T, din) * _silu(z)
    return y @ lp["out_proj"].astype(x.dtype)


def _layer_i(layers, i):
    return {k: v[i] for k, v in layers.items()}


def ref_batch(data, take):
    """The sequences ``take`` as one batch: each token predicts the next."""
    seq = data.seqs[take]
    return {"tokens": seq[:, :-1], "labels": seq[:, 1:]}


def ref_split(p, lo, hi):
    """Subproblem [lo, hi) trains its layers and the head; the head's
    weight is the tied embedding, so the embedding trains in every
    subproblem (and through the input too when the block holds layer 0)."""
    return {"layers": {k: v[lo:hi] for k, v in p["layers"].items()},
            "embed": p["embed"], "final_norm": p["final_norm"]}


def ref_merge(p, train, lo, hi):
    layers = {k: v.at[lo:hi].set(train["layers"][k])
              for k, v in p["layers"].items()}
    return {"embed": train["embed"], "final_norm": train["final_norm"],
            "layers": layers}


def ref_step_static(lo, hi, j):
    return (hi - lo, lo == 0)


def make_prefix(sizes):
    """Frozen-prefix forward z_{lo-1}: the embedding lookup, then layers
    [0, lo), one compiled layer applied ``lo`` times."""
    layer = jax.jit(functools.partial(_layer, sizes))

    def prefix(p, batch, lo):
        z = p["embed"][batch["tokens"]]
        for i in range(lo):
            z = z + layer(_layer_i(p["layers"], i), z)
        return z

    return prefix


def ref_loss(sizes, static, frozen, train, z_in, batch):
    """Subproblem loss; with the block at layer 0 the embedding lookup is
    part of it, else ``z_in`` is the frozen prefix's output."""
    k, first = static
    z = train["embed"][batch["tokens"]] if first else z_in
    step = jax.checkpoint(functools.partial(_layer, sizes))
    for i in range(k):
        z = z + step(_layer_i(train["layers"], i), z)
    h = _rms_norm(z, train["final_norm"], sizes["norm_eps"])
    logits = h @ train["embed"].astype(h.dtype).T
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                               axis=-1)[..., 0]
    return (logz - gold).mean()


def leaves(p):
    """(name, array) of every parameter array, each layer's slice of a
    stacked layer array on its own."""
    out = [("embed", p["embed"]), ("final_norm", p["final_norm"])]
    for k in LAYER_KEYS:
        out += [(f"layers.{k}[{i}]", a) for i, a in enumerate(p["layers"][k])]
    return out
