"""PreResNet-20 for the benchmark: how the program is set up for it, the
weights the benchmark draws, and a plain float32 reference of one FeDepth
subproblem.

The reference is written from the model's description (pre-activation
residual blocks, 3-3-3 stages, GroupNorm, the skip head of FeDepth) in
plain ``jax.numpy``/``lax`` and imports nothing of the program.  It works
on the program's parameter layout, so the same weights feed both:

    {"stem": (3,3,3,w0), "blocks": [{"n1", "conv1", "n2", "conv2",
     ["proj"]}, ...], "head_norm": {"w", "b"}, "classifier": {"w", "b"}}

FeDepth (arXiv:2303.04887, Algorithm 1) trains block j = units [lo, hi)
together with the head; the input layer (the stem) belongs to the block
that holds unit 0, and every unit before ``lo`` is a frozen prefix whose
output is fed forward without gradient.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GN_GROUPS = 8
GN_EPS = 1e-5


# ------------------------------------------------------------------ shapes
def block_channels(sizes):
    """(c_in, c_out, stride) of every residual block."""
    out, c_in = [], sizes["base_widths"][0]
    for s, (n, w) in enumerate(zip(sizes["stage_blocks"],
                                   sizes["base_widths"])):
        for b in range(n):
            out.append((c_in, w, 2 if (s > 0 and b == 0) else 1))
            c_in = w
    return out


def n_units(sizes) -> int:
    return sum(sizes["stage_blocks"])


# ----------------------------------------------------------- the program
def program_context(sizes, traffic, seed: int, kernel_force=None):
    """The program's own data and context for this configuration, as a
    user builds them: ``build_federated`` + ``build_context``."""
    from repro.configs.preresnet20 import ResNetConfig
    from repro.fl import SimConfig, build_context, build_federated

    cfg = ResNetConfig(num_classes=sizes["num_classes"],
                       stage_blocks=tuple(sizes["stage_blocks"]),
                       base_widths=tuple(sizes["base_widths"]),
                       image_size=sizes["image_size"],
                       in_channels=sizes["in_channels"])
    n = traffic["num_clients"]
    data = build_federated(
        num_clients=n, partition="dirichlet", alpha=traffic["alpha"],
        balanced=True, n_train=n * traffic["samples_per_client"],
        n_test=traffic["test_samples"], num_classes=sizes["num_classes"],
        image_size=sizes["image_size"], seed=seed)
    sim = SimConfig(participation=traffic["participation"],
                    lr=traffic["lr"], momentum=traffic["momentum"],
                    local_steps=traffic["local_steps"],
                    batch_size=traffic["batch_size"],
                    mem_batch=traffic["mem_batch"], seed=seed)
    return build_context(data, sim, model_cfg=cfg)


# ---------------------------------------------------------------- weights
def init_params(key, sizes):
    """He-normal convolutions, unit GroupNorm, a 1/sqrt(fan_in) linear
    head; drawn in one traced call from ``key``."""
    w = sizes["base_widths"]
    chans = block_channels(sizes)
    keys = jax.random.split(key, 3 * len(chans) + 2)

    def conv(k, kh, cin, cout):
        return jax.random.normal(k, (kh, kh, cin, cout), jnp.float32) \
            * np.sqrt(2.0 / (kh * kh * cin))

    def norm(c):
        return {"w": jnp.ones((c,), jnp.float32),
                "b": jnp.zeros((c,), jnp.float32)}

    blocks = []
    for i, (cin, cout, stride) in enumerate(chans):
        k1, k2, k3 = keys[3 * i:3 * i + 3]
        bp = {"n1": norm(cin), "conv1": conv(k1, 3, cin, cout),
              "n2": norm(cout), "conv2": conv(k2, 3, cout, cout)}
        if stride != 1 or cin != cout:
            bp["proj"] = conv(k3, 1, cin, cout)
        blocks.append(bp)
    return {
        "stem": conv(keys[-2], 3, sizes["in_channels"], w[0]),
        "blocks": blocks,
        "head_norm": norm(w[-1]),
        "classifier": {
            "w": jax.random.normal(keys[-1], (w[-1], sizes["num_classes"]),
                                   jnp.float32) / np.sqrt(w[-1]),
            "b": jnp.zeros((sizes["num_classes"],), jnp.float32)},
    }


# --------------------------------------------------------------- reference
def ref_batch(data, take):
    """The examples ``take`` of the data set as one batch."""
    return {"images": data.x[take], "labels": data.y[take]}


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, w, b):
    B, H, W, C = x.shape
    g = min(GN_GROUPS, C)
    while C % g:
        g -= 1
    xg = x.reshape(B, H, W, g, C // g)
    mu = xg.mean((1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean((1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + GN_EPS)
    return xg.reshape(B, H, W, C) * w.astype(x.dtype) + b.astype(x.dtype)


def _unit(bp, x, stride):
    h = jax.nn.relu(_group_norm(x, bp["n1"]["w"], bp["n1"]["b"]))
    shortcut = _conv(h, bp["proj"], stride) if "proj" in bp else x
    h = _conv(h, bp["conv1"], stride)
    h = jax.nn.relu(_group_norm(h, bp["n2"]["w"], bp["n2"]["b"]))
    return shortcut + _conv(h, bp["conv2"], 1)


def ref_embed(p, batch):
    return _conv(batch["images"], p["stem"], 1)


def ref_units(sizes, p, z, lo, hi):
    chans = block_channels(sizes)
    for i in range(lo, hi):
        z = _unit(p["blocks"][i], z, chans[i][2])
    return z


def ref_head_loss(sizes, p, z, batch):
    """Skip head: zero-pad the block's channels to the head width, then
    norm, relu, global mean pool, linear, mean cross-entropy."""
    c_head = sizes["base_widths"][-1]
    if z.shape[-1] < c_head:
        z = jnp.pad(z, ((0, 0), (0, 0), (0, 0), (0, c_head - z.shape[-1])))
    h = jax.nn.relu(_group_norm(z, p["head_norm"]["w"], p["head_norm"]["b"]))
    logits = h.mean((1, 2)) @ p["classifier"]["w"].astype(h.dtype) \
        + p["classifier"]["b"].astype(h.dtype)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    return (logz - gold).mean()


def ref_split(p, lo, hi):
    """What subproblem [lo, hi) trains: its units, the head, and the stem
    when the block holds unit 0."""
    train = {"blocks": list(p["blocks"][lo:hi]),
             "head_norm": p["head_norm"], "classifier": p["classifier"]}
    if lo == 0:
        train["stem"] = p["stem"]
    return train


def ref_merge(p, train, lo, hi):
    out = dict(p)
    out["blocks"] = list(p["blocks"][:lo]) + list(train["blocks"]) \
        + list(p["blocks"][hi:])
    for k, v in train.items():
        if k != "blocks":
            out[k] = v
    return out


def ref_step_static(lo, hi, j):
    """The part of a subproblem that its compiled step depends on."""
    return (lo, hi)


def make_prefix(sizes):
    """Frozen-prefix forward z_{lo-1}: the stem, then units [0, lo)."""
    fwd = jax.jit(lambda p, batch, lo: ref_units(
        sizes, p, ref_embed(p, batch), 0, lo), static_argnums=2)
    return fwd


def ref_loss(sizes, static, frozen, train, z_in, batch):
    """Subproblem loss: head(units[lo, hi)(z_in)); with lo == 0 the stem
    is trained and ``z_in`` is unused."""
    lo, hi = static
    merged = ref_merge(frozen, train, lo, hi)
    z = ref_embed(merged, batch) if lo == 0 else z_in
    return ref_head_loss(sizes, merged, ref_units(sizes, merged, z, lo, hi),
                         batch)


def leaves(p):
    """(name, array) of every parameter array, for the comparison."""
    flat, _ = jax.tree_util.tree_flatten_with_path(p)
    return [(jax.tree_util.keystr(path), x) for path, x in flat]
