"""Helpers shared by the built-in strategies (image + LM evals)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.preresnet20 import ResNetConfig
from repro.fl.strategy import accuracy
from repro.models import resnet


@functools.lru_cache(maxsize=64)
def apply_jit(cfg: ResNetConfig):
    return jax.jit(lambda p, x: resnet.apply(p, cfg, x))


def resnet_accuracy(cfg: ResNetConfig, params, x, y) -> float:
    ap = apply_jit(cfg)
    return accuracy(lambda xb: ap(params, xb), x, y)


# logits elements (B x T x vocab-tile) one eval step may hold at once
_ARGMAX_TILE_ELEMS = 1 << 26


def argmax_over_vocab(x, w):
    """``argmax(x @ w, -1)`` without the ``(B, T, V)`` logits: a scan over
    vocab tiles keeps the running max and its index, so only one
    ``(B, T, tile)`` slice exists at a time.  Ties resolve to the lowest
    index, as in ``jnp.argmax``."""
    D, V = w.shape
    rows = x.shape[0] * x.shape[1]
    tile = min(V, max(128, _ARGMAX_TILE_ELEMS // max(rows, 1) // 128 * 128))
    n = -(-V // tile)
    wt = jnp.pad(w, ((0, 0), (0, n * tile - V))).reshape(D, n, tile)
    wt = jnp.moveaxis(wt, 1, 0)                        # (n, D, tile)

    def body(carry, inp):
        best, idx = carry
        i, wi = inp
        col = i * tile + jnp.arange(tile)
        s = jnp.where(col < V, x @ wi, -jnp.inf)
        m, a = s.max(-1), s.argmax(-1) + i * tile
        take = m > best
        return (jnp.where(take, m, best), jnp.where(take, a, idx)), None

    init = (jnp.full(x.shape[:-1], -jnp.inf, jnp.float32),
            jnp.zeros(x.shape[:-1], jnp.int32))
    (_, idx), _ = jax.lax.scan(body, init, (jnp.arange(n), wt))
    return idx


@functools.lru_cache(maxsize=64)
def lm_predict_jit(cfg: ModelConfig, kernel_force: Optional[str]):
    """Jitted next-token argmax over the full vocabulary."""
    from repro.models import build, common as mcommon
    lm = build(cfg)

    def predict(p, toks):
        x, _ = lm.forward_hidden(p, toks, kernel_force=kernel_force)
        x = mcommon.rms_norm(x, p["final_norm"], cfg.norm_eps)
        w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
        return argmax_over_vocab(x, w)

    return jax.jit(predict)


def lm_accuracy(cfg: ModelConfig, params, x, y, *,
                kernel_force: Optional[str] = None, batch: int = 64) -> float:
    """Next-token top-1 accuracy over ``(M, T)`` token/label arrays,
    normalized by VALID positions (labels >= 0) — the LM counterpart of
    ``strategy.accuracy``, which divides by rows."""
    predict = lm_predict_jit(cfg, kernel_force)
    correct, total = 0, 0
    for i in range(0, len(x), batch):
        xb = jnp.asarray(x[i:i + batch])
        yb = jnp.asarray(y[i:i + batch])
        pred = predict(params, xb)
        valid = yb >= 0
        correct += int(((pred == yb) & valid).sum())
        total += int(valid.sum())
    return correct / max(total, 1)
