"""Depth-wise sequential learning (paper Eq. 1 + Figure 4).

For a client with decomposition {(lo_1,hi_1), ...}: solve J subproblems in
order.  Subproblem j trains ONLY units [lo_j, hi_j) plus the head φ; the
prefix is FROZEN and its output activation z_{lo_j - 1} is BUFFERED (the
paper's frozen-then-pass forward), so each subproblem's live memory is one
block, not the network.  :class:`PrefixCache` (default on) makes the
buffering literal at runtime: z_{lo_j-1} is computed once per distinct
batch per subproblem, reused across every SGD step, and advanced
incrementally through the just-trained units between subproblems — see
docs/prefix_cache.md.

Two head strategies (paper §Methodology):
  * ``head="skip"``  — skip connection from the block output straight into
    the shared classifier (zero-pad / pool dimension match where needed).
  * ``head="aux"``   — per-block auxiliary classifier (m-FeDepth); the aux
    heads are extra, tiny, and discarded at inference (the final block
    trains the real head).

Implementations are family-generic via the ``BlockRunner`` protocol with
adapters for LM / ResNet / ViT.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.decomposition import Decomposition
from repro.core.jit_utils import donate, donation_supported
from repro.models import common, resnet as resnet_mod, vit as vit_mod
from repro.obs import active as obs_active


def _jit_cache_probe(cache: dict, key, build, *, name: str, audit=None):
    """``cache.setdefault(key, build())`` with telemetry: when a capture
    is active, count the hit/miss and time the builder (python trace
    construction; XLA compile itself lands in the first dispatch, which
    the scheduler's ``group_update_seconds`` covers).  The disabled path
    is the bare two-line probe every jit cache in the repo already
    uses.

    ``audit`` is the memory-conformance hook
    (:class:`repro.obs.audit.MemoryAuditor`): a callback invoked with
    the cached callable on every probe — call sites only construct one
    when the active capture carries an auditor, so the default path
    never pays for it.  The auditor dedupes per cell, so probing a
    warm shared cache still records each executable once per capture."""
    obs = obs_active()
    if obs is None:
        if key not in cache:
            cache[key] = build()
        if audit is not None:
            audit(cache[key])
        return cache[key]
    if key not in cache:
        t0 = time.perf_counter()
        cache[key] = build()
        obs.metrics.counter("jit_cache_misses", cache=name).inc()
        obs.metrics.histogram("jit_build_seconds", cache=name).observe(
            time.perf_counter() - t0)
    else:
        obs.metrics.counter("jit_cache_hits", cache=name).inc()
    if audit is not None:
        audit(cache[key])
    return cache[key]


# --------------------------------------------------------------------------
# family adapters
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BlockRunner:
    """Decomposes a model into (embed -> units -> head) for FeDepth."""
    n_units: int
    embed: Callable[[Any, Dict], jax.Array]           # params, batch -> z0
    apply_units: Callable[[Any, jax.Array, int, int], jax.Array]
    head_loss: Callable[[Any, jax.Array, Dict, int], jax.Array]
    # which top-level keys are trained with every block (the head φ);
    # embed keys train with block 0 only
    split: Callable[[Any, int, int], Any]  # -> trainable subtree
    merge: Callable[[Any, Any], Any]
    # True when the params feeding ``embed`` and the prefix
    # ``apply_units(·, 0, lo)`` never change while LATER subproblems
    # train, so a buffered z_{lo-1} can be advanced incrementally through
    # the just-trained units and stay exactly equal to a from-scratch
    # prefix forward.  False for families whose head-trained keys leak
    # into the prefix forward (tied embeddings, whisper's enc_norm,
    # hybrid's shared attention) — there :class:`PrefixCache` re-buffers
    # once per subproblem instead (still once, never once per step).
    prefix_stable: bool = True
    # model-family tag keying the memory auditor's conformance cells
    # ("resnet" / "vit" / the LM config family) — label-only, no
    # behavioral meaning
    family: str = "?"


# ---- LM adapter -----------------------------------------------------------
def lm_runner(lm, head: str = "skip", kernel_force=None) -> BlockRunner:
    cfg = lm.cfg
    mod = lm.module

    if cfg.is_encoder_decoder:
        return _whisper_runner(lm, kernel_force)

    layers_key = "units" if cfg.family in ("dense", "moe", "vlm") else (
        "mamba_groups" if cfg.family == "hybrid" else "layers")
    head_keys = {"final_norm", "lm_head"}
    if cfg.family == "hybrid":
        head_keys |= {"shared", "invocation_norms"}
    if cfg.tie_embeddings:
        head_keys |= {"embed"}

    def embed(params, batch):
        from repro.models import transformer
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.embed_inputs(
                params, cfg, batch["tokens"],
                vision_embeds=batch.get("vision_embeds"))
        return params["embed"][batch["tokens"]]

    def apply_units(params, z, lo, hi):
        out, _aux = lm.apply_range(params, z, lo, hi,
                                   kernel_force=kernel_force)
        return out

    def head_loss(params, z, batch, block_idx):
        from repro.kernels import ops
        from repro.models import transformer
        if head == "aux" and "aux_norms" in params \
                and block_idx < lm.num_depth_units - 1:
            norm_w = params["aux_norms"][block_idx]
        else:
            norm_w = params["final_norm"]
        x = common.rms_norm(z, norm_w, cfg.norm_eps)
        labels = batch["labels"]
        if batch.get("vision_embeds") is not None:
            P = batch["vision_embeds"].shape[1]
            x = x[:, P:]
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ce, _ = ops.cross_entropy(x, w, labels, force=kernel_force)
        return ce

    def split(params, lo, hi):
        train = {k: v for k, v in params.items()
                 if k in head_keys or k == "aux_norms"}
        train[layers_key] = jax.tree.map(lambda a: a[lo:hi],
                                         params[layers_key])
        if lo == 0 and "embed" not in train:
            train["embed"] = params["embed"]
        return train

    def merge(params, train, lo: int = None, hi: int = None):
        out = dict(params)
        for k, v in train.items():
            if k == layers_key:
                out[k] = jax.tree.map(
                    lambda full, blk: full.at[lo:hi].set(blk),
                    params[k], v)
            else:
                out[k] = v
        return out

    # tied embeddings train the embed table through the head path every
    # subproblem, and the hybrid family's shared attention params (trained
    # with φ) sit inside apply_range — both leak head updates into the
    # prefix forward, so buffered activations must be re-buffered per
    # subproblem rather than advanced incrementally
    stable = not cfg.tie_embeddings and cfg.family != "hybrid"
    return BlockRunner(lm.num_depth_units, embed, apply_units, head_loss,
                       split, merge, prefix_stable=stable,
                       family=cfg.family)


def _whisper_runner(lm, kernel_force):
    """Whisper: units = encoder layers then decoder layers; the encoder
    output is a buffered activation for decoder blocks (paper's z_j
    buffering); head = decoder final LN + tied embed."""
    from repro.kernels import ops
    from repro.models import whisper
    cfg = lm.cfg
    E = cfg.encoder_layers

    def embed(params, batch):
        # z0 is the (audio frames, token embeds) pair
        S = batch["encoder_embeds"].shape[1]
        x_enc = batch["encoder_embeds"] + params["pos_enc"][None, :S].astype(
            batch["encoder_embeds"].dtype)
        T = batch["tokens"].shape[1]
        x_dec = params["embed"][batch["tokens"]] + params["pos_dec"][None, :T]
        return {"enc": x_enc, "dec": x_dec}

    def apply_units(params, z, lo, hi):
        # ``_enc_range`` is the single encoder path: ``embed`` already
        # added pos_enc, so ``whisper.encode`` (which re-adds it) must
        # never run here — asserted against the reference encoder in
        # tests/test_adapters.py
        enc, dec = z["enc"], z["dec"]
        e_lo, e_hi = min(lo, E), min(hi, E)
        d_lo, d_hi = max(lo - E, 0), max(hi - E, 0)
        if e_hi > e_lo:
            enc = _enc_range(params, cfg, enc, e_lo, e_hi, kernel_force)
        if d_hi > d_lo:
            dec = whisper.apply_decoder_range(params, cfg, dec, enc, d_lo,
                                              d_hi, kernel_force=kernel_force)
        return {"enc": enc, "dec": dec}

    def _enc_range(params, cfg_, x, lo, hi, kf):
        # encoder slice without pos-add / final norm
        import functools
        from repro.models import attention as attn_mod
        layers = jax.tree.map(lambda a: a[lo:hi], params["enc_layers"])

        def body(h, lp):
            hn = common.layer_norm(h, lp["ln1"]["w"], lp["ln1"]["b"],
                                   cfg_.norm_eps)
            h = h + attn_mod.forward(lp["attn"], cfg_, hn, None, causal=False,
                                     kernel_force=kf)
            hn = common.layer_norm(h, lp["ln2"]["w"], lp["ln2"]["b"],
                                   cfg_.norm_eps)
            return h + jax.nn.gelu(hn @ lp["mlp"]["w1"] + lp["mlp"]["b1"]) \
                @ lp["mlp"]["w2"] + lp["mlp"]["b2"], None

        h, _ = common.scan(body, x, layers)
        if hi == cfg_.encoder_layers:
            h = common.layer_norm(h, params["enc_norm"]["w"],
                                  params["enc_norm"]["b"], cfg_.norm_eps)
        return h

    def head_loss(params, z, batch, block_idx):
        dec = z["dec"]
        x = common.layer_norm(dec, params["dec_norm"]["w"],
                              params["dec_norm"]["b"], cfg.norm_eps)
        ce, _ = ops.cross_entropy(x, params["embed"].T, batch["labels"],
                                  force=kernel_force)
        return ce

    head_keys = {"dec_norm", "embed", "enc_norm"}

    def split(params, lo, hi):
        train = {k: params[k] for k in head_keys}
        e_lo, e_hi = min(lo, E), min(hi, E)
        d_lo, d_hi = max(lo - E, 0), max(hi - E, 0)
        if e_hi > e_lo:
            train["enc_layers"] = jax.tree.map(lambda a: a[e_lo:e_hi],
                                               params["enc_layers"])
        if d_hi > d_lo:
            train["dec_layers"] = jax.tree.map(lambda a: a[d_lo:d_hi],
                                               params["dec_layers"])
        if lo == 0:
            train["pos_enc"] = params["pos_enc"]
            train["pos_dec"] = params["pos_dec"]
        return train

    def merge(params, train, lo: int = None, hi: int = None):
        out = dict(params)
        e_lo, e_hi = min(lo, E), min(hi, E)
        d_lo, d_hi = max(lo - E, 0), max(hi - E, 0)
        for k, v in train.items():
            if k == "enc_layers":
                out[k] = jax.tree.map(lambda f, b: f.at[e_lo:e_hi].set(b),
                                      params[k], v)
            elif k == "dec_layers":
                out[k] = jax.tree.map(lambda f, b: f.at[d_lo:d_hi].set(b),
                                      params[k], v)
            else:
                out[k] = v
        return out

    # the tied embed table and enc_norm (applied at the encoder's end
    # inside apply_units) train with the head, so the prefix forward
    # drifts between subproblems — re-buffer instead of advancing
    return BlockRunner(E + cfg.num_layers, embed, apply_units, head_loss,
                       split, merge, prefix_stable=False, family="whisper")


# ---- ResNet adapter -------------------------------------------------------
def resnet_runner(cfg, head: str = "skip") -> BlockRunner:
    n = cfg.num_blocks

    def embed(params, batch):
        return resnet_mod.stem(params, batch["images"])

    def apply_units(params, z, lo, hi):
        return resnet_mod.forward_blocks(params, cfg, z, lo, hi)

    def head_loss(params, z, batch, block_idx):
        # m-FeDepth: auxiliary classifiers at intermediate exits, but the
        # FINAL block must supervise the REAL head (otherwise the global
        # classifier never receives gradient and evaluates at chance)
        if head == "aux" and "aux_heads" in params and block_idx < n - 1:
            ah = params["aux_heads"][f"b{block_idx}"]
            h = z.mean((1, 2))
            logits = h @ ah["w"] + ah["b"]
        else:
            logits = resnet_mod.head_from_block(params, cfg, z, block_idx)
        return _ce_logits(logits, batch["labels"])

    def split(params, lo, hi):
        train = {"blocks": params["blocks"][lo:hi],
                 "head_norm": params["head_norm"],
                 "classifier": params["classifier"]}
        if "aux_heads" in params:
            train["aux_heads"] = params["aux_heads"]
        if lo == 0:
            train["stem"] = params["stem"]
        return train

    def merge(params, train, lo: int = None, hi: int = None):
        # same contract as the LM/ViT adapters' ``.at[lo:hi].set``: a
        # functional splice of exactly [lo, hi) into the full stack (the
        # block list stays a list — stages have different widths, so the
        # stack cannot be one array), head/embed keys passed through.
        # Asserted by the adapter-contract test (tests/test_adapters.py).
        out = dict(params)
        out["blocks"] = (list(params["blocks"][:lo]) + list(train["blocks"])
                         + list(params["blocks"][hi:]))
        for k in train:
            if k != "blocks":
                out[k] = train[k]
        return out

    return BlockRunner(n, embed, apply_units, head_loss, split, merge,
                       family="resnet")


# ---- ViT adapter ----------------------------------------------------------
def vit_runner(cfg, head: str = "skip") -> BlockRunner:
    def embed(params, batch):
        return vit_mod.embed(params, cfg, batch["images"])

    def apply_units(params, z, lo, hi):
        return vit_mod.forward_blocks(params, cfg, z, lo, hi)

    def head_loss(params, z, batch, block_idx):
        logits = vit_mod.head(params, cfg, z)
        return _ce_logits(logits, batch["labels"])

    def split(params, lo, hi):
        train = {"blocks": jax.tree.map(lambda a: a[lo:hi], params["blocks"]),
                 "head_norm": params["head_norm"],
                 "classifier": params["classifier"]}
        if lo == 0:
            for k in ("patch_embed", "cls", "pos"):
                train[k] = params[k]
        return train

    def merge(params, train, lo: int = None, hi: int = None):
        out = dict(params)
        out["blocks"] = jax.tree.map(lambda f, b: f.at[lo:hi].set(b),
                                     params["blocks"], train["blocks"])
        for k in ("head_norm", "classifier", "patch_embed", "cls", "pos"):
            if k in train:
                out[k] = train[k]
        return out

    return BlockRunner(cfg.num_layers, embed, apply_units, head_loss,
                       split, merge, family="vit")


def _ce_logits(logits, labels):
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               labels[:, None], axis=-1)[:, 0]
    return (logz - gold).mean()


# --------------------------------------------------------------------------
# the depth-wise sequential client update (paper Algorithm 1, ClientUpdate)
# --------------------------------------------------------------------------
def block_loss_fn(runner: BlockRunner, params_full, train_params, z_in,
                  batch, lo: int, hi: int, block_idx: int,
                  merge_kw: Optional[dict] = None):
    """Loss of subproblem j: head(block(z_in)) with prefix frozen.
    ``train_params`` are the differentiated leaves; everything else comes
    from ``params_full`` under stop_gradient."""
    frozen = jax.tree.map(jax.lax.stop_gradient, params_full)
    merged = runner.merge(frozen, train_params, lo=lo, hi=hi) \
        if merge_kw is None else runner.merge(frozen, train_params, **merge_kw)
    z = runner.apply_units(merged, jax.lax.stop_gradient(z_in), lo, hi)
    # the aux classifier (m-FeDepth) sits at the block's EXIT unit
    return runner.head_loss(merged, z, batch, hi - 1)


def _prox_term(train, anchor, prox_mu: float):
    sq = sum(jnp.sum((a - b) ** 2) for a, b in zip(
        jax.tree.leaves(train), jax.tree.leaves(anchor)))
    return 0.5 * prox_mu * sq


def make_block_step(runner: BlockRunner, lo: int, hi: int, j: int, *,
                    lr: float, momentum: float, prox_mu: float = 0.0):
    """One jitted SGD-momentum step on subproblem j, recompute variant:
    the frozen-then-pass prefix forward (z_{lo-1}) happens inside the jit
    under stop_gradient every step, so XLA never allocates backward state
    for the prefix — but the prefix forward itself is re-billed per step
    (the pre-:class:`PrefixCache` execution contract, kept as the
    reference path behind ``prefix_cache=False``).  The (train, vel)
    carry is donated so the step updates it in place on gpu/tpu."""

    @functools.partial(jax.jit, donate_argnums=donate(1, 2))
    def step(params, train, vel, anchor, batch):
        def loss(tp):
            z_in = runner.embed(params, batch)
            if lo > 0:
                z_in = runner.apply_units(params, z_in, 0, lo)
            l = block_loss_fn(runner, params, tp, z_in, batch, lo, hi, j)
            if prox_mu > 0:
                l = l + _prox_term(tp, anchor, prox_mu)
            return l

        g = jax.grad(loss)(train)
        vel = jax.tree.map(lambda v, gi: momentum * v + gi, vel, g)
        train = jax.tree.map(lambda t, v: t - lr * v, train, vel)
        return train, vel

    return step


def make_buffered_block_step(runner: BlockRunner, lo: int, hi: int, j: int,
                             *, lr: float, momentum: float,
                             prox_mu: float = 0.0):
    """The :class:`PrefixCache` hot-path step: identical update rule to
    :func:`make_block_step`, but the prefix activation ``z_in`` arrives
    as an argument (buffered once per distinct batch per subproblem) —
    each step runs ONE block-local forward + backward, nothing else.
    ``z_in`` is reused across steps and therefore never donated; the
    (train, vel) carry is."""

    @functools.partial(jax.jit, donate_argnums=donate(1, 2))
    def step(params, train, vel, anchor, z_in, batch):
        def loss(tp):
            l = block_loss_fn(runner, params, tp, z_in, batch, lo, hi, j)
            if prox_mu > 0:
                l = l + _prox_term(tp, anchor, prox_mu)
            return l

        g = jax.grad(loss)(train)
        vel = jax.tree.map(lambda v, gi: momentum * v + gi, vel, g)
        train = jax.tree.map(lambda t, v: t - lr * v, train, vel)
        return train, vel

    return step


def make_prefix_forward(runner: BlockRunner, lo: int):
    """Jitted from-scratch prefix forward: z_{lo-1} = units[0, lo) over
    the embed output, under stop_gradient (pure buffering, no backward
    state)."""

    @jax.jit
    def fwd(params, batch):
        z = runner.embed(params, batch)
        if lo > 0:
            z = runner.apply_units(params, z, 0, lo)
        return jax.lax.stop_gradient(z)

    return fwd


def make_prefix_advance(runner: BlockRunner, lo: int, hi: int):
    """Jitted incremental advance: push a buffered z_{lo-1} through units
    [lo, hi) — the just-trained block (plus any never-trained gap) — to
    obtain z_{hi-1} without replaying the whole prefix."""

    @jax.jit
    def adv(params, z):
        return jax.lax.stop_gradient(runner.apply_units(params, z, lo, hi))

    return adv


class PrefixCache:
    """Buffered z_{lo-1} activations for one client's depth-wise update —
    the paper's prefix-once execution contract, made explicit.

    Per subproblem [lo, hi), :meth:`prepare` buffers the frozen-prefix
    output z_{lo-1} ONCE per distinct batch; every SGD step then reuses
    its buffer, so the per-step cost is one block-local forward+backward
    instead of a prefix replay.  Between subproblems the buffers are
    *advanced* through the just-trained units (``apply_units(z, lo_j,
    lo_{j+1})``) when the runner's prefix params are stable
    (``BlockRunner.prefix_stable``); otherwise (tied embeddings, whisper,
    hybrid) they are re-buffered from scratch — still once per
    subproblem, never once per step.  Total prefix forward cost per
    client: O(depth) per distinct batch, vs O(Σ_j lo_j · steps) on the
    recompute path.

    The held bytes (:meth:`buffered_bytes`) are the same quantity
    ``core.memory_model.ModelMemory.buffered_z_bytes`` prices and the
    systime latency model assumes — one accounting, asserted in
    tests/test_prefix_cache.py.
    """

    def __init__(self, runner: BlockRunner, jit_cache: Optional[dict] = None):
        self.runner = runner
        self._jits = jit_cache if jit_cache is not None else {}
        self.zs: Optional[list] = None   # one buffer per distinct batch
        self._lo: Optional[int] = None   # prefix depth of the buffers

    def _jit(self, key, build):
        return _jit_cache_probe(self._jits, key, build, name="prefix")

    def reset(self) -> None:
        """Drop the buffers (compiled prefix/advance fns are kept).
        ``client_update`` resets a caller-supplied cache on entry so a
        reused instance can never serve one client's activations to the
        next."""
        self.zs = None
        self._lo = None

    def prepare(self, params, batches, lo: int) -> list:
        """Buffer (or advance) z_{lo-1} for every distinct batch and
        return the buffer list, aligned with ``batches``.  The advance
        only runs FORWARD (lo > the buffered depth, the just-trained
        range); any other transition re-buffers from scratch."""
        obs = obs_active()
        if (self.zs is None or not self.runner.prefix_stable
                or lo < self._lo):
            fresh = self.zs is None
            fwd = self._jit(("prefix", lo),
                            lambda: make_prefix_forward(self.runner, lo))
            self.zs = [fwd(params, b) for b in batches]
            if obs is not None:
                # first buffering of an update vs a forced re-buffer
                # (unstable prefix / backward transition)
                obs.metrics.counter(
                    "prefix_cache_buffer" if fresh
                    else "prefix_cache_rebuffer").inc()
        elif lo != self._lo:
            adv = self._jit(("advance", self._lo, lo),
                            lambda: make_prefix_advance(self.runner,
                                                        self._lo, lo))
            self.zs = [adv(params, z) for z in self.zs]
            if obs is not None:
                obs.metrics.counter("prefix_cache_advance").inc()
        self._lo = lo
        if obs is not None:
            obs.metrics.gauge("prefix_cache_buffered_bytes").set(
                self.buffered_bytes())
        return self.zs

    def buffered_bytes(self) -> int:
        """Bytes currently held by the buffers (0 when nothing is
        buffered) — must equal the memory model's accounting."""
        if self.zs is None:
            return 0
        return sum(int(leaf.nbytes) for z in self.zs
                   for leaf in jax.tree.leaves(z))


def client_update(runner: BlockRunner, params, dec: Decomposition, batches,
                  *, lr: float = 0.1, momentum: float = 0.9,
                  local_steps: int = 1, prox_mu: float = 0.0,
                  step_cache: Optional[dict] = None,
                  prefix_cache: Union[bool, PrefixCache] = True):
    """Sequential depth-wise local update.  ``batches``: list of data
    batches cycled within each subproblem.  Returns updated full params.

    SGD with momentum per subproblem (momentum reset per block — each
    subproblem is its own optimization, paper Eq. 1).  ``prox_mu`` adds the
    FedProx proximal term ||w - w_global||^2 showing optimizer-agnosticism.
    Pass a shared ``step_cache`` dict across clients/rounds to reuse
    compiled block steps.

    ``prefix_cache`` selects the execution contract: ``True`` (default)
    buffers z_{lo-1} once per distinct batch per subproblem via
    :class:`PrefixCache` and advances it incrementally between
    subproblems — the paper's prefix-once claim; ``False`` re-runs the
    prefix inside every SGD step (the reference recompute path).  Pass a
    :class:`PrefixCache` instance to inspect the buffers afterwards.
    Both paths produce the same params up to float reassociation.
    """
    step_cache = step_cache if step_cache is not None else {}
    cache: Optional[PrefixCache] = None
    if isinstance(prefix_cache, PrefixCache):
        cache = prefix_cache
        cache.reset()      # never serve a previous client's activations
    elif prefix_cache:
        cache = PrefixCache(runner, jit_cache=step_cache)

    obs = obs_active()
    for j, (lo, hi) in enumerate(dec.blocks):
        block_span = None if obs is None else \
            obs.tracer.begin("block", lo=lo, hi=hi, j=j)
        zs = cache.prepare(params, batches, lo) if cache is not None \
            else None
        train = runner.split(params, lo, hi)
        # the FedProx anchor aliases the split views (cheap, never
        # donated); the (train, vel) carry gets private buffers when the
        # backend honors donation, so the step can update it in place
        # without invalidating ``params``' leaves
        anchor = jax.tree.map(jnp.asarray, train)
        if donation_supported():
            train = jax.tree.map(jnp.copy, train)
        vel = jax.tree.map(jnp.zeros_like, train)

        key = ("buffered" if cache is not None else "recompute",
               lo, hi, j, lr, momentum, prox_mu)
        make = make_buffered_block_step if cache is not None \
            else make_block_step
        audit = None
        if obs is not None and obs.audit is not None:
            step_args = (params, train, vel, anchor) \
                + ((zs[0],) if cache is not None else ()) + (batches[0],)
            audit = (lambda fn, a=step_args, lo=lo, hi=hi:
                     obs.audit.audit_block_step(
                         fn, a, family=runner.family, lo=lo, hi=hi,
                         variant="buffered" if cache is not None
                         else "recompute", n_batches=len(batches)))
        step = _jit_cache_probe(
            step_cache, key,
            lambda: make(runner, lo, hi, j, lr=lr, momentum=momentum,
                         prox_mu=prox_mu),
            name="block_step", audit=audit)

        for _ in range(local_steps):
            if cache is not None:
                for z_in, batch in zip(zs, batches):
                    train, vel = step(params, train, vel, anchor, z_in,
                                      batch)
            else:
                for batch in batches:
                    train, vel = step(params, train, vel, anchor, batch)
        params = runner.merge(params, train, lo=lo, hi=hi)
        if block_span is not None:
            obs.tracer.end(block_span)

    return params


def full_model_loss(runner: BlockRunner, params, batch):
    """End-to-end loss through all units (for eval / FedAvg baselines)."""
    z = runner.embed(params, batch)
    z = runner.apply_units(params, z, 0, runner.n_units)
    return runner.head_loss(params, z, batch, runner.n_units - 1)


# --------------------------------------------------------------------------
# stacked (vmap-over-clients) execution — substrate of VectorizedScheduler
# --------------------------------------------------------------------------
def broadcast_tree(tree, group: int):
    """Stack ``tree`` along a new leading client axis of size ``group``
    (broadcast views: no copy until XLA materializes them)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(jnp.asarray(x),
                                   (group,) + jnp.shape(x)), tree)


def unstack_tree(tree, group: int):
    """Split a leading client axis back into per-client pytrees."""
    return [jax.tree.map(lambda x: x[i], tree) for i in range(group)]


def batch_signature(batches) -> tuple:
    """Shape/dtype signature of one client's batch list; two clients are
    stackable iff their signatures are equal."""
    return tuple(
        tuple((tuple(jnp.shape(leaf)), str(getattr(leaf, "dtype", None)))
              for leaf in jax.tree.leaves(b)) for b in batches)


def stackable(batches_per_client) -> bool:
    """True when every client's batch list can be stacked into one
    ``(clients, steps, ...)`` array pytree (same count, shapes, dtypes)."""
    return len({batch_signature(b) for b in batches_per_client}) == 1


def stack_batches(batches_per_client):
    """Stack per-client batch lists into a ``(clients, batches, ...)``
    pytree: client order is preserved on axis 0, the per-round batch list
    on axis 1 (the local-epoch repetition happens INSIDE the compiled
    update via ``step % n_batches`` indexing, so each distinct batch —
    and its buffered z_{lo-1} prefix activation — is stored once, not
    once per epoch)."""
    per_client = [jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
                  for batches in batches_per_client]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_client)


# XLA:CPU full unroll bound: beyond this many SGD steps per block, compile
# size would grow without runtime benefit and the loop falls back to a
# partially-unrolled scan (XLA:CPU runs convs inside rolled loops ~4x
# slower than unrolled — layouts can't specialize — hence unroll at all)
MAX_UNROLL_STEPS = 32
SCAN_UNROLL = 8


def _unroll_bounds(backend: str):
    """``(full-unroll step bound, scan unroll)`` on ``backend``.
    Off the CPU the steps run as a rolled scan: the TPU compile of an
    unrolled group update takes ~4x longer (134 s vs 31 s for a 3-client
    full-width PreResNet-20 group of 12 steps, compiled for a v5e), and
    each new group shape pays it again."""
    if backend == "cpu":
        return MAX_UNROLL_STEPS, SCAN_UNROLL
    return 0, 1


def run_local_steps(step, carry, batches, local_steps: int):
    """Run ``local_steps`` epochs of ``step(carry, batch) -> carry`` over
    a stacked batch axis, inside a trace.  On XLA:CPU short step counts
    fully unroll with static ``s % n_batches`` slices — epoch repeats
    become the SAME subgraph, so XLA CSE dedupes anything that only
    depends on the batch; long ones, and every count elsewhere, use a
    scan over a ``step % n_batches`` index vector (a dynamic gather per
    step — no materialized ``local_steps`` concatenation of the data or
    of any buffered activations riding along in ``batches``) to bound
    compile size (:func:`_unroll_bounds`)."""
    n_batches = jax.tree.leaves(batches)[0].shape[0]
    n_steps = local_steps * n_batches
    max_unroll, scan_unroll = _unroll_bounds(jax.default_backend())
    if n_steps <= max_unroll:
        for s in range(n_steps):
            batch = jax.tree.map(lambda x, i=s % n_batches: x[i], batches)
            carry = step(carry, batch)
        return carry
    idx = jnp.arange(n_steps, dtype=jnp.int32) % n_batches

    def body(c, i):
        b = jax.tree.map(lambda x: x[i], batches)
        return step(c, b), None

    carry, _ = jax.lax.scan(body, carry, idx, unroll=scan_unroll)
    return carry


def make_group_update(runner: BlockRunner, blocks, *, lr: float,
                      momentum: float, local_steps: int = 1,
                      prox_mu: float = 0.0, prefix_cache: bool = True):
    """Jitted group update: ``jax.vmap`` over the client axis of an
    entire depth-wise local update (all blocks, all SGD steps).  One
    dispatch covers the whole group's round — vs. clients x blocks x
    steps dispatches on the sequential path.

    ``blocks`` is the shared ``Decomposition.blocks`` tuple; momentum and
    the FedProx anchor reset per block, like :func:`client_update`, and
    steps visit ``local_steps`` repetitions of the batch axis in the same
    order as the sequential ``for local_steps: for batch`` loop.

    With ``prefix_cache`` (default), the buffered z_{lo-1} lives in the
    stacked trace: per subproblem it is computed once per distinct batch
    (vmapped over the batch axis) and threaded through
    :func:`run_local_steps` alongside the data, so each SGD step — and
    in particular every iteration of the long-step-count *scan*, where
    XLA CSE cannot hoist loop-invariant prefix work — runs only the
    block-local forward+backward.  Between subproblems the buffers
    advance through the just-trained units (see :class:`PrefixCache` for
    the ``prefix_stable`` contract).  The stacked params argument is
    donated, so the broadcast input buffer is reused for the outputs
    rather than copied each dispatch.
    """

    def sgd_step(params, train, vel, anchor, z_in, batch, lo, hi, j):
        def loss(tp):
            if z_in is None:
                z = runner.embed(params, batch)
                if lo > 0:
                    z = runner.apply_units(params, z, 0, lo)
            else:
                z = z_in
            l = block_loss_fn(runner, params, tp, z, batch, lo, hi, j)
            if prox_mu > 0:
                l = l + _prox_term(tp, anchor, prox_mu)
            return l

        g = jax.grad(loss)(train)
        vel = jax.tree.map(lambda v, gi: momentum * v + gi, vel, g)
        train = jax.tree.map(lambda t, v: t - lr * v, train, vel)
        return train, vel

    def one_client(params, batches):
        zs, prev_lo = None, None
        for j, (lo, hi) in enumerate(blocks):
            if prefix_cache:
                if zs is None or not runner.prefix_stable:
                    fwd = make_prefix_forward(runner, lo)
                    zs = jax.vmap(fwd, in_axes=(None, 0))(params, batches)
                elif lo != prev_lo:
                    adv = make_prefix_advance(runner, prev_lo, lo)
                    zs = jax.vmap(adv, in_axes=(None, 0))(params, zs)
                prev_lo = lo
            train = runner.split(params, lo, hi)
            anchor = train
            vel = jax.tree.map(jnp.zeros_like, train)
            if prefix_cache:
                train, vel = run_local_steps(
                    lambda c, x, lo=lo, hi=hi, j=j, a=anchor: sgd_step(
                        params, c[0], c[1], a, x[0], x[1], lo, hi, j),
                    (train, vel), (zs, batches), local_steps)
            else:
                train, vel = run_local_steps(
                    lambda c, b, lo=lo, hi=hi, j=j, a=anchor: sgd_step(
                        params, c[0], c[1], a, None, b, lo, hi, j),
                    (train, vel), batches, local_steps)
            params = runner.merge(params, train, lo=lo, hi=hi)
        return params

    return jax.jit(jax.vmap(one_client), donate_argnums=donate(0))


def group_update_for(runner: BlockRunner, dec: Decomposition, *,
                     lr: float = 0.1, momentum: float = 0.9,
                     local_steps: int = 1, prox_mu: float = 0.0,
                     step_cache: Optional[dict] = None,
                     prefix_cache: bool = True):
    """The cached jitted group update for one decomposition — the exact
    callable :func:`client_update_batched` dispatches, exposed so mesh
    executors (``fl.scale.executor.ShardedScheduler``) can wrap the SAME
    compiled function in ``shard_map`` instead of rebuilding it (one
    cache key, one compile, identical lanes on every path)."""
    step_cache = step_cache if step_cache is not None else {}
    key = (dec.blocks, lr, momentum, local_steps, prox_mu,
           bool(prefix_cache))
    return _jit_cache_probe(
        step_cache, key,
        lambda: make_group_update(runner, dec.blocks, lr=lr,
                                  momentum=momentum,
                                  local_steps=local_steps, prox_mu=prox_mu,
                                  prefix_cache=bool(prefix_cache)),
        name="group")


def client_update_batched(runner: BlockRunner, params, dec: Decomposition,
                          batches_per_client, *, lr: float = 0.1,
                          momentum: float = 0.9, local_steps: int = 1,
                          prox_mu: float = 0.0,
                          step_cache: Optional[dict] = None,
                          prefix_cache: bool = True):
    """Depth-wise local updates for a GROUP of clients sharing one
    decomposition, as a single stacked computation.

    Same contract as calling :func:`client_update` once per client (the
    broadcast global ``params`` is the start point for everyone; only the
    data differs), modulo float associativity of the batched convolutions.
    Returns a list of per-client updated full param trees, in the order of
    ``batches_per_client``.  Pass a shared ``step_cache`` so one compiled
    group update serves every round (jit re-specializes per group size).
    ``prefix_cache`` selects the same execution contract as in
    :func:`client_update`; the donated stacked-params input is always a
    fresh broadcast buffer, never the caller's tree.
    """
    update = group_update_for(runner, dec, lr=lr, momentum=momentum,
                              local_steps=local_steps, prox_mu=prox_mu,
                              step_cache=step_cache,
                              prefix_cache=prefix_cache)
    group = len(batches_per_client)
    out = update(broadcast_tree(params, group),
                 stack_batches(batches_per_client))
    return unstack_tree(out, group)
