#!/usr/bin/env python3
"""Smoke run of FeDepth training on a TPU, through the library API.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: Phase A's cohort on a mesh
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # reduced, on the CPU

Phase A trains full PreResNet-20 (3-3-3 stages, widths 16/32/64, 32 px,
10 classes) over 100 synthetic clients at the ``SimConfig`` defaults (10
clients a round, batch 64, ``mem_batch`` 128, fair budgets) for two
FeDepth rounds, once with the sequential and once with the vectorized
scheduler, at full f32 matmul precision (``CHECK_PRECISION``).  It
checks that loss and accuracy are finite and that the two schedulers'
final params agree (``PARAM_DIST_TOL``).

Phase B runs one FeDepth round of mamba2-370m at its published widths (2
clients, one budget) with automatic kernel dispatch, at the default
precision.  It checks that the compiled block step holds Pallas kernels
(``tpu_custom_call``) and that one block loss, at f32 precision, matches
the jnp reference dispatch on the same device.

``--chips 4`` runs only Phase A's first round, three ways: the sharded
scheduler with host aggregation, the sharded scheduler with on-mesh
aggregation, and the vectorized scheduler on one device as reference.
It checks that the params agree and that the sharded run spread its
groups beyond the default device.

Each phase prints one JSON line: compile seconds, wall seconds of each
round (the last one is after warm-up, but may still compile a cohort
group the first round did not have), accuracy and peak device bytes.
The last line of stdout is ``{"ok": true, "device": {...}}``.  A failed
check raises, so the script exits non-zero without that line.  Without
a TPU it exits non-zero unless ``--rehearse`` is given; that flag shrinks
every size so the phases run on the CPU, with the Pallas kernel bodies
in interpret mode.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, get_reduced_config  # noqa: E402
from repro.configs import preresnet20  # noqa: E402
from repro.core import blockwise  # noqa: E402
from repro.core.decomposition import decompose  # noqa: E402
from repro.fl import (RoundEngine, SimConfig, build_context,  # noqa: E402
                      build_federated, get_strategy)
from repro.fl.engine import scenario_budgets  # noqa: E402
from repro.fl.sampling import VectorizedScheduler  # noqa: E402
from repro.fl.scale import ShardedScheduler  # noqa: E402
from repro.fl.seq import build_lm_context, build_seq_data  # noqa: E402
from repro.fl.strategies.fedepth import FedepthStrategy  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build, resnet  # noqa: E402

# Schedulers agree up to float reassociation: a vmapped group's convs
# sum in another order than one client's.  ReLU and momentum amplify
# that through Phase A's 12 SGD steps per block per round; on a v5e the
# final params differ by up to 3.1e-4, far past the elementwise
# tolerance tests/test_vectorized.py sets for two steps of a reduced
# model.  So agreement is measured against how far training moved the
# params: ||a - b|| / ||b - init||.  A scheduler that drops or
# misroutes a client's or a block's update misses by a sizable share of
# that distance.
PARAM_DIST_TOL = 0.05
# Phase B: |loss(auto dispatch) - loss(ref dispatch)| <= LOSS_RTOL * loss(ref)
LOSS_RTOL = 1e-3
# Matmul precision of every compared computation.  A TPU runs f32 matmuls
# and convolutions as one bf16 pass by default; comparing at full f32
# keeps that rounding out of what the comparison measures.
CHECK_PRECISION = "float32"
# Phase B sizes: the compiled block steps of this decomposition take at
# most ~7.2 GiB (arguments + temporaries) in a v5e compile rehearsal
LM_BATCH, LM_SEQ, LM_TEST_SEQS = 4, 1024, 8

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from its
    own monitoring events.  A persistent-cache hit skips the backend
    compile, so a warm second run reads lower."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.total += duration


def peak_bytes(device=None):
    stats = (device or jax.devices()[0]).memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _flat(tree) -> np.ndarray:
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree.leaves(tree)])


def param_distance(got, want, init) -> float:
    """``||got - want|| / ||want - init||``: how far apart two trained
    models are, as a share of the distance training moved ``want``."""
    g, w, i = _flat(got), _flat(want), _flat(init)
    return float(np.linalg.norm(g - w) / np.linalg.norm(w - i))


def check_distance(dist: float, what: str) -> None:
    if not dist <= PARAM_DIST_TOL:
        raise AssertionError(f"{what}: params {dist:.3g} of the training "
                             f"distance apart, over {PARAM_DIST_TOL}")


# --------------------------------------------------------------- Phase A
def paper_setup(rehearse: bool):
    """PreResNet-20 on the paper's protocol, or a reduced stand-in."""
    if rehearse:
        cfg = preresnet20.reduced(image_size=16)
        data = build_federated(num_clients=8, n_train=320, n_test=80,
                               image_size=16)
        sim = SimConfig(rounds=1, participation=0.5, batch_size=16)
    else:
        cfg = preresnet20.CONFIG
        data = build_federated(num_clients=100, image_size=32)
        sim = SimConfig(rounds=2)
    return cfg, data, sim


def resnet_test_loss(cfg, params, data, n: int = 1000) -> float:
    x = jnp.asarray(data.x_test[:n])
    y = jnp.asarray(data.y_test[:n])
    logits = resnet.apply(params, cfg, x)
    return float(-jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(logits), y[:, None], axis=-1)))


def run_engine(strategy, ctx, scheduler, clock):
    c0, t0 = clock.total, time.perf_counter()
    state, hist = RoundEngine(strategy, ctx, scheduler=scheduler).run(
        eval_every=1)
    jax.block_until_ready(state)
    return state, hist, {
        "wall_s": time.perf_counter() - t0,
        "compile_s": clock.total - c0,
        "round_s": [h.seconds for h in hist],
        "accuracy": hist[-1].accuracy,
    }


def phase_a(rehearse: bool, clock: CompileClock) -> None:
    cfg, data, sim = paper_setup(rehearse)
    states, record = {}, {"phase": "A", "model": cfg.name,
                          "image_size": cfg.image_size,
                          "clients": len(data.client_indices),
                          "cohort": max(1, int(round(
                              sim.participation * len(data.client_indices)))),
                          "rounds": sim.rounds,
                          "matmul_precision": CHECK_PRECISION}
    init = get_strategy("fedepth").init_state(
        build_context(data, sim, model_cfg=cfg))
    with jax.default_matmul_precision(CHECK_PRECISION):
        for sched in ("sequential", "vectorized"):
            state, _, rec = run_engine(
                get_strategy("fedepth"),
                build_context(data, sim, model_cfg=cfg), sched, clock)
            rec["test_loss"] = resnet_test_loss(cfg, state, data)
            states[sched] = state
            record[sched] = rec
    seq, vec = states["sequential"], states["vectorized"]
    record["max_abs_diff"] = max_abs_diff(seq, vec)
    record["param_distance"] = param_distance(vec, seq, init)
    record["param_distance_tol"] = PARAM_DIST_TOL
    record["peak_bytes_in_use"] = peak_bytes()
    emit(record)
    for sched in states:
        if not (np.isfinite(record[sched]["test_loss"])
                and np.isfinite(record[sched]["accuracy"])):
            raise AssertionError(f"Phase A {sched}: non-finite loss or "
                                 f"accuracy")
    check_distance(record["param_distance"], "vectorized vs sequential")


# --------------------------------------------------------------- Phase B
def block_loss_fn(runner, lo: int, hi: int, j: int):
    """Jitted loss of block ``j`` = units [lo, hi) — the value its SGD step
    differentiates — from the params and one batch."""

    @jax.jit
    def loss(params, batch):
        z = runner.embed(params, batch)
        if lo > 0:
            z = runner.apply_units(params, z, 0, lo)
        return blockwise.block_loss_fn(runner, params,
                                       runner.split(params, lo, hi), z,
                                       batch, lo, hi, j)

    return loss


def phase_b(rehearse: bool, on_chip: bool, clock: CompileClock) -> None:
    if rehearse:
        cfg, B, T, n_test = get_reduced_config("mamba2-370m"), 2, 16, 4
    else:
        cfg, B, T, n_test = (get_config("mamba2-370m"), LM_BATCH, LM_SEQ,
                             LM_TEST_SEQS)
    # automatic dispatch on the chip; off it, the same Pallas kernel
    # bodies in interpret mode
    force = None if on_chip else "interpret"
    data = build_seq_data(2, n_per_client=B, n_test=n_test,
                          vocab_size=cfg.vocab_size, seq_len=T)
    sim = SimConfig(rounds=1, participation=1.0, batch_size=B, mem_batch=B)
    ctx = build_lm_context(data, sim, cfg, kernel_force=force)
    # one budget, the fair scenario's x1/2 tier, so one decomposition
    budget = scenario_budgets(ctx.mem, [0.5])[0]
    dec = decompose(ctx.mem, int(budget))
    ctx = dataclasses.replace(ctx, ratios=np.full(2, 0.5),
                              budgets=np.full(2, budget), decomps=[dec, dec])

    strategy = get_strategy("fedepth")
    state, _, rec = run_engine(strategy, ctx, None, clock)

    lo, hi = dec.blocks[-1]
    j = len(dec.blocks) - 1
    batch = data.client_batch(0, B, np.random.default_rng(0))
    with jax.default_matmul_precision(CHECK_PRECISION):
        loss_auto = float(block_loss_fn(strategy.runner, lo, hi, j)(state,
                                                                    batch))
        ref_runner = blockwise.lm_runner(build(cfg), kernel_force="ref")
        loss_ref = float(block_loss_fn(ref_runner, lo, hi, j)(state, batch))

    custom_calls = None
    if on_chip:
        step = ctx.caches["fedepth_step"][
            ("buffered", lo, hi, j, sim.lr, sim.momentum, 0.0)]
        train = strategy.runner.split(state, lo, hi)
        z = blockwise.make_prefix_forward(strategy.runner, lo)(state, batch)
        custom_calls = step.lower(state, train, train, train, z,
                                  batch).compile().as_text().count(
                                      "tpu_custom_call")
    rec.update(phase="B", model=cfg.name, batch=B, seq_len=T,
               blocks=[list(b) for b in dec.blocks],
               budget_mib=float(budget) / 2**20,
               kernel_dispatch="auto" if force is None else force,
               block_step_tpu_custom_calls=custom_calls,
               block=[lo, hi], loss=loss_auto, loss_ref=loss_ref,
               loss_rtol=LOSS_RTOL, loss_precision=CHECK_PRECISION,
               peak_bytes_in_use=peak_bytes())
    emit(rec)
    if not (np.isfinite(loss_auto) and np.isfinite(rec["accuracy"])):
        raise AssertionError("Phase B: non-finite loss or accuracy")
    if abs(loss_auto - loss_ref) > LOSS_RTOL * abs(loss_ref):
        raise AssertionError(f"Phase B: block loss {loss_auto} vs ref "
                             f"{loss_ref}, over rtol {LOSS_RTOL}")
    if on_chip and not custom_calls:
        raise AssertionError("Phase B: the compiled block step holds no "
                             "Pallas kernel (tpu_custom_call)")


# ------------------------------------------------------------ four chips
def phase_mesh(rehearse: bool, clock: CompileClock) -> None:
    """Phase A's first-round cohort on the data mesh, against one device.
    One round: each of the three ways compiles its own programs (one per
    group shape and device), and compiling is most of the time here."""
    cfg, data, sim = paper_setup(rehearse)
    sim = dataclasses.replace(sim, rounds=1)
    devices = jax.devices()

    def run(scheduler):
        # masked aggregation is what lets aggregate="mesh" fuse the fold
        return run_engine(FedepthStrategy(masked_aggregation=True),
                          build_context(data, sim, model_cfg=cfg),
                          scheduler, clock)

    init = FedepthStrategy().init_state(build_context(data, sim,
                                                      model_cfg=cfg))
    ways = (("vectorized_one_device", VectorizedScheduler()),
            ("sharded_host", ShardedScheduler()),
            ("sharded_mesh", ShardedScheduler(aggregate="mesh")))
    states, recs = {}, {}
    for name, scheduler in ways:
        with jax.default_matmul_precision(CHECK_PRECISION):
            state, _, rec = run(scheduler)
        rec["peak_bytes_per_device"] = [peak_bytes(d) for d in devices]
        if states:
            ref = states["vectorized_one_device"]
            rec["max_abs_diff"] = max_abs_diff(state, ref)
            rec["param_distance"] = param_distance(state, ref, init)
        # one line per way, so a cut run still shows what finished
        emit({"phase": "mesh", "way": name, "model": cfg.name,
              "devices": len(devices), "matmul_precision": CHECK_PRECISION,
              "param_distance_tol": PARAM_DIST_TOL, **rec})
        states[name], recs[name] = state, rec
    ref_rec, host_rec, mesh_rec = recs.values()

    # Nothing piled onto the default device: the reference ran on it
    # alone, so the host-aggregated sharded run must raise some other
    # device's peak.  Not every device: a round may hold fewer stackable
    # groups than there are devices, and an idle device still reports a
    # small nonzero peak.  The CPU keeps no memory stats (rehearsal).
    if not rehearse and not any(
            h > r for h, r in zip(host_rec["peak_bytes_per_device"][1:],
                                  ref_rec["peak_bytes_per_device"][1:])):
        raise AssertionError("sharded scheduler ran every group on the "
                             "default device")
    for rec in recs.values():
        if not np.isfinite(rec["accuracy"]):
            raise AssertionError("mesh phase: non-finite accuracy")
    check_distance(host_rec["param_distance"],
                   "sharded (host aggregation) vs vectorized")
    check_distance(mesh_rec["param_distance"],
                   "sharded (mesh aggregation) vs vectorized")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="1: phases A and B; 4: the sharded cohort only")
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced sizes, allowed without a TPU")
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {device.platform!r});"
              f" --rehearse runs the reduced phases on it", file=sys.stderr)
        return 2
    if jax.device_count() != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{jax.device_count()} devices", file=sys.stderr)
        return 2

    enable_compile_cache()
    clock = CompileClock()
    if args.chips == 1:
        phase_a(args.rehearse, clock)
        phase_b(args.rehearse, on_chip, clock)
    else:
        phase_mesh(args.rehearse, clock)
    emit({"ok": True, "device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": jax.device_count()}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
