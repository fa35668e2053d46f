import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

Proves the distribution config is coherent without hardware:

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b \
        --shape train_4k [--multi-pod] [--fedepth-block LO:HI] [--out d.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init); it gives this process 512 host placeholder
devices so ``jax.make_mesh`` can build the production meshes.  Smoke
tests and benchmarks never import this module.
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPE_BY_NAME, SHAPES
from repro.configs.shapes import input_specs, shape_applicable
from repro.launch import sharding, steps
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.roofline import analysis


def mesh_devices(multi_pod: bool) -> int:
    return 512 if multi_pod else 256


MICRO_TOKENS = 8192  # target per-device tokens per microbatch


def default_accum(cfg, shape, mesh) -> int:
    """Grad-accumulation steps so one microbatch's per-device activations
    fit HBM (65k tokens/device at d=4096 cannot — see DESIGN.md §5)."""
    if shape.mode != "train":
        return 1
    bshards = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names and shape.global_batch % (
                bshards * mesh.shape[ax]) == 0:
            bshards *= mesh.shape[ax]
    per_dev_tokens = (shape.global_batch // bshards) * shape.seq_len
    accum = max(1, per_dev_tokens // MICRO_TOKENS)
    while shape.global_batch % (accum * bshards):
        accum -= 1
    return max(1, accum)


def depth_scaled(cfg, n_units: int):
    """Config with depth reduced to n_units finest-decomposition units
    (same widths/vocab/experts) — the repeating cell for cost
    extrapolation."""
    import dataclasses
    if cfg.family == "hybrid":
        return dataclasses.replace(cfg,
                                   num_layers=n_units * cfg.hybrid_attn_every)
    if cfg.is_encoder_decoder:
        return dataclasses.replace(cfg, encoder_layers=n_units,
                                   num_layers=n_units)
    if cfg.family == "ssm":
        return dataclasses.replace(cfg, num_layers=n_units)
    return dataclasses.replace(cfg, num_layers=n_units * cfg.moe_every)


def depth_units(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.hybrid_attn_every
    if cfg.is_encoder_decoder:
        return cfg.num_layers  # enc and dec scale together
    if cfg.family == "ssm":
        return cfg.num_layers
    return cfg.num_layers // cfg.moe_every


def _lower_costing(cfg, shape, mesh, fsdp=None, no_remat=False,
                   decode_tokens=1):
    """Lower + compile the costing variant: chunked ref kernels with ALL
    scans unrolled (common.unroll_scans context) and accum=1, so
    cost_analysis sees every loop body.  ``fsdp`` is pinned to the FULL
    config's policy (a depth-1 llama4 falls under the FSDP param
    threshold and would otherwise lower under a different sharding
    regime, breaking extrapolation).  Returns (flops, bytes, colls)."""
    lm = build(cfg)
    params_shape = steps.abstract_params(lm)
    pspecs = sharding.to_named(
        sharding.param_specs(cfg, params_shape, mesh, fsdp=fsdp), mesh)
    bspecs = sharding.to_named(sharding.batch_specs(cfg, shape, mesh), mesh)
    specs = input_specs(cfg, shape)
    import contextlib
    from repro.models import common as model_common
    step_fn, _ = steps.step_for_shape(lm, shape, kernel_force="ref",
                                      accum_steps=1,
                                      decode_tokens=decode_tokens)
    remat_ctx = model_common.disable_remat() if no_remat \
        else contextlib.nullcontext()
    with jax.set_mesh(mesh), model_common.unroll_scans(), remat_ctx:
        if shape.mode == "train":
            opt_shape = steps.abstract_opt_state(params_shape)
            jitted = jax.jit(step_fn, in_shardings=(pspecs, pspecs, bspecs),
                             out_shardings=(pspecs, pspecs, None))
            compiled = jitted.lower(params_shape, opt_shape, specs).compile()
        elif shape.mode == "prefill":
            compiled = jax.jit(step_fn, in_shardings=(pspecs, bspecs),
                               out_shardings=None).lower(
                params_shape, specs).compile()
        else:
            compiled = jax.jit(
                step_fn, in_shardings=(pspecs, bspecs),
                out_shardings=(None, bspecs["cache"])).lower(
                params_shape, specs).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    try:
        colls = analysis.collective_bytes(compiled.as_text())
    except Exception:
        colls = {}
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), colls)


def costing_extrapolate(cfg, shape, mesh, fsdp=None,
                        no_remat=False, decode_tokens=1) -> dict:
    """Depth-1/depth-2 linear extrapolation of per-device cost terms.

    XLA cost_analysis counts while-loop bodies once (verified), so the
    full-depth scanned lowering undercounts by the trip count.  The
    repeating depth cell is measured directly: cost(U) = c1 + (U-1)*(c2-c1).
    Residual undercount: the per-timestep recurrence inside rwkv6/mamba2
    oracles (<2% of those archs' FLOPs — projections dominate) and the
    remaining accumulation loop (accum=1 here, none).
    """
    U = depth_units(cfg)
    fsdp = sharding.needs_fsdp(cfg) if fsdp is None else fsdp
    f1, b1, c1 = _lower_costing(depth_scaled(cfg, 1), shape, mesh, fsdp,
                                no_remat, decode_tokens)
    f2, b2, c2 = _lower_costing(depth_scaled(cfg, 2), shape, mesh, fsdp,
                                no_remat, decode_tokens)
    flops = f1 + (U - 1) * (f2 - f1)
    byts = b1 + (U - 1) * (b2 - b1)
    kinds = set(c1) | set(c2)
    colls = {k: c1.get(k, 0) + (U - 1) * (c2.get(k, 0) - c1.get(k, 0))
             for k in kinds}
    return {"flops": flops, "bytes": byts, "collectives": colls,
            "cell": {"f1": f1, "f2": f2, "b1": b1, "b2": b2}}


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               fedepth_block=None, accum_steps=None, costing: bool = True,
               fsdp=None, no_remat: bool = False, force_window: int = 0,
               buffered_z: bool = False, ws_decode: bool = False,
               decode_tokens: int = 1, moe_ep: bool = False,
               verbose: bool = True) -> dict:
    import dataclasses
    cfg = get_config(arch)
    if force_window:
        # beyond-assignment path: run a dense arch at long context by
        # switching it to sliding-window attention (bounded ring KV cache)
        cfg = dataclasses.replace(cfg, sliding_window=force_window)
    shape = SHAPE_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    lm = build(cfg)

    params_shape = steps.abstract_params(lm)
    pspecs = sharding.to_named(
        sharding.param_specs(cfg, params_shape, mesh, fsdp=fsdp), mesh)
    bspecs = sharding.to_named(sharding.batch_specs(cfg, shape, mesh), mesh)
    specs = input_specs(cfg, shape)

    if accum_steps is None:
        accum_steps = default_accum(cfg, shape, mesh)
    if buffered_z and shape.mode == "train":
        # the paper's z buffering: block step consumes the stored prefix
        # activation instead of tokens
        import jax.numpy as jnp_
        specs = dict(specs)
        del specs["tokens"]
        specs["z_in"] = jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len, cfg.d_model), jnp_.bfloat16)
        bsp = dict(sharding.batch_specs(cfg, shape, mesh))
        from jax.sharding import PartitionSpec as P_
        bsp.pop("tokens", None)
        baxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        b = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
        bsp["z_in"] = P_(b, None, None)
        bspecs = sharding.to_named(bsp, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    micro_shardings = None
    if accum_steps > 1:
        # to_micro moves the split accum axis to dim 0; every original dim
        # (incl. the now-smaller batch dim) keeps its sharding
        micro_shardings = jax.tree.map(
            lambda ns: NamedSharding(mesh, P(None, *ns.spec)), bspecs)
    step_fn, needs_opt = steps.step_for_shape(
        lm, shape, fedepth_block=fedepth_block, accum_steps=accum_steps,
        grad_shardings=pspecs, microbatch_shardings=micro_shardings,
        buffered_z=buffered_z, decode_tokens=decode_tokens)

    import contextlib
    from repro.models import common as model_common
    remat_ctx = model_common.disable_remat() if no_remat \
        else contextlib.nullcontext()
    ws_ctx = model_common.weight_stationary_decode() if ws_decode \
        else contextlib.nullcontext()
    ep_ctx = model_common.ep_moe() if moe_ep else contextlib.nullcontext()
    with jax.set_mesh(mesh), remat_ctx, ws_ctx, ep_ctx:
        if shape.mode == "train":
            if fedepth_block is not None:
                # momentum exists only for the trained block
                from repro.core import blockwise
                runner = blockwise.lm_runner(lm)
                train_shape = jax.eval_shape(
                    lambda p: runner.split(p, *fedepth_block), params_shape)
                opt_shape = steps.abstract_opt_state(train_shape)
                opt_specs = sharding.to_named(
                    sharding.param_specs(cfg, train_shape, mesh), mesh)
            else:
                opt_shape = steps.abstract_opt_state(params_shape)
                opt_specs = pspecs
            jitted = jax.jit(
                step_fn,
                in_shardings=(pspecs, opt_specs, bspecs),
                out_shardings=(pspecs, opt_specs, None))
            lowered = jitted.lower(params_shape, opt_shape, specs)
        elif shape.mode == "prefill":
            jitted = jax.jit(step_fn, in_shardings=(pspecs, bspecs),
                             out_shardings=None)
            lowered = jitted.lower(params_shape, specs)
        else:  # decode
            cache_out_specs = bspecs["cache"]
            jitted = jax.jit(step_fn, in_shardings=(pspecs, bspecs),
                             out_shardings=(None, cache_out_specs))
            lowered = jitted.lower(params_shape, specs)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] lower={t_lower:.1f}s "
              f"compile={t_compile:.1f}s")
        print(mem)
        print({k: v for k, v in (compiled.cost_analysis() or {}).items()
               if k in ("flops", "bytes accessed")})

    roof = analysis.analyze(compiled, None, cfg, shape, mesh_name,
                            mesh_devices(multi_pod), arch)
    if costing and fedepth_block is None:
        cost = costing_extrapolate(cfg, shape, mesh, fsdp=fsdp,
                                   no_remat=no_remat,
                                   decode_tokens=decode_tokens)
        roof.flops_per_device = cost["flops"]
        roof.bytes_per_device = cost["bytes"]
        roof.collectives_by_kind = cost["collectives"]
        roof.collective_bytes_per_device = float(
            sum(cost["collectives"].values()))
    elif costing:
        # block steps don't extrapolate linearly in total depth: cost the
        # EXACT step with every scan unrolled (prefix fwd + block fwd/bwd).
        # NOTE: a FRESH jax.jit — the first jit caches its traced lowering
        # and would ignore the unroll context.
        from repro.models import common as model_common
        remat_ctx2 = model_common.disable_remat() if no_remat \
            else contextlib.nullcontext()
        with jax.set_mesh(mesh), model_common.unroll_scans(), remat_ctx2:
            cost_fn, _ = steps.step_for_shape(
                lm, shape, fedepth_block=fedepth_block, kernel_force="ref",
                accum_steps=1, buffered_z=buffered_z)
            c_unrolled = jax.jit(
                cost_fn, in_shardings=(pspecs, opt_specs, bspecs),
                out_shardings=(pspecs, opt_specs, None)).lower(
                params_shape, opt_shape, specs).compile()
        cost = c_unrolled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        roof.flops_per_device = float(cost.get("flops", 0.0))
        roof.bytes_per_device = float(cost.get("bytes accessed", 0.0))
        colls = analysis.collective_bytes(c_unrolled.as_text())
        roof.collectives_by_kind = colls
        roof.collective_bytes_per_device = float(sum(colls.values()))
    out = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "ok", "lower_s": t_lower, "compile_s": t_compile,
           "fedepth_block": list(fedepth_block) if fedepth_block else None,
           "accum_steps": accum_steps,
           **roof.to_dict()}
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes"):
            out[f"mem_{attr}"] = int(getattr(mem, attr, 0))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=[s.name for s in SHAPES])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) on this process's mesh")
    ap.add_argument("--fedepth-block", default=None,
                    help="LO:HI unit range -> lower the FeDepth block step")
    ap.add_argument("--accum", type=int, default=None,
                    help="override grad-accumulation steps")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="force pure-TP sharding (perf variant for decode)")
    ap.add_argument("--no-remat", action="store_true",
                    help="disable per-unit rematerialization")
    ap.add_argument("--moe-ep", action="store_true",
                    help="explicit shard_map all-to-all expert parallelism")
    ap.add_argument("--decode-tokens", type=int, default=1,
                    help="decode N tokens per dispatch (amortizes "
                         "loop-invariant weight gathers)")
    ap.add_argument("--ws-decode", action="store_true",
                    help="weight-stationary decode (replicate activations "
                         "over data instead of gathering FSDP weights)")
    ap.add_argument("--fedepth-buffered", action="store_true",
                    help="block step consumes buffered z_in (paper's "
                         "frozen-then-pass buffering)")
    ap.add_argument("--force-window", type=int, default=0,
                    help="force sliding-window attention (dense arch at "
                         "long context)")
    ap.add_argument("--out", default=None, help="write JSON result here")
    args = ap.parse_args(argv)

    fb = None
    if args.fedepth_block:
        lo, hi = args.fedepth_block.split(":")
        fb = (int(lo), int(hi))

    results = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                try:
                    results.append(dryrun_one(arch, shape.name,
                                              multi_pod=args.multi_pod))
                except Exception as e:  # a failure here is a bug: report it
                    traceback.print_exc()
                    results.append({"arch": arch, "shape": shape.name,
                                    "status": "FAILED", "error": str(e)})
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required (or --all)")
        results.append(dryrun_one(args.arch, args.shape,
                                  multi_pod=args.multi_pod,
                                  fedepth_block=fb,
                                  accum_steps=args.accum,
                                  fsdp=(False if args.no_fsdp else None),
                                  no_remat=args.no_remat,
                                  force_window=args.force_window,
                                  buffered_z=args.fedepth_buffered,
                                  ws_decode=args.ws_decode,
                                  decode_tokens=args.decode_tokens,
                                  moe_ep=args.moe_ep))

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)

    failed = [r for r in results if r.get("status") == "FAILED"]
    print(f"\n{len(results)} combos: "
          f"{sum(r.get('status') == 'ok' for r in results)} ok, "
          f"{sum(r.get('status') == 'skipped' for r in results)} skipped, "
          f"{len(failed)} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
