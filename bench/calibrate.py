#!/usr/bin/env python3
"""Readings that set a cell's limits, taken in one process on the chip.

    python3 bench/calibrate.py --config <config> --traffic <mix> \
        --seeds 12 --control-seeds 3 --fault-seeds 3 [--first-seed N]

For each seed it builds the program as a run does, drives the compared
rounds, and reads the numbers of ``compare.py`` against the float32
reference at ``highest`` precision (the lower readings).  On the first
``--control-seeds`` seeds it also reads the control, the reference in
bfloat16 put in the program's place, and on the first ``--fault-seeds``
the fault of a half batch: the reference in the program's place with
half of every batch left out (the upper readings).  One JSON line per
reading on standard output.  The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def cell_for(config: str, traffic: str):
    """A cell of ``config`` under the traffic mix ``traffic``, with no
    limits: it need not be in ``BENCHMARK.json``."""
    import harness
    return harness.Cell(
        name=f"{config}.{traffic}", config=config, chips=1,
        sizes=json.loads((harness.BENCH / "configs"
                          / f"{config}.json").read_text()),
        traffic=json.loads((harness.BENCH / "traffic"
                            / f"{traffic}.json").read_text()),
        limits={}, per_layer=[], end_to_end=[],
        model=harness.load_module(harness.BENCH / "configs" / f"{config}.py"),
        flops=harness.load_module(harness.BENCH / "flops" / f"{config}.py"))


def half_rows(batch):
    return {k: v[:len(v) // 2] for k, v in batch.items()}


def readings(cell, seeds, control_seeds, fault_seeds, *, require_tpu=True,
             kernel_force=None, emit=print):
    """``emit`` one dict per reading (``kind``: program, control_bf16,
    fault_half_batch) with the numbers of ``compare.py``."""
    import jax.numpy as jnp
    import harness
    from compare import numbers

    harness.check_device(cell.chips, require_tpu)
    caches = {}
    n = cell.traffic["compare_rounds"]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        bench = harness.Bench(cell, seed, kernel_force=kernel_force,
                              caches=caches)
        if i == 0:
            bench.warm_up()
        init, p_first, p_last, rounds = bench.compared_rounds(n)
        bench.close()
        r_first, r_last = harness.reference_params(cell, init, rounds)
        t1 = time.perf_counter()

        def emit_numbers(kind, first, last, **extra):
            got = numbers(cell.model.leaves, init, (first, r_first),
                          (last, r_last))
            emit({"cell": cell.name, "kind": kind, "seed": seed,
                  **{k: v for k, (v, _) in got.items()},
                  "leaves": {k: leaf for k, (_, leaf) in got.items()},
                  **extra})

        emit_numbers("program", p_first, p_last,
                     program_and_ref_s=t1 - t0)
        if i < control_seeds:
            c_first, c_last = harness.reference_params(
                cell, init, rounds, dtype=jnp.bfloat16, precision="default")
            emit_numbers("control_bf16", c_first, c_last)
        if i < fault_seeds:
            f_first, f_last = harness.reference_params(
                cell, init, rounds, rows=half_rows)
            emit_numbers("fault_half_batch", f_first, f_last)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_100_000_000)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    harness.use_checkout_cache()
    cell = cell_for(args.config, args.traffic)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    readings(cell, seeds, args.control_seeds, args.fault_seeds,
             emit=lambda r: print(json.dumps(r), flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
