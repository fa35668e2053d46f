#!/usr/bin/env python3
"""Readings that set a cell's limits, taken in one process on the chip.

    python3 bench/calibrate.py --config <config> --traffic <mix> \
        --seeds 16 --control-seeds 4 --fault-seeds 4 [--first-seed N]

For each seed it builds the program as a run does, drives the compared
rounds, and reads the numbers of ``compare.py`` against the float32
reference at ``highest`` precision, each round of the reference run from
the program's params at that round's start (the lower readings).  On the
first ``--control-seeds`` seeds it also reads the control, the reference
in bfloat16 put in the program's place, and on the first
``--fault-seeds`` the fault of a half batch: the reference in the
program's place with half of every batch left out (the upper readings);
each of them too runs every round from the program's params at its
start.  One JSON line per reading on standard output, with the worst
round and each round's numbers.  The benchmark's own runs never run
this.
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
    from compare import by_round, worst

    harness.check_device(cell.chips, require_tpu)
    caches = {}
    n = cell.traffic["compare_rounds"]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        bench = harness.Bench(cell, seed, kernel_force=kernel_force,
                              caches=caches)
        if i == 0:
            bench.warm_up()
        params, rounds = bench.compared_rounds(n)
        bench.close()
        starts = params[:-1]
        refs = harness.reference_params(cell, starts, rounds)
        t1 = time.perf_counter()

        def emit_numbers(kind, progs, **extra):
            per = by_round(cell.model.leaves, starts, progs, refs)
            got = worst(per)
            emit({"cell": cell.name, "kind": kind, "seed": seed,
                  **{k: v for k, (v, _) in got.items()},
                  "worst": {k: where for k, (_, where) in got.items()},
                  "by_round": {k: [rd[k][0] for rd in per] for k in got},
                  **extra})

        emit_numbers("program", params[1:], program_and_ref_s=t1 - t0)
        if i < control_seeds:
            emit_numbers("control_bf16", harness.reference_params(
                cell, starts, rounds, dtype=jnp.bfloat16,
                precision="default"))
        if i < fault_seeds:
            emit_numbers("fault_half_batch", harness.reference_params(
                cell, starts, rounds, rows=half_rows))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--fault-seeds", type=int, default=4)
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
