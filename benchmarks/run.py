"""Benchmark harness: one entry per paper table/figure + infra reports.
Print ``name,us_per_call,derived`` CSV per benchmark.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]
    REPRO_BENCH_SCALE=full ...   # paper-scale rounds
"""
import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (async_sim, comm, faults, fig5_partial_training,
                        fig7_vit_finetune, kernel_microbench, obs_overhead,
                        prefix_cache, roofline_report, round_engine, scale,
                        seq_fastpath, table1_memory,
                        table2_budget_scenarios, table3_unbalanced)

BENCHES = {
    "table1_memory": table1_memory.main,
    "table2_budget_scenarios": table2_budget_scenarios.main,
    "table3_unbalanced": table3_unbalanced.main,
    "fig5_partial_training": fig5_partial_training.main,
    "fig7_vit_finetune": fig7_vit_finetune.main,
    "kernel_microbench": kernel_microbench.main,
    "seq_fastpath": seq_fastpath.main,
    "roofline_report": roofline_report.main,
    "round_engine": round_engine.main,
    "async_sim": async_sim.main,
    "prefix_cache": prefix_cache.main,
    "comm": comm.main,
    "scale": scale.main,
    "obs_overhead": obs_overhead.main,
    "faults": faults.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(BENCHES), default=None)
    args = ap.parse_args()
    enable_compile_cache()
    failed = []
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        print(f"\n=== {name} ===")
        try:
            fn()
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"\nFAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
