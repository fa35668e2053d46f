#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Prints the number of compiles inside the
window and every compared number beside its limit on standard error, and
the result as one JSON line, the last of standard output.  Exits non-zero,
with no result, when JAX finds no TPU or fewer chips than the cell asks
for, or when the checkout lacks the program.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import harness
    try:
        import repro  # the program under test, from this checkout only
        if ROOT / "src" not in Path(repro.__file__).resolve().parents:
            raise ImportError(f"repro comes from {repro.__file__}, not "
                              f"from this checkout")
        cell = harness.load_cell(args.workload)
        harness.check_device(cell.chips)
    except (ImportError, FileNotFoundError, KeyError,
            harness.NoDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.use_checkout_cache()

    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
