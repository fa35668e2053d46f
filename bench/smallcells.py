"""The cells of ``BENCHMARK.json`` at reduced sizes, for the benchmark's
own tests on the CPU.  Each configuration's reduced sizes, the traffic it
is tested under and its kernel mode are in
``configs/<config>.small.json``."""
import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CELLS = [w["name"] for w in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]
CONFIGS = sorted(p.name[:-len(".small.json")]
                 for p in (BENCH / "configs").glob("*.small.json"))


def reduction(config: str) -> dict:
    return json.loads((BENCH / "configs" / f"{config}.small.json")
                      .read_text())


def small(cell):
    """``cell`` with the reduced sizes and traffic of its configuration."""
    r = reduction(cell.config)
    return dataclasses.replace(cell, sizes={**cell.sizes, **r["sizes"]},
                               traffic={**cell.traffic, **r["traffic"]})


def kernel_force(cell):
    return reduction(cell.config)["kernel_force"]
