"""Fixtures of the benchmark's own tests: ``bench/`` and the program's
``src/`` on the path, and each cell of ``BENCHMARK.json`` at reduced
sizes."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
for path in (BENCH, BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

from smallcells import CELLS  # noqa: E402


@pytest.fixture(params=CELLS)
def small_cell(request):
    import harness
    from smallcells import small
    return small(harness.load_cell(request.param))
