"""Each configuration's plain reference agrees with the program at
reduced sizes on the CPU, where both compute in float32: the
comparison's numbers read far under any limit.  This holds for every
configuration under ``configs/``, whether or not a cell runs it yet."""
import pytest

import calibrate
import compare
import harness
from smallcells import CONFIGS, kernel_force, reduction, small


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_agrees_with_the_program(config):
    cell = small(calibrate.cell_for(config, reduction(config)["mix"]))
    bench = harness.Bench(cell, 2**32 + 77, kernel_force=kernel_force(cell))
    assert bench.decomposition_mismatch() == 0
    params, rounds = bench.compared_rounds(2)
    refs = harness.reference_params(cell, params[:-1], rounds)
    got = compare.numbers(cell.model.leaves, params[:-1], params[1:], refs)
    for name, (value, leaf) in got.items():
        assert value < 1e-3, (name, value, leaf)
