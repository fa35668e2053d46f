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
    init, p_first, p_last, rounds = bench.compared_rounds(2)
    r_first, r_last = harness.reference_params(cell, init, rounds)
    got = compare.numbers(cell.model.leaves, init, (p_first, r_first),
                          (p_last, r_last))
    for name, (value, leaf) in got.items():
        assert value < 1e-3, (name, value, leaf)
