"""The comparison that decides ``correct`` fails what it must: the
control (the reference in bfloat16 in the program's place), a run with
the timed path broken underneath, once for each fault a training cell can
have on one chip, and a decomposition other than the traffic mix states."""
import time

import pytest

import calibrate
import compare
import harness
from smallcells import kernel_force

SEED = 3_900_000_017


def _numbers(cell, **variant):
    bench = harness.Bench(cell, SEED, kernel_force=kernel_force(cell))
    init, _, _, rounds = bench.compared_rounds(cell.traffic["compare_rounds"])
    bench.close()
    r_first, r_last = harness.reference_params(cell, init, rounds)
    c_first, c_last = harness.reference_params(cell, init, rounds, **variant)
    return compare.numbers(cell.model.leaves, init, (c_first, r_first),
                           (c_last, r_last))


def test_control_fails_the_limits(small_cell):
    import jax.numpy as jnp
    got = _numbers(small_cell, dtype=jnp.bfloat16, precision="default")
    assert any(v > small_cell.limits[k] for k, (v, _) in got.items())


def test_half_batch_in_the_reference_fails_the_limits(small_cell):
    got = _numbers(small_cell, rows=calibrate.half_rows)
    assert any(v > small_cell.limits[k] for k, (v, _) in got.items())


def _broken_run(cell):
    return harness.run(cell, SEED, 0.2, False, time.perf_counter(),
                       require_tpu=False, kernel_force=kernel_force(cell))


def test_round_that_returns_its_state_unchanged(small_cell, monkeypatch):
    from repro.fl.engine import RoundEngine
    monkeypatch.setattr(RoundEngine, "run_round",
                        lambda self, state, rd, batch_fn: (state, 0, 0))
    r = _broken_run(small_cell)
    assert r["correct"] is False
    assert r["checks"]["gap_first"]["value"] == pytest.approx(1.0)


def test_half_of_every_batch_left_out(small_cell, monkeypatch):
    """The depth-wise update trains on half of each batch's rows; the
    mean is taken over the rest."""
    from repro.core import blockwise
    update = blockwise.client_update

    def half(runner, params, dec, batches, **kw):
        batches = [{k: v[:len(v) // 2] for k, v in b.items()}
                   for b in batches]
        return update(runner, params, dec, batches, **kw)

    monkeypatch.setattr(blockwise, "client_update", half)
    r = _broken_run(small_cell)
    assert r["correct"] is False


def test_decomposition_other_than_the_mix_states(small_cell, monkeypatch):
    """The program's memory model gives the tier another decomposition:
    its first block starts one unit early."""
    import dataclasses
    from repro.core import decomposition
    decompose = decomposition.decompose

    def early(mem, budget, **kw):
        dec = decompose(mem, budget, **kw)
        (lo, hi), *rest = dec.blocks
        return dataclasses.replace(dec, blocks=((lo - 1, hi), *rest),
                                   skipped_prefix=lo - 1)

    monkeypatch.setattr(decomposition, "decompose", early)
    r = _broken_run(small_cell)
    assert r["correct"] is False
    assert r["checks"]["decomposition_mismatch"]["value"] == 1
