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
    """The numbers of the reference's ``variant`` put in the program's
    place, each round run from the program's params at its start."""
    bench = harness.Bench(cell, SEED, kernel_force=kernel_force(cell))
    params, rounds = bench.compared_rounds(cell.traffic["compare_rounds"])
    bench.close()
    starts = params[:-1]
    refs = harness.reference_params(cell, starts, rounds)
    controls = harness.reference_params(cell, starts, rounds, **variant)
    return compare.numbers(cell.model.leaves, starts, controls, refs)


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
    assert r["checks"]["gap"]["value"] == pytest.approx(1.0)


def test_fault_in_a_later_round_fails(small_cell, monkeypatch, capsys):
    """From the second round on the round returns its state unchanged.
    The reference restarts each round from the program's params, and
    still the later round reads 1."""
    from repro.fl.engine import RoundEngine
    run_round = RoundEngine.run_round

    def stale(self, state, rd, batch_fn):
        if rd >= 1:
            return state, 0, 0
        return run_round(self, state, rd, batch_fn)

    monkeypatch.setattr(RoundEngine, "run_round", stale)
    r = _broken_run(small_cell)
    assert r["correct"] is False
    assert r["checks"]["gap"]["value"] == pytest.approx(1.0)
    by_round = next(line for line in capsys.readouterr().err.splitlines()
                    if line.startswith("gap by round:"))
    first, second = by_round.split(":", 1)[1].split(";")[0].split()[:2]
    assert float(first) < small_cell.limits["gap"]
    assert float(second) == pytest.approx(1.0)
    assert "worst: round 2," in by_round


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


def test_calibration_reads_every_round_of_each_kind(small_cell):
    """``calibrate.readings`` gives the program's numbers under the
    limits and the control's and the half batch's over one of them, each
    with its worst round and every round's value."""
    lines = []
    calibrate.readings(small_cell, [SEED], 1, 1, require_tpu=False,
                       kernel_force=kernel_force(small_cell),
                       emit=lines.append)
    assert [r["kind"] for r in lines] == ["program", "control_bf16",
                                          "fault_half_batch"]
    n = small_cell.traffic["compare_rounds"]
    for r in lines:
        assert set(r["by_round"]) == {"gap", "dist"}
        for name, values in r["by_round"].items():
            assert len(values) == n and r[name] == max(values)
            assert r["worst"][name].startswith("round ")
        over = [r[k] > small_cell.limits[k] for k in ("gap", "dist")]
        assert any(over) is (r["kind"] != "program"), r
