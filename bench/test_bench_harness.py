"""The harness end to end at reduced sizes on the CPU: one run of each
cell prints a valid result line, and the command refuses to run without
a TPU."""
import json
import math
import os
import subprocess
import sys
import time

import pytest

import harness
from smallcells import BENCH, kernel_force

SEED = 2**33 + 12345      # more than 32 bits, as the benchmark's seeds are


def test_every_cell_runs_and_is_correct(small_cell):
    r = harness.run(small_cell, SEED, 0.3, False, time.perf_counter(),
                    require_tpu=False, kernel_force=kernel_force(small_cell))
    line = json.loads(json.dumps(r))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"round_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"] == "s"
    assert line["device"]["platform"] == "cpu"
    checks = line["checks"]
    assert checks["compiles_in_window"]["value"] == 0
    for name in ("gap", "dist"):
        assert math.isfinite(checks[name]["value"])
        assert checks[name]["value"] <= checks[name]["limit"]


def test_same_seed_same_inputs(small_cell):
    """The seed fixes the weights, the data and the cohorts."""
    def draw(seed):
        b = harness.Bench(small_cell, seed,
                          kernel_force=kernel_force(small_cell))
        (init, _), rounds = b.compared_rounds(1)
        return init, rounds

    (i1, r1), (i2, r2), (i3, _) = draw(SEED), draw(SEED), draw(SEED + 1)
    leaves = small_cell.model.leaves
    for (_, a), (_, b) in zip(leaves(i1), leaves(i2)):
        assert (a == b).all()
    assert any((a != b).any() for (_, a), (_, b) in zip(leaves(i1),
                                                         leaves(i3)))
    for (blk1, bat1, w1), (blk2, bat2, w2) in zip(r1[0], r2[0]):
        assert blk1 == blk2 and w1 == w2
        for x, y in zip(bat1, bat2):
            assert all((x[k] == y[k]).all() for k in x)


def test_traced_run_reads_the_layers(small_cell, monkeypatch):
    """A traced run reduces the profiler's trace to the cell's per-layer
    metrics, ``busy_s``/``window_s`` and a breakdown.  The CPU has no
    device plane, so the recorded chip trace of the cell stands in."""
    import xtrace
    from types import SimpleNamespace
    recorded = xtrace.load(str(BENCH / "testdata" / f"{small_cell.name}"
                                                    ".trace.json.gz"))
    monkeypatch.setattr(xtrace, "load_logdir", lambda _: recorded)
    # the chip the trace was recorded on, so that its peaks apply
    chip = SimpleNamespace(platform="cpu", device_kind="TPU v5 lite",
                           memory_stats=lambda: None)
    monkeypatch.setattr(harness, "check_device", lambda *a: chip)
    r = harness.run(small_cell, SEED, 0.3, True, time.perf_counter(),
                    require_tpu=False, kernel_force=kernel_force(small_cell))
    assert r["correct"] is True
    names = {m["name"] for m in small_cell.per_layer}
    assert set(r["metrics"]) <= names
    assert {"dispatches_per_round", "block_train_ms", "device_idle_share",
            "round_mfu"} <= set(r["metrics"])
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    for key in ("device_ops", "idle_gaps"):
        rows = r["breakdown"][key]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    assert list(r)[-1] == "checks"


def test_command_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "preresnet20.partial", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=BENCH.parent, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")
