"""``chip_smoke.py`` and the compile-cache placement of the entry points.

The smoke script's phases run here in-process at their ``--rehearse``
sizes (one round, reduced models, Pallas kernels in interpret mode).
Without ``--rehearse`` the script must refuse a machine that has no
TPU, and it must fail where the rest of the repository is missing.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _cpu_env(**extra):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src")}
    env.update(extra)
    return env


@pytest.fixture
def restore_cache_config():
    """Undo a persistent-cache setting made in this process."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_chip_smoke_rehearsal_runs_every_phase(capsys, restore_cache_config):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main(["--rehearse"]) == 0
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    a, b, last = lines[-3:]
    assert a["phase"] == "A" and a["clients"] == 8 and a["cohort"] == 4
    for sched in ("sequential", "vectorized"):
        assert len(a[sched]["round_s"]) == 1
        assert 0.0 <= a[sched]["accuracy"] <= 1.0
    assert a["max_abs_diff"] <= 2e-5
    assert 0.0 <= a["param_distance"] <= 1e-4
    assert b["phase"] == "B" and b["kernel_dispatch"] == "interpret"
    assert abs(b["loss"] - b["loss_ref"]) <= b["loss_rtol"] * b["loss_ref"]
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu_or_repo(where, tmp_path):
    """On the CPU without ``--rehearse``, and next to nothing of the
    repository, the script exits non-zero and prints no result."""
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path))
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=_cpu_env(PYTHONPATH=""), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch,
                                                        restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_compile_cache_lands_in_env_dir(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, the helper changes nothing
    and the compiled entry lands in that directory."""
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=_cpu_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(tmp_path)]
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())
