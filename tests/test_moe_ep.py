"""shard_map expert-parallel MoE (explicit all_to_all) vs the portable
scatter-dispatch path: exact agreement on a multi-device host mesh.

NOTE: this file spawns a subprocess so the 8-device XLA_FLAGS never leak
into the main test process (everything else runs on 1 device).
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_reduced_config
from repro.models import moe, moe_ep, common

cfg = get_reduced_config("qwen3-moe-235b-a22b")  # 4 experts, top-2
mesh = jax.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
p = moe.init(key, cfg)
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, cfg.d_model)) * 0.5

ref_out, _ = moe.forward(p, cfg, x, capacity_factor=8.0)
with jax.set_mesh(mesh):
    ep_out, _ = moe_ep.forward_ep(p, cfg, x, mesh, capacity_factor=8.0)
err = float(jnp.abs(ep_out - ref_out).max())
assert err < 1e-4, err

# and the context-based delegation inside moe.forward
with jax.set_mesh(mesh), common.ep_moe():
    del_out, _ = moe.forward(p, cfg, x, capacity_factor=8.0)
err2 = float(jnp.abs(del_out - ref_out).max())
assert err2 < 1e-4, err2
print("EP_OK", err, err2)
"""


def test_moe_ep_matches_dense_dispatch():
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, timeout=560,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": os.environ.get("HOME", ""),
                              # the 8-device mesh is a CPU host-platform
                              # trick; never let a libtpu install hijack
                              # the stripped subprocess env
                              "JAX_PLATFORMS": "cpu"},
                         cwd=Path(__file__).resolve().parents[1])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "EP_OK" in out.stdout
