"""Shared fixtures.  NOTE: tests run on the default single CPU device —
never import repro.launch.dryrun here (it forces 512 host devices)."""
import os

import jax
import pytest


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def multi_device_env():
    """Clean environment for SUBPROCESS tests that need a forced
    multi-device CPU mesh.  XLA reads ``XLA_FLAGS`` exactly once, at
    backend init — this parent process already initialized jax on one
    device, so multi-device tests must run in a fresh interpreter whose
    script sets ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (or calls ``repro.launch.mesh.force_host_device_count``) BEFORE any
    jax import touches the backend.  See docs/scale.md §Testing on a
    forced mesh."""
    return {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
            "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"}
