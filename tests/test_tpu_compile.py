"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler ships with jaxlib's TPU support, so these tests compile
the four Pallas kernels at real model widths and the PreResNet-20
FeDepth programs at full width for one chip of a ``v5e:2x2`` topology.
What the chip's compiler refuses — a block narrower than the (8, 128)
tiling, a primitive Mosaic cannot lower, more VMEM or HBM than a v5e
has — then fails here.  Interpret-mode parity tests cannot see any of
it.  Nothing runs, so these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import: one process at a time may load libtpu, and every pytest-xdist
worker imports every test module.  Keep these tests in this one file,
so one worker loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import preresnet20
from repro.core import blockwise
from repro.core.decomposition import decompose
from repro.core.memory_model import resnet_memory
from repro.fl.engine import SCENARIOS, SimConfig, scenario_budgets
from repro.kernels.chunked_ce import chunked_cross_entropy
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_ssd import mamba2_scan
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.models import resnet

V5E_HBM_BYTES = 16 * 2**30
GROUP = 10      # the cohort of SimConfig's defaults: 0.1 of 100 clients


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of ``tree`` placed on the described device."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


# --------------------------------------------------------------- kernels
def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


# each kernel at the widths of the model that dispatches to it, T=2048
KERNELS = {
    # mamba2-370m head: D=1024, V=50288
    "chunked_ce": (chunked_cross_entropy,
                   (_f32(1, 2048, 1024), _f32(1024, 50288), _i32(1, 2048))),
    # rwkv6-7b: 64 heads of 64
    "rwkv6_scan": (rwkv6_scan,
                   (_f32(1, 2048, 64, 64),) * 4
                   + (_f32(64, 64), _f32(1, 64, 64, 64))),
    # mamba2-370m: H=32, P=64, N=128
    "mamba2_ssd": (mamba2_scan,
                   (_f32(1, 2048, 32, 64), _f32(1, 2048, 32), _f32(32),
                    _f32(1, 2048, 128), _f32(1, 2048, 128), _f32(32),
                    _f32(1, 32, 64, 128))),
    # qwen2-7b: 28 query heads, 4 KV heads, D=128
    "flash_attention": (flash_attention,
                        (_f32(1, 2048, 28, 128), _f32(1, 2048, 4, 128),
                         _f32(1, 2048, 4, 128))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = KERNELS[name]
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------- PreResNet-20, full width
@pytest.fixture(scope="module")
def paper():
    """Full PreResNet-20 at the paper's protocol (SimConfig defaults):
    param shapes, one client batch, and the fair scenario's
    decompositions keyed by budget ratio."""
    cfg, sim = preresnet20.CONFIG, SimConfig()
    params = jax.eval_shape(lambda: resnet.init(jax.random.PRNGKey(0), cfg))
    s = cfg.image_size
    batch = {"images": _f32(sim.batch_size, s, s, cfg.in_channels),
             "labels": _i32(sim.batch_size)}
    mem = resnet_memory(cfg, sim.mem_batch)
    ratios = SCENARIOS[sim.scenario]
    decs = {r: decompose(mem, int(b))
            for r, b in zip(ratios, scenario_budgets(mem, ratios))}
    return cfg, sim, params, batch, decs


def test_preresnet20_block_step_fits_v5e(paper, one_chip):
    """The buffered block step of the full-budget client, which trains
    all nine residual blocks as one subproblem."""
    cfg, sim, params, batch, decs = paper
    (lo, hi), = decs[1.0].blocks
    runner = blockwise.resnet_runner(cfg)
    train = jax.eval_shape(lambda p: runner.split(p, lo, hi), params)
    z = jax.eval_shape(blockwise.make_prefix_forward(runner, lo), params,
                       batch)
    step = blockwise.make_buffered_block_step(runner, lo, hi, 0, lr=sim.lr,
                                              momentum=sim.momentum)
    compiled = step.lower(*_on(one_chip, (params, train, train, train, z,
                                          batch))).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES


def test_preresnet20_group_update_fits_v5e(paper, one_chip, monkeypatch):
    """The vectorized scheduler's update of a whole 10-client group of
    full-budget clients: 12 local SGD steps (6 batches, 2 epochs) over
    all nine blocks, vmapped, as a TPU traces it (a rolled step loop).
    The other budget tiers compile the same steps cut into more blocks."""
    unroll_bounds = blockwise._unroll_bounds
    monkeypatch.setattr(blockwise, "_unroll_bounds",
                        lambda backend: unroll_bounds("tpu"))
    cfg, sim, params, batch, decs = paper
    # build_federated's default 40k images over 100 balanced clients
    n_batches = 40_000 // 100 // sim.batch_size
    update = blockwise.group_update_for(
        blockwise.resnet_runner(cfg), decs[1.0], lr=sim.lr,
        momentum=sim.momentum, local_steps=sim.local_steps)

    def stacked(a, *lead):
        return jax.ShapeDtypeStruct(lead + a.shape, a.dtype)

    compiled = update.lower(*_on(one_chip, (
        jax.tree.map(lambda a: stacked(a, GROUP), params),
        jax.tree.map(lambda a: stacked(a, GROUP, n_batches), batch),
    ))).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES
