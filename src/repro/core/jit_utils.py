"""Small jit helpers shared by the hot paths.

Buffer donation (``jax.jit(donate_argnums=...)``) lets XLA reuse an
input buffer for an output of the same shape/dtype instead of
allocating a fresh one — for the FL hot paths that means the (train,
vel) step carries, the stacked group-update params, and the per-round
client payloads folded by aggregation are updated in place rather than
copied each dispatch.  Donation is only implemented on device backends
(gpu/tpu); XLA:CPU ignores it and logs a warning per unusable buffer,
so :func:`donate` gates on the backend to keep CPU runs clean.

Callers that donate an argument must pass PRIVATE buffers: donating a
view that aliases a live tree (e.g. ``runner.split``'s pass-through
leaves aliasing the full params) would invalidate the original on the
backends where donation is real.  See ``block_carry`` in
``core/blockwise.py`` for the pattern: ``client_update`` builds each
block's donated carry in one jitted program whose outputs are fresh
copies, never forwarded inputs.
"""
from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=None)
def donation_supported() -> bool:
    """True when the default backend honors ``donate_argnums``."""
    return jax.default_backend() in ("gpu", "tpu")


def donate(*argnums: int) -> tuple:
    """``donate_argnums`` for the current backend: the given argnums on
    gpu/tpu, ``()`` on cpu (where donation is a no-op that only warns)."""
    return argnums if donation_supported() else ()
