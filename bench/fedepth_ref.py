"""Plain reference of FeDepth rounds (arXiv:2303.04887, Algorithm 1),
generic over a configuration's reference module (``configs/<name>.py``).

One round: every client of the cohort starts from the global params and
solves its subproblems in order.  Subproblem j trains units [lo, hi) and
the head with SGD + momentum (momentum restarts per subproblem), one step
per batch, ``local_steps`` passes over the client's batches; the prefix
before ``lo`` is frozen and recomputed from the current params.  The
server then takes the cohort's average weighted by sample count.

Nothing here imports the program.  The control computes the same rounds
in another dtype (bfloat16) in the program's place.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def cast(tree, dtype):
    """Floating leaves to ``dtype``; integer leaves (labels, tokens) kept."""
    return jax.tree.map(
        lambda x: jnp.asarray(x, dtype)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
        else jnp.asarray(x), tree)


def draw_rounds(rng, client_indices, n_rounds: int, cohort: int,
                batch_size: int, batch_of: Callable):
    """The inputs of ``n_rounds`` rounds, drawn from the simulation's
    random stream ``rng`` as the paper's protocol draws them: a uniform
    cohort of ``cohort`` clients without replacement, then for each
    client in cohort order |D_k| // B batches (at least one) of B
    distinct examples, ``batch_of(indices)`` each.  Returns, per round,
    (client, batches) in cohort order."""
    out = []
    for _ in range(n_rounds):
        clients = []
        for k in rng.choice(len(client_indices), size=cohort, replace=False):
            idx = client_indices[k]
            b = min(batch_size, len(idx))
            clients.append((int(k), [
                batch_of(rng.choice(idx, size=b, replace=False))
                for _ in range(max(1, len(idx) // batch_size))]))
        out.append(clients)
    return out


class Reference:
    """``mod`` provides ``ref_split``, ``ref_merge``, ``ref_step_static``,
    ``ref_loss`` and ``make_prefix``; ``rows`` keeps only the first
    ``rows`` examples of every batch (the fault of a half batch)."""

    def __init__(self, mod, sizes, *, lr: float, momentum: float,
                 local_steps: int, dtype=jnp.float32,
                 precision: str = "highest", rows: Optional[Callable] = None):
        self.mod, self.sizes = mod, sizes
        self.lr, self.momentum, self.local_steps = lr, momentum, local_steps
        self.dtype, self.precision, self.rows = dtype, precision, rows
        self.prefix = mod.make_prefix(sizes)
        self._steps: Dict[tuple, Callable] = {}

    def _step(self, static):
        if static not in self._steps:
            mod, sizes, lr, mom = self.mod, self.sizes, self.lr, self.momentum

            def step(frozen, train, vel, z_in, batch):
                g = jax.grad(lambda tp: mod.ref_loss(
                    sizes, static, frozen, tp, z_in, batch))(train)
                vel = jax.tree.map(lambda v, gi: mom * v + gi, vel, g)
                train = jax.tree.map(lambda t, v: t - lr * v, train, vel)
                return train, vel

            self._steps[static] = jax.jit(step)
        return self._steps[static]

    def client(self, params, blocks: Sequence[Tuple[int, int]], batches):
        mod = self.mod
        for j, (lo, hi) in enumerate(blocks):
            step = self._step(mod.ref_step_static(lo, hi, j))
            zs = [self.prefix(params, b, lo) if lo > 0 else None
                  for b in batches]
            train = mod.ref_split(params, lo, hi)
            vel = jax.tree.map(jnp.zeros_like, train)
            for _ in range(self.local_steps):
                for z, b in zip(zs, batches):
                    train, vel = step(params, train, vel, z, b)
            params = mod.ref_merge(params, train, lo, hi)
        return params

    def round(self, params, clients: List[tuple]):
        """``clients``: (blocks, batches, weight) for each client of the
        cohort, in cohort order.  Returns the new global params."""
        w = np.asarray([c[2] for c in clients], np.float64)
        w = (w / w.sum()).astype(np.float32)
        with jax.default_matmul_precision(self.precision):
            params = cast(params, self.dtype)
            total = None
            for (blocks, batches, _), wk in zip(clients, w):
                if self.rows is not None:
                    batches = [self.rows(b) for b in batches]
                batches = [cast(b, self.dtype) for b in batches]
                local = self.client(params, blocks, batches)
                part = jax.tree.map(lambda x: x * jnp.asarray(wk, x.dtype),
                                    local)
                total = part if total is None else jax.tree.map(
                    jnp.add, total, part)
                del local, part
            return total
