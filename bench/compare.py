"""The numbers that decide ``correct``: how far the program's global params
after the compared rounds lie from the reference's.

``gap`` is taken leaf by leaf on the change from the initial params: the
gap between the program's norm of a leaf's change and the reference's,
over the reference's norm of that leaf or of the median leaf, whichever
is larger; the worst leaf counts.  Leaves whose reference change after
the first round is under a thousandth of the median leaf's are left out
(they do not train: a skipped prefix, or a leaf whose gradient is nought
to rounding); the median is over the leaves that move at all.  ``dist`` is the whole model's distance from the reference,
over the distance the reference moved: it sees a change in direction that
the norms cannot.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

import numpy as np

MOVED = 1e-3   # share of the median leaf's change under which a leaf is left out


def _norm(a) -> float:
    a = np.asarray(a, np.float64).ravel()
    return float(np.sqrt(np.dot(a, a)))


def changes(leaves_fn, init, params) -> Dict[str, float]:
    base = dict(leaves_fn(init))
    return {name: _norm(np.asarray(x, np.float32) - base[name])
            for name, x in leaves_fn(params)}


def moved_leaves(ref_first: Dict[str, float]) -> List[str]:
    """Leaves the reference moves by a thousandth of the median moved
    leaf or more (a partial-training cohort leaves many exactly still)."""
    med = statistics.median(v for v in ref_first.values() if v > 0)
    return [n for n, v in ref_first.items() if v >= MOVED * med]


def gap(prog: Dict[str, float], ref: Dict[str, float],
        counted: List[str]) -> Tuple[float, str]:
    med = statistics.median(ref[n] for n in counted)
    per = {n: abs(prog[n] - ref[n]) / max(ref[n], med) for n in counted}
    worst = max(per, key=per.get)
    return per[worst], worst


def dist(leaves_fn, init, prog, ref) -> float:
    """||prog - ref|| / ||ref - init|| over every leaf."""
    base = dict(leaves_fn(init))
    p = dict(leaves_fn(prog))
    num = den = 0.0
    for name, r in leaves_fn(ref):
        r = np.asarray(r, np.float32)
        num += _norm(np.asarray(p[name], np.float32) - r) ** 2
        den += _norm(r - base[name]) ** 2
    return float(np.sqrt(num / den))


def numbers(leaves_fn, init, firsts, lasts) -> Dict[str, tuple]:
    """``firsts``/``lasts``: (program, reference) params after the first
    and the last compared round.  Returns name -> (value, worst leaf)."""
    ref_first = changes(leaves_fn, init, firsts[1])
    counted = moved_leaves(ref_first)
    g1 = gap(changes(leaves_fn, init, firsts[0]), ref_first, counted)
    g2 = gap(changes(leaves_fn, init, lasts[0]),
             changes(leaves_fn, init, lasts[1]), counted)
    return {"gap_first": g1, "gap_last": g2,
            "dist_last": (dist(leaves_fn, init, lasts[0], lasts[1]), "all")}
