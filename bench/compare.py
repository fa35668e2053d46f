"""The numbers that decide ``correct``: how far each compared round of the
program lies from one round of the reference started from the same params.

Round ``r`` starts from the program's params at its start, ``s_r``; the
program ends it at ``p_r`` and the reference, run from ``s_r`` on the same
inputs, at ``q_r``.  Each round then carries one round of precision error,
never several compounded.

``gap`` of a round is taken leaf by leaf on the change from ``s_r``: the
gap between the program's norm of a leaf's change and the reference's,
over the reference's norm of that leaf or of the median leaf, whichever
is larger; the worst leaf counts.  Leaves whose reference change in the
first round is under a thousandth of the median leaf's are left out of
every round (they do not train: a skipped prefix, or a leaf whose
gradient is nought to rounding); the median is over the leaves that move
at all.  ``dist`` of a round is the whole model's distance from the
reference, over the distance the reference moved: it sees a change in
direction that the norms cannot.  Each number is the worst over the
rounds.  A round that returns its state unchanged reads 1 on both.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence, Tuple

import numpy as np

MOVED = 1e-3   # share of the median leaf's change under which a leaf is left out


def _norm(a) -> float:
    a = np.asarray(a, np.float64).ravel()
    return float(np.sqrt(np.dot(a, a)))


def _worst(values: Dict) -> object:
    """The key of the largest value; a NaN counts as the largest."""
    return max(values, key=lambda k: (math.isnan(values[k]), values[k]))


def changes(leaves_fn, start, params) -> Dict[str, float]:
    base = dict(leaves_fn(start))
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
    worst = _worst(per)
    return per[worst], worst


def dist(leaves_fn, start, prog, ref) -> float:
    """||prog - ref|| / ||ref - start|| over every leaf."""
    base = dict(leaves_fn(start))
    p = dict(leaves_fn(prog))
    num = den = 0.0
    for name, r in leaves_fn(ref):
        r = np.asarray(r, np.float32)
        num += _norm(np.asarray(p[name], np.float32) - r) ** 2
        den += _norm(r - base[name]) ** 2
    return float(np.sqrt(num / den))


def by_round(leaves_fn, starts: Sequence, progs: Sequence,
             refs: Sequence) -> List[Dict[str, tuple]]:
    """Round ``r`` started at ``starts[r]`` and ended at ``progs[r]`` in
    the program and at ``refs[r]`` in the reference.  Returns, per
    round, name -> (value, worst leaf)."""
    counted = None
    out = []
    for start, prog, ref in zip(starts, progs, refs):
        ref_change = changes(leaves_fn, start, ref)
        if counted is None:
            counted = moved_leaves(ref_change)
        out.append({"gap": gap(changes(leaves_fn, start, prog), ref_change,
                               counted),
                    "dist": (dist(leaves_fn, start, prog, ref), "all")})
    return out


def worst(rounds: List[Dict[str, tuple]]) -> Dict[str, tuple]:
    """Each number's worst round of ``by_round``'s: name -> (value,
    "round <r>, <leaf>"), rounds counted from 1."""
    out = {}
    for name in rounds[0]:
        r = _worst({i: rd[name][0] for i, rd in enumerate(rounds)})
        value, leaf = rounds[r][name]
        out[name] = (value, f"round {r + 1}, {leaf}")
    return out


def numbers(leaves_fn, starts: Sequence, progs: Sequence,
            refs: Sequence) -> Dict[str, tuple]:
    """``gap`` and ``dist``, each the worst over the rounds."""
    return worst(by_round(leaves_fn, starts, progs, refs))
