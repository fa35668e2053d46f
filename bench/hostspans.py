"""Host self time of the program's own spans in a profiler trace.

The program opens a ``jax.profiler.TraceAnnotation`` named
``repro.<kind>`` around each of its layers (``repro.obs.trace.annotate``):
the round, the cohort draw, each client's update, each block and its
set-up, step dispatches and merge, the prefix cache, the payload, the
wire and the aggregation.  They lie on the host plane of the trace, one
line per thread, each with its attributes as stats (``host_bytes``,
``mode``, ``client`` ...).

A span's self time is its duration less the union of its direct
children, nesting by containment on one thread, both clipped to the
harness's window.  The harness's own ``bench.*`` spans are ignored, so
the self times of the spans of one round add up to the round's host
time.  Readers in ``metrics/`` sum them by span name.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional

import xtrace

PREFIX = "repro."


class ProgramSpan(NamedTuple):
    name: str           # "repro.block.steps"
    start: int          # ns, clipped to the window
    end: int
    stats: dict
    thread: str         # the host line it lies on
    self_ns: int        # clipped duration less its direct children's


def program_events(logdir: str) -> Dict[str, List[list]]:
    """The program's spans that ``jax.profiler`` wrote under ``logdir``:
    host line name -> ``[name, start_ns, duration_ns, stats]``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(paths)}")
    out: Dict[str, List[list]] = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                    {k: v for k, v in ev.stats}]
                   for ev in line.events if ev.name.startswith(PREFIX)]
            if evs:
                out.setdefault(line.name, []).extend(evs)
    return out


def with_program_events(space: dict, events: Dict[str, List[list]]) -> dict:
    """``space`` (as ``xtrace.load_logdir`` gives it) with the program's
    spans added to its host plane, each line under its thread's name."""
    host = next((p for p in space["planes"]
                 if p["name"].startswith("/host:")), None)
    if host is None:
        host = {"name": "/host:CPU", "lines": []}
        space["planes"].append(host)
    lines = {l["name"]: l for l in host["lines"]}
    for name, evs in events.items():
        if name not in lines:
            lines[name] = {"name": name, "events": []}
            host["lines"].append(lines[name])
        lines[name]["events"].extend(evs)
    return space


def load_logdir(logdir: str) -> dict:
    """``xtrace.load_logdir``'s dict, with the program's spans kept as
    ``[name, start_ns, duration_ns, stats]`` on the host plane."""
    return with_program_events(xtrace.load_logdir(logdir),
                               program_events(logdir))


def _lines(space: dict) -> Dict[str, List[list]]:
    out: Dict[str, List[list]] = {}
    for p in space["planes"]:
        if p["name"].startswith("/host:"):
            for l in p["lines"]:
                evs = [ev for ev in l["events"] if ev[0].startswith(PREFIX)]
                if evs:
                    out.setdefault(l["name"], []).extend(evs)
    return out


def _covered(intervals: Iterable[tuple]) -> int:
    return sum(e - s for s, e in xtrace._union(
        [(s, e) for s, e in intervals if e > s]))


def program_spans(space: dict, t0: int, t1: int) -> List[ProgramSpan]:
    """Every program span that overlaps ``[t0, t1)``, clipped to it, with
    its self time."""
    out: List[ProgramSpan] = []
    for thread, evs in _lines(space).items():
        evs = sorted(evs, key=lambda ev: (ev[1], -ev[2]))
        children: List[List[tuple]] = [[] for _ in evs]
        stack: List[int] = []
        for i, (_, s, d, _st) in enumerate(evs):
            while stack and evs[stack[-1]][1] + evs[stack[-1]][2] < s + d:
                stack.pop()
            clip = (max(s, t0), min(s + d, t1))
            if stack:
                children[stack[-1]].append(clip)
            stack.append(i)
        for (name, s, d, stats), kids in zip(evs, children):
            a, b = max(s, t0), min(s + d, t1)
            if b <= a:
                continue
            out.append(ProgramSpan(name, a, b, stats, thread,
                                   b - a - _covered(kids)))
    return out


def reduce(space: dict) -> xtrace.Reduced:
    """``xtrace.Reduced(space)`` that also exposes the program's spans
    in the window as ``program_spans``."""
    red = xtrace.Reduced(space)
    red.program_spans = program_spans(space, red.t0, red.t1)
    return red


def trim(space: dict, keep_s: float) -> dict:
    """``xtrace.trim`` that keeps the program's spans too, clipped to the
    first ``keep_s`` seconds of the window."""
    red = xtrace.Reduced(space)
    t0, t1 = red.t0, red.t0 + int(keep_s * 1e9)
    events = {thread: [[n, max(s, t0), min(s + d, t1) - max(s, t0), st]
                       for n, s, d, st in evs if s < t1 and s + d > t0]
              for thread, evs in _lines(space).items()}
    rest = {"planes": [
        {**p, "lines": [{**l, "events": [ev for ev in l["events"]
                                         if not ev[0].startswith(PREFIX)]}
                        for l in p["lines"]]} for p in space["planes"]]}
    return with_program_events(xtrace.trim(rest, keep_s),
                               {k: v for k, v in events.items() if v})


def spans_of(view) -> Optional[List[ProgramSpan]]:
    """The program's spans in the window, or ``None`` where the trace
    holds none (a program that writes no ``repro.*`` spans)."""
    spans = getattr(view.trace, "program_spans", None)
    return spans or None


def host_ms(view, kinds: Iterable[str]) -> Optional[float]:
    """Host milliseconds per round of self time in the spans ``kinds``
    (``repro.<kind>``)."""
    spans = spans_of(view)
    if spans is None:
        return None
    names = {PREFIX + k for k in kinds}
    return 1e-6 * sum(sp.self_ns for sp in spans
                      if sp.name in names) / view.rounds


def host_bytes(view, kinds: Iterable[str]) -> Optional[float]:
    """The ``host_bytes`` stat of the spans ``kinds``, summed, per
    round."""
    spans = spans_of(view)
    if spans is None:
        return None
    names = {PREFIX + k for k in kinds}
    return sum(int(sp.stats.get("host_bytes", 0)) for sp in spans
               if sp.name in names) / view.rounds
