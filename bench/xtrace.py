"""Reduce a ``jax.profiler`` trace of the measured window to the numbers
the per-layer metrics read.

A trace is first turned into a plain dict, the form the recorded test
trace under ``testdata/`` keeps:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]},
                           ...]},
                {"name": "/host:CPU", "lines": [...]}]}

Device planes are named ``/device:<platform>:<n>``.  On each, the line
"XLA Modules" holds one event per program execution (named after the
jitted function, ``jit_<name>(<id>)``) and "XLA Ops" one event per
operation inside it; a Pallas kernel appears there under its kernel
function's name.  The host plane holds the ``TraceAnnotation`` spans the
harness records (``bench.window``, ``bench.round``, ``bench.sample``,
``bench.batch``, ``bench.client_update``, ``bench.aggregate``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

MODULES, OPS = "XLA Modules", "XLA Ops"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")


def load_logdir(logdir: str) -> dict:
    """The trace that ``jax.profiler`` wrote under ``logdir``, as a dict
    holding what the reduction reads: the device planes' programs and
    operations, and the harness's host spans."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(paths)}")
    planes = []
    for plane in ProfileData.from_file(paths[0]).planes:
        host = plane.name.startswith("/host:")
        lines = []
        for line in plane.lines:
            if host:
                evs = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                       for ev in line.events
                       if ev.name.startswith(SPAN_PREFIX)]
            elif line.name in (MODULES, OPS):
                evs = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                       for ev in line.events]
            else:
                continue
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def program_name(event_name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return _SUFFIX.sub("", event_name)


def _bisect(sorted_values, x) -> int:
    """Index of the first value >= ``x``."""
    import bisect
    return bisect.bisect_left(sorted_values, x)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Reduced:
    """Device programs, operations and host spans inside the window."""

    def __init__(self, space: dict):
        self.devices = [p for p in space["planes"]
                        if p["name"].startswith("/device:")
                        and any(l["name"] == OPS for l in p["lines"])]
        host = [ev for p in space["planes"] if p["name"].startswith("/host:")
                for l in p["lines"] for ev in l["events"]
                if ev[0].startswith(SPAN_PREFIX)]
        wins = [ev for ev in host if ev[0] == WINDOW]
        if len(wins) != 1:
            raise ValueError(f"trace holds {len(wins)} '{WINDOW}' spans, "
                             f"expected 1")
        self.t0, self.t1 = wins[0][1], wins[0][1] + wins[0][2]
        self.spans = [ev for ev in host if ev[0] != WINDOW]
        if not self.devices:
            raise ValueError("trace holds no device plane with XLA Ops")

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _events(self, line_name: str, plane: dict) -> List[list]:
        return [ev for l in plane["lines"] if l["name"] == line_name
                for ev in l["events"]
                if ev[1] >= self.t0 and ev[1] + ev[2] <= self.t1]

    def modules(self) -> List[list]:
        """Program executions in the window, over every device."""
        return [ev for p in self.devices for ev in self._events(MODULES, p)]

    def ops(self) -> List[list]:
        return [ev for p in self.devices for ev in self._events(OPS, p)]

    def program_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for name, _, dur in self.modules():
            out[program_name(name)] += dur * 1e-9
        return dict(out)

    def op_events(self, *names: str) -> List[list]:
        """Operations whose name contains one of ``names``."""
        return [ev for ev in self.ops() if any(n in ev[0] for n in names)]

    def busy_intervals(self, plane: dict) -> List[Tuple[int, int]]:
        return _union([(s, s + d) for _, s, d in self._events(OPS, plane)])

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        total = sum(e - s for p in self.devices
                    for s, e in self.busy_intervals(p))
        return total * 1e-9 / len(self.devices)

    def innermost(self) -> List[Tuple[int, int, str]]:
        """The host spans flattened to disjoint (start, end, name) pieces,
        each named after the innermost span that covers it."""
        bounds = sorted({t for _, s, d in self.spans for t in (s, s + d)})
        pieces = []
        for a, b in zip(bounds, bounds[1:]):
            pieces.append([a, b, None])
        # longest first, so an inner span overwrites the outer one
        for name, s, d in sorted(self.spans, key=lambda ev: -ev[2]):
            lo = _bisect(bounds, s)
            hi = _bisect(bounds, s + d)
            for piece in pieces[lo:hi]:
                piece[2] = name[len(SPAN_PREFIX):]
        return [(a, b, n) for a, b, n in pieces if n is not None]

    def idle_by_span(self) -> Dict[str, float]:
        """Idle device seconds, each gap between busy intervals attributed
        to the innermost host span that covers its middle ("none" where no
        span does), averaged over devices."""
        out: Dict[str, float] = defaultdict(float)
        pieces = self.innermost()
        starts = [a for a, _, _ in pieces]
        for plane in self.devices:
            prev = self.t0
            for s, e in self.busy_intervals(plane) + [(self.t1, self.t1)]:
                if s > prev:
                    mid = (prev + s) // 2
                    i = _bisect(starts, mid + 1) - 1
                    who = pieces[i][2] if i >= 0 and mid < pieces[i][1] \
                        else "none"
                    out[who] += (s - prev) * 1e-9 / len(self.devices)
                prev = max(prev, e)
        return dict(out)


def breakdown(red: Reduced, top: int = 10) -> dict:
    progs = sorted(red.program_seconds().items(), key=lambda kv: -kv[1])
    idle = sorted(red.idle_by_span().items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in progs[:top]],
            "idle_gaps": [[k, v] for k, v in idle[:top]]}


def trim(space: dict, keep_s: float) -> dict:
    """The first ``keep_s`` seconds of the window: device programs and
    operations that lie inside it, and the harness's host spans clipped
    to it.  A trace small enough to keep as test data."""
    red = Reduced(space)
    t0, t1 = red.t0, red.t0 + int(keep_s * 1e9)
    out = {"planes": []}
    for p in space["planes"]:
        host = p["name"].startswith("/host:")
        lines = []
        for l in p["lines"]:
            if host:
                evs = [[n, max(s, t0), min(s + d, t1) - max(s, t0)]
                       for n, s, d in l["events"]
                       if n.startswith(SPAN_PREFIX) and s < t1 and s + d > t0]
            elif l["name"] in (MODULES, OPS):
                evs = [ev for ev in l["events"]
                       if ev[1] >= t0 and ev[1] + ev[2] <= t1]
            else:
                evs = []
            if evs:
                lines.append({"name": l["name"], "events": evs})
        if lines:
            out["planes"].append({"name": p["name"], "lines": lines})
    return out
