"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged over
  the devices;
* program time: the summed device time of each compiled program (the
  ``XLA Modules`` line), keyed by the program's name without its id;
* idle gaps: the stretches of the window in which no operation ran, each
  named after the innermost benchmark span (``bench:<phase>``) that the
  host was in at the gap's middle.

The window is the host span from the first ``bench:round`` to the end of
the last one, on the trace's own clock.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import NamedTuple, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN = "bench:"
ROUND_SPAN = SPAN + "round"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float       # seconds on the trace's clock
    end: float


class Summary(NamedTuple):
    window: tuple              # (start, end) on the trace's clock
    n_devices: int
    busy_s: float              # per device, averaged
    programs: dict             # program name -> device seconds (summed)
    ops: list                  # [(program:op, seconds)], longest first
    gaps: list                 # [(host phase, seconds)], longest first

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_events(path: str) -> list[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                out.append(Event(plane.name, line.name, e.name, s,
                                 s + e.duration_ns * 1e-9))
    return out


def op_name(name: str) -> str:
    """``%fusion.1 = f32[622] fusion(...)`` -> ``fusion.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def program_name(name: str) -> str:
    """``jit_run(1234)`` and ``jit_run.5`` -> ``jit_run``."""
    return re.sub(r"(\(\d+\)|\.\d+)$", "", name)


def merge(intervals) -> list[tuple]:
    out: list[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list[tuple]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def window_of(events: list[Event]) -> Optional[tuple]:
    rounds = [e for e in events if e.name == ROUND_SPAN]
    if not rounds:
        return None
    return (min(e.start for e in rounds), max(e.end for e in rounds))


def host_spans(events: list[Event]) -> list[Event]:
    return sorted((e for e in events if e.name.startswith(SPAN)),
                  key=lambda e: e.start)


def phase_at(spans: list[Event], t: float) -> str:
    """The innermost benchmark span that contains ``t``."""
    best = None
    for e in spans:
        if e.start > t:
            break
        if e.end >= t and (best is None or e.start >= best.start):
            best = e
    return best.name[len(SPAN):] if best is not None else "outside"


def reduce(events: list[Event], top: int = 10) -> Optional[Summary]:
    """None when the trace holds no window or no device plane."""
    win = window_of(events)
    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    if win is None or not planes:
        return None
    lo, hi = win
    busy_total = 0.0
    busy0 = None
    programs: dict = collections.defaultdict(float)
    ops: dict = collections.defaultdict(float)
    for plane in planes:
        op_ev = [e for e in events if e.plane == plane and e.line == OPS_LINE]
        mod_ev = sorted((e for e in events if e.plane == plane
                         and e.line == MODULES_LINE), key=lambda e: e.start)
        busy = clip(merge((e.start, e.end) for e in op_ev), lo, hi)
        busy_total += length(busy)
        if busy0 is None:
            busy0 = busy
        starts = [e.start for e in mod_ev]
        for e in mod_ev:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                programs[program_name(e.name)] += d
        for e in op_ev:
            d = min(e.end, hi) - max(e.start, lo)
            if d <= 0:
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            owner = program_name(mod_ev[i].name) \
                if i >= 0 and mod_ev[i].end >= e.start else "?"
            ops[f"{owner}:{op_name(e.name)}"] += d
    spans = host_spans(events)
    gaps = []
    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            gaps.append((phase_at(spans, 0.5 * (s + e)), e - s))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window=win, n_devices=len(planes),
                   busy_s=busy_total / len(planes), programs=dict(programs),
                   ops=sorted(ops.items(), key=lambda kv: -kv[1])[:top],
                   gaps=gaps[:top])


def describe(events: list[Event], per_line: int = 5) -> str:
    """Plane and line names with a few events each (to look at a trace)."""
    by = collections.defaultdict(list)
    for e in events:
        by[(e.plane, e.line)].append(e)
    rows = []
    for (plane, line), evs in sorted(by.items()):
        rows.append(f"{plane} | {line} | {len(evs)} events")
        for e in sorted(evs, key=lambda e: e.start)[:per_line]:
            rows.append(f"    {e.name} start={e.start:.6f} "
                        f"dur={e.end - e.start:.6f}")
    return "\n".join(rows)
