"""Reduce the program's ``fl.*`` host spans in a profiler trace.

The program marks its round path with wall-clock spans on the profiler's
clock (``repro.telemetry.profiler``): ``fl.round`` per round and, inside
it, ``fl.channels``, ``fl.sort``, ``fl.schedule``, ``fl.batches``,
``fl.local_train``, ``fl.finish``, ``fl.aggregate`` and ``fl.eval``;
``fl.h2d`` and ``fl.sync`` mark each explicit transfer to and from the
device and carry its size as the stat ``bytes``.  For each span name this
gives, clipped to a window:

* the count and the total seconds;
* the self seconds: the total less the part covered by ``fl.*`` spans
  nested in it on the same thread;
* the device's idle seconds inside it (idle: outside the busy union of the
  first device's ops, as ``trace_reduce`` computes it);
* the sum of each numeric stat other than the identifiers ``round`` and
  ``client``.

``trace_reduce`` reads the same trace for device time and names idle gaps
by the harness's ``bench:`` spans; this module leaves it as it is.
"""
from __future__ import annotations

import bisect
import collections
from typing import NamedTuple, Optional

from bench import trace_reduce as tr

PREFIX = "fl."
ROUND = PREFIX + "round"
IDS = ("round", "client")      # stats that name a span, not summed

# per-layer metric -> (span, what of it, unit); each is divided by rounds
METRICS = {
    "round.host_self_s": (ROUND, "self_s", "s/round"),
    "schedule.host_s": ("fl.schedule", "total_s", "s/round"),
    "batches.host_s": ("fl.batches", "total_s", "s/round"),
    "finish.host_s": ("fl.finish", "total_s", "s/round"),
    "finish.idle_s": ("fl.finish", "idle_s", "s/round"),
    "aggregate.host_s": ("fl.aggregate", "total_s", "s/round"),
    "h2d.bytes": ("fl.h2d", "bytes", "bytes/round"),
    "host_sync.count": ("fl.sync", "count", "count/round"),
}


class Span(NamedTuple):
    thread: tuple      # (plane, line): spans nest only on one thread
    name: str
    start: float       # seconds on the trace's clock
    end: float
    stats: dict


class Totals(NamedTuple):
    count: int
    total_s: float
    self_s: float
    idle_s: float
    stats: dict        # stat name -> sum


def read(path: str) -> tuple[list[tr.Event], list[Span]]:
    """Every event of the trace, as ``trace_reduce.read_events`` gives
    them, and the ``fl.*`` ones with their stats."""
    from jax.profiler import ProfileData
    events, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                end = s + e.duration_ns * 1e-9
                events.append(tr.Event(plane.name, line.name, e.name, s,
                                       end))
                if e.name.startswith(PREFIX):
                    spans.append(Span((plane.name, line.name), e.name, s,
                                      end, dict(e.stats)))
    return events, spans


def device_busy(events: list[tr.Event], window: tuple) -> list[tuple]:
    """The first device's busy union, clipped to ``window`` (empty when
    the trace has no device plane)."""
    planes = sorted({e.plane for e in events
                     if tr.DEVICE_PLANE.match(e.plane)})
    if not planes:
        return []
    return tr.clip(tr.merge((e.start, e.end) for e in events
                            if e.plane == planes[0]
                            and e.line == tr.OPS_LINE), *window)


def round_window(spans: list[Span]) -> Optional[tuple]:
    """From the first ``fl.round``'s start to the last one's end."""
    rounds = [s for s in spans if s.name == ROUND]
    if not rounds:
        return None
    return (min(s.start for s in rounds), max(s.end for s in rounds))


class _Busy:
    """Busy seconds inside any interval, by bisection over a sorted,
    disjoint union."""

    def __init__(self, busy: list[tuple]):
        self.starts = [s for s, _ in busy]
        self.ends = [e for _, e in busy]
        self.cum = [0.0]
        for s, e in busy:
            self.cum.append(self.cum[-1] + e - s)

    def inside(self, a: float, b: float) -> float:
        i = bisect.bisect_right(self.ends, a)
        j = bisect.bisect_left(self.starts, b)
        if i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, a - self.starts[i])
                - max(0.0, self.ends[j - 1] - b))


def reduce(spans: list[Span], window: tuple,
           busy: list[tuple] = ()) -> dict[str, Totals]:
    """Per span name: count, total, self and idle seconds and stat sums
    inside ``window``; ``busy`` is the device's busy union."""
    lo, hi = window
    dev = _Busy(list(busy))
    acc: dict = {}
    threads = collections.defaultdict(list)
    for s in spans:
        threads[s.thread].append(s)
    for evs in threads.values():
        evs.sort(key=lambda s: (s.start, -s.end))
        stack: list[Span] = []
        for s in evs:
            while stack and stack[-1].end <= s.start:
                stack.pop()
            a, b = max(s.start, lo), min(s.end, hi)
            if b <= a:
                continue
            t = acc.setdefault(s.name, [0, 0.0, 0.0, 0.0,
                                        collections.Counter()])
            t[0] += 1
            t[1] += b - a
            t[2] += b - a
            t[3] += (b - a) - dev.inside(a, b)
            for k, v in s.stats.items():
                if k not in IDS and isinstance(v, (int, float)) \
                        and not isinstance(v, bool):
                    t[4][k] += v
            if stack:      # the enclosing span loses the nested part
                parent = stack[-1]
                acc[parent.name][2] -= max(0.0, min(b, parent.end) - a)
            stack.append(s)
    return {k: Totals(v[0], v[1], v[2], v[3], dict(v[4]))
            for k, v in acc.items()}


def per_round(totals: dict[str, Totals], rounds: int) -> dict[str, float]:
    """``METRICS`` over ``rounds``; a metric whose span is missing from
    the trace is left out."""
    out = {}
    for metric, (name, what, _) in METRICS.items():
        t = totals.get(name)
        if t is None or rounds <= 0:
            continue
        v = t.stats.get(what, 0) if what == "bytes" else getattr(t, what)
        out[metric] = v / rounds
    return out
