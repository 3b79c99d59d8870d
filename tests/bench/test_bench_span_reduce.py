"""bench/span_reduce.py: count, total, self and idle seconds and stat sums
of the program's fl.* spans, on hand-made spans and on a trace recorded
on a TPU v5e (which holds none)."""
import pathlib

import pytest

from bench import span_reduce as sr
from bench import trace_reduce as tr

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_small.xplane.pb"
HOST = ("/host:CPU", "python")


def sp(name, start, end, thread=HOST, **stats):
    return sr.Span(thread, name, start, end, stats)


def test_self_time_leaves_out_nested_spans():
    spans = [
        sp("fl.round", 0.0, 10.0, round=0),
        sp("fl.finish", 1.0, 4.0, client=1),
        sp("fl.h2d", 1.5, 2.0, bytes=100),
        sp("fl.sync", 3.0, 3.5, bytes=4),
        sp("fl.finish", 5.0, 6.0, client=2),
        # another thread: nests in nothing here
        sp("fl.batches", 2.0, 9.0, thread=("/host:CPU", "worker")),
    ]
    t = sr.reduce(spans, (0.0, 10.0))
    assert t["fl.round"].total_s == pytest.approx(10.0)
    assert t["fl.round"].self_s == pytest.approx(10.0 - 3.0 - 1.0)
    assert t["fl.finish"].count == 2
    assert t["fl.finish"].total_s == pytest.approx(4.0)
    assert t["fl.finish"].self_s == pytest.approx(4.0 - 0.5 - 0.5)
    assert t["fl.batches"].self_s == pytest.approx(7.0)
    # with no device busy, all of a span is idle
    assert t["fl.finish"].idle_s == pytest.approx(4.0)


def test_idle_inside_a_span_is_its_time_off_the_device():
    spans = [sp("fl.round", 0.0, 10.0), sp("fl.finish", 2.0, 8.0)]
    busy = [(1.0, 3.0), (4.0, 5.0), (7.5, 9.0)]
    t = sr.reduce(spans, (0.0, 10.0), busy)
    assert t["fl.round"].idle_s == pytest.approx(10.0 - 4.5)
    # busy inside [2, 8]: 1 + 1 + 0.5
    assert t["fl.finish"].idle_s == pytest.approx(6.0 - 2.5)


def test_stats_sum_and_identifiers_do_not():
    spans = [sp("fl.h2d", 0.0, 1.0, bytes=3140, round=0, client=4),
             sp("fl.h2d", 1.0, 2.0, bytes=6653480, round=0, client=4),
             sp("fl.sync", 2.0, 3.0, bytes=4)]
    t = sr.reduce(spans, (0.0, 3.0))
    assert t["fl.h2d"].stats == {"bytes": 3140 + 6653480}
    m = sr.per_round(t, 2)
    assert m["h2d.bytes"] == (3140 + 6653480) / 2
    assert m["host_sync.count"] == 0.5


def test_spans_are_clipped_to_the_window():
    spans = [sp("fl.round", 0.0, 4.0), sp("fl.round", 4.0, 8.0),
             sp("fl.eval", 3.0, 5.0), sp("fl.sort", 9.0, 10.0)]
    t = sr.reduce(spans, (2.0, 6.0))
    assert t["fl.round"].count == 2
    assert t["fl.round"].total_s == pytest.approx(4.0)
    assert t["fl.eval"].total_s == pytest.approx(2.0)
    assert "fl.sort" not in t
    assert sr.round_window(spans) == (0.0, 8.0)


def test_a_trace_without_fl_spans_reads_no_host_metric():
    assert sr.per_round({}, 3) == {}
    assert sr.round_window([]) is None


def test_recorded_trace_reads_as_trace_reduce_reads_it():
    """The recorded chip trace through this reader: the same events, so
    the same Summary, and no fl.* span (it predates them)."""
    events, spans = sr.read(str(FIXTURE))
    assert spans == []
    assert events == tr.read_events(str(FIXTURE))
    s = tr.reduce(events)
    assert s == tr.reduce(tr.read_events(str(FIXTURE)))
    busy = sr.device_busy(events, s.window)
    assert tr.length(busy) == pytest.approx(s.busy_s)
