"""bench/trace_reduce.py: busy union, idle share, program time and idle
gaps, on hand-made events and on a trace recorded on a TPU v5e."""
import pathlib

import pytest

from bench import trace_reduce as tr

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_small.xplane.pb"
DEV = "/device:TPU:0"


def ev(plane, line, name, start, end):
    return tr.Event(plane, line, name, start, end)


def test_reduce_on_hand_made_events():
    events = [
        ev("/host:CPU", "python", "bench:round", 0.0, 10.0),
        ev("/host:CPU", "python", "bench:prepare", 0.0, 3.0),
        ev("/host:CPU", "python", "bench:eval", 8.0, 10.0),
        ev(DEV, "XLA Modules", "jit_run(7)", 3.0, 6.0),
        ev(DEV, "XLA Modules", "jit_ev(9)", 8.5, 9.5),
        # two overlapping ops count once in the busy union
        ev(DEV, "XLA Ops", "fusion.1", 3.0, 5.0),
        ev(DEV, "XLA Ops", "convolution.2", 4.0, 6.0),
        ev(DEV, "XLA Ops", "fusion.3", 8.5, 9.5),
        # outside the window: ignored
        ev(DEV, "XLA Ops", "fusion.4", 11.0, 12.0),
    ]
    s = tr.reduce(events)
    assert s.window == (0.0, 10.0)
    assert s.busy_s == pytest.approx(4.0)
    assert s.programs == {"jit_run": 3.0, "jit_ev": 1.0}
    assert s.gaps[0] == ("prepare", pytest.approx(3.0))
    assert sorted(s.gaps) == sorted([("prepare", pytest.approx(3.0)),
                                     ("round", pytest.approx(2.5)),
                                     ("eval", pytest.approx(0.5))])
    assert dict(s.ops)["jit_run:fusion.1"] == pytest.approx(2.0)


def test_reduce_without_device_or_window_reads_nothing():
    host = [ev("/host:CPU", "python", "bench:round", 0.0, 1.0)]
    assert tr.reduce(host) is None
    dev = [ev(DEV, "XLA Ops", "fusion.1", 0.0, 1.0)]
    assert tr.reduce(dev) is None


def test_program_names_drop_their_ids():
    assert tr.program_name("jit_run(1234)") == "jit_run"
    assert tr.program_name("jit_agg.17") == "jit_agg"
    assert tr.program_name("jit_ev") == "jit_ev"


def test_reduce_on_a_trace_recorded_on_the_chip():
    """Two 'rounds' on one v5e chip: jitted tanh(x @ x) twice under
    bench:local_train, a 20 ms host sleep under bench:prepare, a jitted
    sum(sin(x)) read back under bench:eval."""
    s = tr.reduce(tr.read_events(str(FIXTURE)))
    assert s.n_devices == 1
    assert s.window == pytest.approx((0.043137, 0.0892925), abs=1e-6)
    assert set(s.programs) == {"jit__lambda"}
    # ops never overlap within a program here: busy equals program time
    assert s.busy_s == pytest.approx(s.programs["jit__lambda"], rel=0.01)
    assert 0 < s.busy_s < 1e-4
    assert 1.0 - s.busy_s / s.window_s > 0.99
    assert [g[0] for g in s.gaps[:2]] == ["prepare", "prepare"]
    assert s.gaps[0][1] == pytest.approx(0.0212, abs=5e-4)
    assert s.ops[0][0] == "jit__lambda:sine_reduce_fusion"
