"""The round path's wall-clock spans (``repro.telemetry.profiler``): which
``fl.*`` spans a profiled sync round writes, how they nest, the bytes
``fl.h2d`` counts against shape arithmetic, and that the profiler changes
nothing in the run."""
import collections
import dataclasses

import jax
import numpy as np
import pytest

from bench import span_reduce, trace_reduce
from repro.orchestrator import OrchestratorConfig, run_orchestrated
from repro.sysmodel.population import FleetConfig
from repro.telemetry import profiler
from repro.train.fl_loop import FLRunConfig

N_DEVICES = 3
CFG = FLRunConfig(rounds=2, n_train=96, n_test=32, eval_every=1, lr=0.05,
                  batch_size=16, seed=5, use_planner=False)
PER_CLIENT = ("fl.schedule", "fl.batches", "fl.local_train", "fl.finish")
PER_ROUND = ("fl.round", "fl.channels", "fl.sort", "fl.aggregate",
             "fl.eval")


def _run(log_dir=None, *, use_pool=False, rounds=CFG.rounds):
    return run_orchestrated(dataclasses.replace(CFG, rounds=rounds),
                            FleetConfig(n_devices=N_DEVICES),
                            OrchestratorConfig(policy="sync",
                                               use_pool=use_pool),
                            jax_profile=log_dir)


def _spans(log_dir):
    _, spans = span_reduce.read(trace_reduce.find_xplane(str(log_dir)))
    return spans


def _batch_bytes(hist) -> int:
    """Minibatch bytes of every client trained: (steps, B) float32 images
    of 28x28x1 and int32 labels."""
    n = CFG.n_train // N_DEVICES
    bs = min(CFG.batch_size, n)
    steps = max(int(round(CFG.tau * n / bs)), 1)
    clients = sum(r.n_clients for r in hist.rounds)
    return clients * steps * bs * (28 * 28 * 4 + 4)


@pytest.fixture(scope="module")
def unpooled(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("unpooled")
    hist = _run(str(log_dir))
    return hist, _spans(log_dir)


def test_every_span_appears_once_per_round_or_client(unpooled):
    hist, spans = unpooled
    counts = collections.Counter(s.name for s in spans)
    trained = sum(r.n_clients for r in hist.rounds)
    assert trained > 0
    for name in PER_ROUND:
        assert counts[name] == CFG.rounds, name
    # P4 is solved for every device of a static fleet, feasible or not
    assert counts["fl.schedule"] == CFG.rounds * N_DEVICES
    for name in ("fl.batches", "fl.local_train", "fl.finish"):
        assert counts[name] == trained, name
    # minibatch images and labels
    assert counts["fl.h2d"] == 2 * trained
    # each client's bit count, the eval's accuracy and loss
    assert counts["fl.sync"] == trained + 2 * CFG.rounds
    rounds = sorted(s.stats["round"] for s in spans if s.name == "fl.round")
    assert rounds == list(range(CFG.rounds))
    for s in spans:
        if s.name in PER_CLIENT:
            assert {"round", "client"} <= set(s.stats), s


def test_spans_nest_inside_their_round(unpooled):
    _, spans = unpooled
    rounds = [s for s in spans if s.name == "fl.round"]
    for s in spans:
        if s.name == "fl.round":
            continue
        assert any(r.thread == s.thread and r.start <= s.start
                   and s.end <= r.end for r in rounds), s


def test_round_self_time_and_children_add_up(unpooled):
    """Per round: self time plus the union of the spans nested in it is
    its duration, so nested spans never overlap."""
    _, spans = unpooled
    for r in (s for s in spans if s.name == "fl.round"):
        inside = [s for s in spans if s is not r and s.thread == r.thread
                  and r.start <= s.start and s.end <= r.end]
        totals = span_reduce.reduce([r] + inside, (r.start, r.end))
        children = trace_reduce.length(trace_reduce.merge(
            (s.start, s.end) for s in inside))
        assert totals["fl.round"].self_s + children == pytest.approx(
            r.end - r.start, rel=0.01)
        assert totals["fl.round"].self_s > 0


@pytest.mark.parametrize("use_pool", [False, True])
def test_h2d_bytes_are_the_minibatches(use_pool, request, tmp_path):
    """On either route the finish is one compiled program per width: its
    segment ids are constants of the program, not transfers, so a round
    puts only the minibatches."""
    if use_pool:
        rounds = 1
        hist = _run(str(tmp_path), use_pool=True, rounds=rounds)
        spans = _spans(tmp_path)
    else:
        rounds = CFG.rounds
        hist, spans = request.getfixturevalue("unpooled")
    assert any(s.name == "fl.local_train" for s in spans)
    totals = span_reduce.reduce(spans, span_reduce.round_window(spans))
    want = _batch_bytes(hist)
    assert totals["fl.h2d"].stats["bytes"] == want
    got = span_reduce.per_round(totals, rounds)
    assert got["h2d.bytes"] == want / rounds
    assert set(got) == set(span_reduce.METRICS)


def test_profiler_leaves_the_round_bitwise_unchanged(unpooled):
    hist, _ = unpooled
    plain = _run()
    for a, b in zip(jax.tree.leaves(hist.params),
                    jax.tree.leaves(plain.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [r.test_loss for r in hist.rounds] == \
        [r.test_loss for r in plain.rounds]


def test_no_profiler_no_events(tmp_path):
    """Spans, puts and reads outside a profiler trace are not kept for a
    later one, and return what ``jnp.asarray``/``device_get`` would."""
    host = np.arange(6, dtype=np.float32)
    with profiler.span("fl.round", round=0):
        dev = profiler.put(host)
        back = profiler.read({"x": dev})
    np.testing.assert_array_equal(back["x"], host)
    assert isinstance(dev, jax.Array)
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    assert _spans(tmp_path) == []


def test_put_under_jit_emits_nothing(tmp_path):
    host = np.arange(4, dtype=np.int32)
    f = jax.jit(lambda v: v + profiler.put(host))
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = f(np.ones(4, np.int32))
        profiler.read(out)
    finally:
        jax.profiler.stop_trace()
    names = [s.name for s in _spans(tmp_path)]
    assert "fl.h2d" not in names
    assert names.count("fl.sync") == 1
