"""Telemetry subsystem: registry semantics, trace exports, manifests,
bounded event-trace retention, and the no-op guarantee (telemetry on vs
off must be bitwise-identical on the seeded simulation)."""
import dataclasses
import json
import os

import pytest

from repro.orchestrator import OrchestratorConfig, run_orchestrated
from repro.orchestrator.events import EventQueue
from repro.sysmodel.population import FleetConfig
from repro.telemetry import (NULL_TELEMETRY, REQUIRED_KEYS, MetricsRegistry,
                             Telemetry, TraceSink, build_manifest,
                             to_jsonable, trace_signature_hash,
                             validate_manifest)
from repro.topology import TopologyConfig
from repro.train.fl_loop import (PHASES, FLRunConfig, History, RoundLog,
                                 run_fl)

TINY = dict(rounds=2, n_train=128, n_test=64, eval_every=1, lr=0.1,
            seed=0)


def _fleet(n=4):
    return FleetConfig(n_devices=n)


# ------------------------------------------------------------- registry

def test_counter_accumulates_per_label_set():
    reg = MetricsRegistry()
    reg.counter("energy", 2.0, device=1, phase="train")
    reg.counter("energy", 3.0, device=1, phase="train")
    reg.counter("energy", 5.0, device=2, phase="train")
    assert reg.value("energy", device=1, phase="train") == 5.0
    assert reg.value("energy", device=2, phase="train") == 5.0
    # label order must not matter
    assert reg.value("energy", phase="train", device=1) == 5.0


def test_gauge_last_write_wins_and_stores_verbatim():
    reg = MetricsRegistry()
    obj = 0.1 + 0.2          # a float with repr noise
    reg.gauge("acc", 0.5, round=0)
    reg.gauge("acc", obj, round=0)
    assert reg.value("acc", round=0) is obj


def test_histogram_appends():
    reg = MetricsRegistry()
    reg.observe("lat", 1.0, device=0)
    reg.observe("lat", 2.0, device=0)
    assert reg.value("lat", device=0) == [1.0, 2.0]
    assert reg.total("lat") == 3.0


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x", 1.0)
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x", 1.0)


def test_total_filters_on_label_superset():
    reg = MetricsRegistry()
    reg.counter("e", 1.0, device=0, phase="train", round=0)
    reg.counter("e", 2.0, device=0, phase="uplink", round=0)
    reg.counter("e", 4.0, device=1, phase="train", round=1)
    assert reg.total("e") == 7.0
    assert reg.total("e", phase="train") == 5.0
    assert reg.total("e", device=0) == 3.0
    assert reg.total("e", phase="train", round=1) == 4.0
    assert reg.total("missing") == 0.0


def test_series_sweeps_sorted_over_label():
    reg = MetricsRegistry()
    for r in (2, 0, 1):
        reg.gauge("acc", 0.1 * r, round=r)
    assert reg.series("acc", "round") == [(0, 0.0), (1, 0.1), (2, 0.2)]
    assert reg.label_values("acc", "round") == [0, 1, 2]


def test_registry_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("e", 1.5, phase="train")
    reg.gauge("acc", 0.25, round=0)
    path = str(tmp_path / "m.jsonl")
    n = reg.to_jsonl(path)
    rows = [json.loads(l) for l in open(path)]
    assert n == len(rows) == 2
    by_name = {r["name"]: r for r in rows}
    assert by_name["e"]["kind"] == "counter"
    assert by_name["e"]["labels"] == {"phase": "train"}
    assert by_name["e"]["value"] == 1.5
    assert by_name["acc"]["kind"] == "gauge"


# ----------------------------------------------------------- trace sink

def test_perfetto_schema():
    sink = TraceSink()
    sink.span("device/0", "train", 1.0, 3.0, round=0)
    sink.span("device/1", "uplink", 3.0, 4.0)
    sink.instant("server", "EDGE_MERGE", 4.5, cell=1)
    doc = sink.to_perfetto()
    evs = doc["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert len(spans) == 2 and len(instants) == 1
    tr = next(e for e in spans if e["name"] == "train")
    assert tr["ts"] == pytest.approx(1e6) and tr["dur"] == pytest.approx(2e6)
    assert tr["args"]["round"] == 0
    assert instants[0]["s"] == "t"
    # one process per track group, one thread per track
    names = {(e["pid"], e["tid"]): e["args"]["name"] for e in meta
             if e["name"] == "thread_name"}
    assert set(names.values()) == {"device/0", "device/1", "server"}
    procs = {e["args"]["name"] for e in meta if e["name"] == "process_name"}
    assert procs == {"device", "server"}
    # every event lands on a declared (pid, tid)
    for e in spans + instants:
        assert (e["pid"], e["tid"]) in names


def test_trace_jsonl_time_ordered(tmp_path):
    sink = TraceSink()
    sink.span("device/0", "b", 5.0, 6.0)
    sink.instant("server", "a", 1.0)
    path = str(tmp_path / "t.jsonl")
    n = sink.write_jsonl(path)
    rows = [json.loads(l) for l in open(path)]
    assert n == 2
    assert [r["name"] for r in rows] == ["a", "b"]
    assert rows[0]["type"] == "instant" and rows[1]["type"] == "span"


# ------------------------------------------------------------- manifest

def test_manifest_required_keys_and_hash():
    m = build_manifest(FLRunConfig(**TINY), _fleet(), OrchestratorConfig(),
                       trace_signature=(("x", 1),))
    assert validate_manifest(m) == []
    assert m["config"]["run"]["seed"] == 0
    assert m["seeds"]["run"] == 0
    assert m["trace_signature_hash"] == trace_signature_hash((("x", 1),))
    # stability: same signature, same hash; different signature differs
    assert trace_signature_hash((("x", 1),)) \
        != trace_signature_hash((("x", 2),))
    bad = {k: m[k] for k in list(m) if k != "git_sha"}
    assert validate_manifest(bad) == ["git_sha"]
    assert validate_manifest("not a dict") == list(REQUIRED_KEYS)


def test_to_jsonable_handles_configs():
    out = to_jsonable({"fleet": _fleet(), "t": (1, 2)})
    assert out["fleet"]["n_devices"] == 4
    assert out["t"] == [1, 2]
    json.dumps(out)   # must be serializable end to end


# --------------------------------------------- bounded trace retention

def _drive(q, seq):
    for t, kind, client in seq:
        q.push(t, kind, client)
    while len(q):
        q.pop()


def test_trace_limit_keeps_newest_and_counts_evictions():
    seq = [(float(i), "complete", i) for i in range(10)]
    q = EventQueue(trace_limit=3)
    _drive(q, seq)
    assert len(q.trace) == 3
    assert [c for _, _, _, c in q.trace] == [7, 8, 9]
    assert q.n_evicted == 7


def test_rolling_signature_matches_across_identical_runs():
    seq = [(float(i) * 0.5, "complete", i % 3) for i in range(20)]
    sigs = []
    for _ in range(2):
        q = EventQueue(trace_limit=4)
        _drive(q, seq)
        sigs.append(q.trace_signature())
    assert sigs[0] == sigs[1]
    assert sigs[0][0] == "blake2b" and sigs[0][1] == 20
    # a diverging pop sequence must change the signature
    q = EventQueue(trace_limit=4)
    _drive(q, seq[:-1] + [(99.0, "retry", 0)])
    assert q.trace_signature() != sigs[0]


def test_full_retention_signature_format_unchanged():
    seq = [(1.0, "complete", 0), (2.0, "churn", 1)]
    q = EventQueue()
    _drive(q, seq)
    sig = q.trace_signature()
    assert sig == ((1.0, 0, "complete", 0), (2.0, 1, "churn", 1))
    # a bounded queue that never evicted also keeps the tuple form
    q2 = EventQueue(trace_limit=10)
    _drive(q2, seq)
    assert q2.trace_signature() == sig


def test_rolling_signature_rejects_nondefault_digits():
    q = EventQueue(trace_limit=1)
    _drive(q, [(1.0, "complete", 0), (2.0, "complete", 1)])
    with pytest.raises(ValueError, match="digits"):
        q.trace_signature(digits=3)


def test_trace_limit_validation():
    with pytest.raises(ValueError):
        EventQueue(trace_limit=0)
    with pytest.raises(ValueError):
        OrchestratorConfig(event_trace_limit=0)


# -------------------------------------------------- no-op guard (slow)

def _row_key(hist):
    return [dataclasses.asdict(r) for r in hist.rounds]


@pytest.mark.slow
def test_telemetry_is_bitwise_invisible():
    """trace_signature + every RoundLog field identical with telemetry
    on vs off (the sync golden equivalence, telemetry edition)."""
    cfg = FLRunConfig(method="anycostfl", **TINY)
    h_off = run_fl(cfg, _fleet())
    h_on = run_fl(cfg, _fleet(), telemetry=Telemetry())
    assert h_off.trace == h_on.trace
    assert h_off.best_acc == h_on.best_acc
    assert _row_key(h_off) == _row_key(h_on)


@pytest.mark.slow
def test_phase_components_sum_to_totals():
    tol = 1e-9
    hists = [
        run_fl(FLRunConfig(method="anycostfl", **TINY), _fleet()),
        run_orchestrated(
            FLRunConfig(method="anycostfl", **TINY),
            FleetConfig(n_devices=6,
                        topology=TopologyConfig(kind="hier", n_cells=2)),
            OrchestratorConfig(policy="sync")),
    ]
    for hist in hists:
        for r in hist.rounds:
            assert sum(r.phase_energy().values()) \
                == pytest.approx(r.energy_j, rel=tol, abs=tol)
            assert sum(r.phase_latency().values()) \
                == pytest.approx(r.latency_s, rel=tol, abs=tol)
            assert sum(r.phase_comm().values()) \
                == pytest.approx(r.comm_bits, rel=tol, abs=tol)
        totals = hist.phase_totals()
        assert set(totals["energy_j"]) == set(PHASES)


@pytest.mark.slow
def test_fedbuff_energy_components_sum():
    hist = run_orchestrated(
        FLRunConfig(method="anycostfl", **TINY), _fleet(6),
        OrchestratorConfig(policy="fedbuff", buffer_size=3))
    assert hist.rounds
    for r in hist.rounds:
        assert r.energy_train_j + r.energy_uplink_j \
            == pytest.approx(r.energy_j, rel=1e-9, abs=1e-9)
        # critical-path latency attribution along the triggering arrival:
        # components must sum exactly to the merge-to-merge latency
        assert r.latency_train_s + r.latency_uplink_s \
            + r.latency_backhaul_s \
            == pytest.approx(r.latency_s, rel=1e-9, abs=1e-9)
        assert r.latency_train_s >= 0.0 and r.latency_uplink_s >= 0.0


# ------------------------------------------- RoundLog as registry view

def test_roundlog_view_over_registry():
    reg = MetricsRegistry()
    hist = History(FLRunConfig(**TINY), [], registry=reg)
    log = hist.log_round(0, latency_s=1.5, energy_j=2.5, flops=3.0,
                         comm_bits=4.0, mean_alpha=0.5, mean_beta=0.25,
                         mean_gain=1.0, energy_train_j=2.0,
                         energy_uplink_j=0.5)
    assert hist.rounds == [log]
    assert log.latency_s == 1.5
    assert reg.value("round.energy_j", round=0) == 2.5
    # the view reads back the exact stored objects
    assert RoundLog.from_registry(reg, 0) == log
    hist.log_eval(log, 0.75, 0.1)
    assert log.test_acc == 0.75 and hist.best_acc == 0.75
    assert reg.value("round.test_acc", round=0) == 0.75


def test_to_rows_emits_every_field():
    reg = MetricsRegistry()
    hist = History(FLRunConfig(**TINY), [], registry=reg)
    hist.log_round(0, latency_s=1.0, energy_j=1.0, flops=1.0,
                   comm_bits=8.0, mean_alpha=1.0, mean_beta=1.0,
                   mean_gain=1.0)
    rows = hist.to_rows()
    field_names = {f.name for f in dataclasses.fields(RoundLog)}
    assert field_names <= set(rows[0])
    assert {"cum_latency_s", "cum_energy_j", "cum_flops",
            "cum_comm_bits"} <= set(rows[0])


# ----------------------------------------------------- session / flush

def test_null_telemetry_is_inert(tmp_path):
    assert not NULL_TELEMETRY.enabled
    NULL_TELEMETRY.span("device/0", "train", 0.0, 1.0)
    NULL_TELEMETRY.counter("e", 1.0)
    assert NULL_TELEMETRY.flush() == {}


def test_session_flush_writes_bundle(tmp_path):
    tel = Telemetry(str(tmp_path / "out"))
    tel.span("device/0", "train", 0.0, 1.0, round=0)
    tel.instant("server", "EDGE_MERGE", 1.5)
    tel.counter("cost.energy_j", 1.0, phase="train")
    paths = tel.flush(manifest=build_manifest(FLRunConfig(**TINY)))
    assert set(paths) == {"perfetto", "trace_jsonl", "metrics_jsonl",
                          "manifest"}
    for p in paths.values():
        assert os.path.exists(p)
    doc = json.load(open(paths["perfetto"]))
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
    m = json.load(open(paths["manifest"]))
    assert validate_manifest(m) == []


def test_session_flush_without_dir_raises():
    with pytest.raises(ValueError, match="out_dir"):
        Telemetry().flush()


def test_profile_trace_fails_when_profiler_cannot_start(tmp_path,
                                                        monkeypatch):
    """--jax-profile DIR on a jaxlib whose profiler cannot start fails
    the run instead of running on without a profile."""
    import jax

    from repro.telemetry import profile_trace

    def refuse(_):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with profile_trace(str(tmp_path / "prof")):
            pass
    # no directory, no profiler: the body runs as it is
    with profile_trace(None) as log_dir:
        assert log_dir is None


def test_telemetry_session_holds_no_profiler_switch():
    """The profiler is the run's (--jax-profile DIR), not the telemetry
    session's: a profiled run is the plain program plus its spans."""
    assert not hasattr(Telemetry(), "jax_profile")
    assert not hasattr(NULL_TELEMETRY, "jax_profile")
