"""The benchmark's data files: they parse, cross-reference and keep to the
character rules, and a cell can be added by adding files alone."""
import json
import re
import shutil

import pytest

from bench import harness, reference
from bench.paths import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


def test_benchmark_keys_and_names():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in METRICS]
    for group in ("configs", "workloads"):
        names += [e["name"] for e in BENCHMARK[group]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e


@pytest.mark.parametrize("cell", sorted(
    p.stem for p in (BENCH / "workloads").glob("*.json")))
def test_cell_files_parse_and_agree(cell):
    """Every workload file parses; one that BENCHMARK.json names agrees
    with its entry there."""
    workload, config = harness.load_cell(cell)
    assert workload["name"] == cell
    assert workload["config"] == config["name"]
    assert set(workload["limits"]) <= {"loss_gap", "train_gap",
                                       "update_gap"}
    entry = next((w for w in BENCHMARK["workloads"] if w["name"] == cell),
                 None)
    if entry is not None:
        assert workload["config"] == entry["config"]
        assert workload["chips"] == entry["chips"]
        conf = next(c for c in BENCHMARK["configs"]
                    if c["name"] == config["name"])
        assert (ROOT / conf["file"]).is_file()
        assert conf["reduced"] == config["reduced"]
    for key in ("source", "reduced", "assumed", "arch"):
        assert key in config
    reference.load_model(config["arch"])
    for m in harness.cell_metrics(BENCHMARK, cell)[1]:
        assert harness.load_metric(m["name"]).UNIT == m["unit"]


def test_every_file_under_bench_is_named_by_the_rules():
    for p in BENCH.rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            rel = p.relative_to(ROOT).as_posix()
            assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", rel), rel


def test_peaks_table_refuses_unknown_devices():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.DeviceError):
        harness.peaks("cpu")


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A throwaway workload and configuration in their own directory load
    with the same code, and the metrics it reports follow BENCHMARK.json."""
    shutil.copytree(BENCH / "configs", tmp_path / "configs")
    shutil.copytree(BENCH / "workloads", tmp_path / "workloads")
    extra = json.loads((BENCH / "workloads" / "fmnist.sync.json")
                       .read_text())
    extra.update(name="fmnist.extra", eval_every=1, why="added by a test")
    (tmp_path / "workloads" / "fmnist.extra.json").write_text(
        json.dumps(extra))
    workload, config = harness.load_cell("fmnist.extra", tmp_path)
    assert workload["name"] == "fmnist.extra"
    assert config["name"] == "fmnist-cnn"
    bench = dict(BENCHMARK, workloads=BENCHMARK["workloads"] + [
        {"name": "fmnist.extra", "config": "fmnist-cnn", "traffic": "x",
         "chips": 1, "why": "added by a test"}])
    e2e, per_layer = harness.cell_metrics(bench, "fmnist.extra")
    assert {m["name"] for m in e2e} == {m["name"] for m in
                                         BENCHMARK["end_to_end"]}
    assert {m["name"] for m in per_layer} == {
        m["name"] for m in BENCHMARK["per_layer"] if "workloads" not in m}
