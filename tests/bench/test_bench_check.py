"""The output check fails what it must: the reference in bfloat16 in the
program's place (the control), and runs with the local step broken
underneath the harness, on the pooled and the unpooled route.  CPU-sized
copy of the fmnist-cnn cells."""
import json

import jax
import pytest

from bench import cell as cell_mod, compare, harness, reference
from bench.paths import BENCH, ROOT

TINY = ROOT / "tests" / "bench" / "fixtures" / "tiny"
CELL = "fmnist.sync.unpooled"
LIMITS = harness.load_cell(CELL)[0]["limits"]
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _tiny(use_pool=True):
    workload, config = harness.load_cell("tiny.sync", TINY)
    workload = dict(workload, limits=LIMITS, use_pool=use_pool)
    return workload, config


STEPS = ("_local_steps_fast", "_local_steps")   # pooled, unpooled route


def unchanged(cell):
    """The local step returns the parameters it was given."""
    client = cell.sim.client
    for name in STEPS:
        setattr(client, name, lambda alpha, n: jax.jit(lambda p, b: p))


def half_batch(cell):
    """The local step trains on half of each minibatch."""
    client = cell.sim.client

    def halved(orig):
        def steps(alpha, n):
            run = orig(alpha, n)
            return jax.jit(lambda p, b: run(p, jax.tree.map(
                lambda x: x[:, : x.shape[1] // 2], b)))
        return steps

    for name in STEPS:
        setattr(client, name, halved(getattr(client, name)))


def _run(fault, use_pool):
    workload, config = _tiny(use_pool)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.run_cell(workload, config, 2**31 + 11, 0.5, False,
                            harness.cell_metrics(bench, CELL), 0.0, CPU,
                            bench_dir=TINY, break_program=fault)


@pytest.mark.parametrize("fault,expect", [
    (None, True), (unchanged, False), (half_batch, False)])
def test_run_is_correct_only_when_sound(fault, expect):
    _check_run(_run(fault, True), fault, expect)


@pytest.mark.parametrize("fault,expect", [
    (None, True), (unchanged, False), (half_batch, False)])
def test_unpooled_run_is_correct_only_when_sound(fault, expect):
    _check_run(_run(fault, False), fault, expect)


def _check_run(res, fault, expect):
    assert res.line["correct"] is expect, res.check
    assert list(res.line)[-1] == "check"
    assert res.line["attempted"] >= 1 and res.line["failed"] == 0
    assert set(res.line["metrics"]) == {"round_s", "client_samples_per_s",
                                        "setup_s"}
    if fault is None:    # warm-up left the window nothing to compile
        assert [r["compiles"] for r in res.notes[2]["window_rounds"]] == [
            0] * res.line["attempted"]


def test_control_in_bfloat16_fails_the_limits():
    workload, config = _tiny()
    seed = 5
    cell = cell_mod.Cell(workload, config, seed, cell_mod.CompileCounter())
    checked = [cell.checked_round()[1] for _ in range(3)]
    model, test = cell.model, cell.test
    cell.close()
    rounds = [c.clients for c in checked]
    ref = reference.follow(model, seed, rounds, config["lr"], test)
    control = reference.follow(model, seed, rounds, config["lr"], test,
                               dtype="bfloat16")
    values = compare.numbers(control, ref)
    assert not compare.verdict(values, LIMITS), values
    program = compare.numbers(harness.program_rounds(checked), ref)
    assert compare.verdict(program, LIMITS), program


def test_reference_init_is_seeded():
    model = reference.load_model("fmnist-cnn")
    a = model.init(reference.seed_key(2**33 + 1))
    b = model.init(reference.seed_key(2**33 + 1))
    c = model.init(reference.seed_key(1))
    assert (a["conv1"]["w"] == b["conv1"]["w"]).all()
    assert not (a["conv1"]["w"] == c["conv1"]["w"]).all()
    assert BENCH.is_dir()
