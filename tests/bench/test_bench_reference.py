"""The fmnist-cnn reference against a golden recorded before its layer
table moved behind the model-file interface: the same weights, clients and
minibatches give the same deltas, test losses and local changes, to every
digit, in float32, in the bfloat16 control and with half batches."""
import json

import jax
import numpy as np
import pytest

from bench import harness, reference
from bench.paths import ROOT

FIXTURES = ROOT / "tests" / "bench" / "fixtures"
GOLDEN = json.loads((FIXTURES / "fmnist-reference-golden.json").read_text())
SEED = 2**33 + 5
ALPHAS = ((0.25, 0.55, 1.0), (0.4, 0.7, 0.85))
BETAS = ((1 / 15, 0.2, 0.5), (0.05, 0.3, 1 / 15))
STEPS, B = 4, 8


def _rounds_and_test():
    """Two rounds of three clients on the tiny fixture's data, each with
    its own rows of the train split."""
    _, config = harness.load_cell("tiny.sync", FIXTURES / "tiny")
    model = reference.load_model(config["arch"])
    task = reference.load_task(model.TASK)
    k_data, _ = jax.random.split(reference.seed_key(SEED))
    train, test = (task.rows(s) for s in task.make(k_data, config))
    rounds = []
    for r, (alphas, betas) in enumerate(zip(ALPHAS, BETAS)):
        clients = []
        for i, (a, b) in enumerate(zip(alphas, betas)):
            at = (3 * r + i) * STEPS * B
            clients.append(reference.Client(
                alpha=a, beta=b,
                key=jax.random.fold_in(jax.random.PRNGKey(7), 10 * r + i),
                batches={k: x[at:at + STEPS * B].reshape(
                    (STEPS, B) + x.shape[1:]) for k, x in train.items()}))
        rounds.append(clients)
    return model, config, rounds, test


@pytest.mark.parametrize("side,kw", [
    ("float32", {}), ("bfloat16", {"dtype": "bfloat16"}),
    ("half_batch", {"half_batch": True})])
def test_fmnist_reference_matches_its_golden(side, kw):
    model, config, rounds, test = _rounds_and_test()
    f = reference.follow(model, SEED, rounds, config["lr"], test, keep=True,
                         **kw)
    want = GOLDEN[side]
    assert f.deltas == want["deltas"]
    assert f.losses == want["losses"]
    assert f.change_sq == want["change_sq"]
    assert [float(np.sum(np.square(u.astype(np.float64))))
            for u in f.updates] == want["update_sq"]
    assert {k: float(np.sum(np.square(np.asarray(v, np.float64))))
            for k, v in f.change.items()} == want["change_sq_by_leaf"]
