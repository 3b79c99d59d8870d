"""The client finish (``AnycostClient.finish_round``): one compiled
program per width bucket, the same one the pooled route calls through
``finish_round_fast``, with the sub-model sliced by a compiled shrink."""
import collections

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import schedule, shrinking
from repro.core.anycost import AnycostClient

ALPHA = 0.4
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _strategy(beta):
    return schedule.Strategy(alpha=ALPHA, beta=beta, freq=1e9, phi=0.5,
                             varphi=0.5, gain=0.05, T_cmp=1, T_com=1,
                             E_cmp=1, E_com=1, feasible=True)


@pytest.fixture(scope="module")
def setup():
    from repro.models.registry import build_model
    cfg = get_config("fmnist-cnn")
    model = build_model(cfg)
    spec = shrinking.cnn_shrink_spec(cfg)
    sorted_global = shrinking.sort_channels(
        model.init(jax.random.PRNGKey(0)), spec)
    sub = shrinking.shrink(sorted_global, ALPHA, spec)
    leaves, treedef = jax.tree_util.tree_flatten(sub)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    trained = jax.tree_util.tree_unflatten(treedef, [
        x - 0.01 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    return model, spec, sorted_global, sub, trained


def _client(model, spec):
    return AnycostClient(model, spec, lr=0.05, batch_size=32)


def test_finish_round_equals_finish_round_fast(setup):
    model, spec, sorted_global, sub, trained = setup
    client = _client(model, spec)
    key = jax.random.PRNGKey(7)
    strat = _strategy(1 / 15)
    a = client.finish_round(sorted_global, ALPHA, trained, strat, 4, key,
                            w_per_sample=2.0)
    b = client.finish_round_fast(ALPHA, trained, strat, 4, key, sub=sub,
                                 w_per_sample=2.0)
    for x, y in zip(jax.tree.leaves((a.values, a.mask)),
                    jax.tree.leaves((b.values, b.mask))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a.bits == b.bits > 0
    assert (a.beta_realized, a.n_samples, a.flops) == \
        (b.beta_realized, b.n_samples, b.flops)
    # FGC kept a strict subset of the trained width's coordinates
    kept = sum(float(np.sum(m)) for m in jax.tree.leaves(a.mask))
    width = sum(x.size for x in jax.tree.leaves(sub))
    assert 0 < kept < width


def test_new_beta_or_key_lowers_no_new_program(setup):
    """Per alpha: one finish program (``jit_core``) and one shrink
    program (``jit_shrink``); a new beta or key each round lowers
    nothing, so a warmed round compiles nothing."""
    model, spec, sorted_global, _, trained = setup
    client = _client(model, spec)
    lowered = collections.Counter()

    def seen(event, _secs, fun_name="", **_kw):
        if event == LOWERING:
            lowered[fun_name] += 1

    jax.monitoring.register_event_duration_secs_listener(seen)
    try:
        first = client.finish_round(sorted_global, ALPHA, trained,
                                    _strategy(0.05), 4,
                                    jax.random.PRNGKey(0))
        after_first = sum(lowered.values())
        second = client.finish_round(sorted_global, ALPHA, trained,
                                     _strategy(1 / 15), 4,
                                     jax.random.PRNGKey(3))
    finally:
        jax.monitoring.unregister_event_duration_listener(seen)
    assert lowered["jit(core)"] == 1
    assert lowered["jit(shrink)"] == 1
    assert sum(lowered.values()) == after_first
    # the second call really ran at its own rate
    assert second.bits > first.bits
