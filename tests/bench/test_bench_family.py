"""A model family other than the image CNNs, added as files in a directory
of its own (``fixtures/toy``: a one-block grouped-query attention LM and a
token task): it loads, the reference follows it and it is counted, with no
file under ``bench/`` written for it.  The width-group description is
checked on it and on the CNNs: shrink then expand puts every kept element
back in place, and the mask covers exactly those."""
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness, reference
from bench.paths import BENCH, ROOT

TOY = ROOT / "tests" / "bench" / "fixtures" / "toy"
STEPS, B = 4, 8


def _toy():
    workload, config = harness.load_cell("toy.sync", TOY)
    model = reference.load_model(config["arch"], TOY)
    return config, model, reference.load_task(model.TASK, TOY)


def test_a_model_family_is_added_by_files_alone():
    config, model, task = _toy()
    assert pathlib.Path(model.__file__).parent.parent == TOY
    assert pathlib.Path(task.__file__).parent.parent == TOY
    assert not (BENCH / "models" / f"{config['arch']}.py").exists()
    assert not (BENCH / "tasks" / f"{model.TASK}.py").exists()
    # the CNNs' files are still found from a directory that lacks them
    cnn = reference.load_model("fmnist-cnn", TOY)
    assert pathlib.Path(cnn.__file__).parent == BENCH / "models"
    with pytest.raises(FileNotFoundError):
        reference.load_model("no-such-arch", TOY)


def test_reference_follows_a_toy_lm():
    config, model, task = _toy()
    seed = 2**33 + 3
    k_data, _ = jax.random.split(reference.seed_key(seed))
    train, test = (task.rows(s) for s in task.make(k_data, config))
    rounds = [[reference.Client(
        alpha=a, beta=b, key=jax.random.PRNGKey(3 * r + i),
        batches={k: x[at:at + STEPS * B].reshape((STEPS, B) + x.shape[1:])
                 for k, x in train.items()})
        for i, (a, b, at) in enumerate(zip(
            (0.25, 0.55, 1.0), (0.5, 0.3, 0.7), (0, 32, 64)))]
        for r in range(2)]
    before = reference.test_loss(model, jax.jit(model.init)(
        jax.random.split(reference.seed_key(seed))[1]), test)
    f = reference.follow(model, seed, rounds, config["lr"], test, keep=True)
    assert set(f.deltas[0]) == set(reference.flat(model.param_shapes()))
    assert f.deltas[0]["block.attn.wq.w"] > 0
    assert f.deltas[0]["block.mlp.up.w"] > 0
    assert all(math.isfinite(x) for x in f.losses)
    assert f.losses[-1] < before
    assert f.change_sq[0] > 0 and len(f.updates) == 3
    control = reference.follow(model, seed, rounds, config["lr"], test,
                               dtype="bfloat16")
    assert control.change_sq[0] != f.change_sq[0]


def test_flops_count_a_toy_lm():
    """Counted by hand: D 16, KV 2, 3 query heads per KV head, HEAD 4,
    FF 24, VOCAB 32, 11 predicted positions; alpha 0.25 keeps 2 query
    heads per KV head and 12 hidden units."""
    _, model, _ = _toy()
    t = 11
    full = (2 * t * (16 * 24 + 16 * 8 + 16 * 8 + 24 * 16) + 4 * t * t * 24
            + 2 * t * (16 * 24 + 24 * 16) + 2 * t * 16 * 32)
    quarter = (2 * t * (16 * 16 + 16 * 8 + 16 * 8 + 16 * 16)
               + 4 * t * t * 16 + 2 * t * (16 * 12 + 12 * 16)
               + 2 * t * 16 * 32)
    assert flops.forward(model, 1.0) == full
    assert flops.forward(model, 0.25) == quarter
    assert flops.train(model, 0.25) == 3 * quarter
    assert flops.sub_shapes(model, 0.25)["block.attn.wo.w"] == (16, 16)


def _expected_mask(model, shape, path, alpha):
    """Ones on the channels the width groups keep, built index by index."""
    mask = np.ones(shape, bool)
    n = reference.widths(model, alpha)
    for g in model.width_groups():
        for e in g.entries:
            if e.path == path:
                pos = np.arange(shape[e.axis])
                kept = (pos // e.block) % g.size < n[g.name]
                bcast = [1] * len(shape)
                bcast[e.axis] = -1
                mask &= kept.reshape(bcast)
    return mask


@pytest.mark.parametrize("arch,bench_dir", [
    ("toy-attn", TOY), ("fmnist-cnn", BENCH), ("vgg9-cifar", BENCH)])
@pytest.mark.parametrize("alpha", [0.25, 0.55, 1.0])
def test_shrink_then_expand_puts_kept_elements_back(arch, bench_dir, alpha):
    model = reference.load_model(arch, bench_dir)
    shapes = reference.flat(model.param_shapes())
    keys = jax.random.split(jax.random.PRNGKey(0), len(shapes))
    params = reference.nest({p: jax.random.normal(k, s) for k, (p, s) in
                             zip(keys, shapes.items())})
    sorted_p = reference.sort_channels(model, params)
    sub = reference.shrink(model, sorted_p, alpha)
    sub_shapes = flops.sub_shapes(model, alpha)
    assert {p: x.shape for p, x in reference.flat(sub).items()} == sub_shapes
    full, mask = (reference.flat(t) for t in
                  reference.expand(model, sub, shapes))
    for path, x in reference.flat(sorted_p).items():
        want = _expected_mask(model, x.shape, path, alpha)
        np.testing.assert_array_equal(np.asarray(mask[path]) == 1, want)
        assert int(np.sum(mask[path])) == math.prod(sub_shapes[path])
        np.testing.assert_array_equal(full[path], np.where(want, x, 0.0))


@pytest.mark.parametrize("arch,bench_dir", [
    ("toy-attn", TOY), ("fmnist-cnn", BENCH)])
def test_sorting_channels_keeps_the_function(arch, bench_dir):
    """Every leaf a group's permutation touches is in the group: the
    sorted model computes what the unsorted one does."""
    model = reference.load_model(arch, bench_dir)
    task = reference.load_task(model.TASK, bench_dir)
    config = harness.load_cell(
        "toy.sync" if bench_dir == TOY else "fmnist.sync.unpooled",
        bench_dir)[1]
    config = dict(config, n_train=16, n_test=16)
    _, test = task.make(jax.random.PRNGKey(1), config)
    params = model.init(jax.random.PRNGKey(2))
    params = jax.tree.map(lambda x: x + 0.1 * jax.random.normal(
        jax.random.PRNGKey(3), x.shape), params)     # biases not all zero
    sorted_p = reference.sort_channels(model, params)
    batch = jax.tree.map(jnp.asarray, task.rows(test))
    assert float(model.loss(sorted_p, batch)) == pytest.approx(
        float(model.loss(params, batch)), rel=1e-5)
    assert any(not np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(sorted_p)))


@pytest.mark.parametrize("layout", [dict(outer=3), dict(block=3)])
def test_a_width_group_that_does_not_match_its_leaf_raises(layout):
    """A model file whose group declares a wrong ``outer`` or ``block``
    fails at sort, shrink and expand instead of cutting the wrong lanes."""
    model = reference.load_model("toy-attn", TOY)

    class Wrong:
        param_shapes = staticmethod(model.param_shapes)

        @staticmethod
        def width_groups():
            return tuple(g._replace(entries=tuple(
                e._replace(**layout) if e.path == "block.attn.wo.w" else e
                for e in g.entries)) for g in model.width_groups())

    shapes = reference.flat(model.param_shapes())
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="block.attn.wo.w"):
        reference.sort_channels(Wrong, params)
    with pytest.raises(ValueError, match="block.attn.wo.w"):
        reference.shrink(Wrong, params, 0.25)
    sub = reference.shrink(model, params, 0.25)
    with pytest.raises(ValueError, match="block.attn.wo.w"):
        reference.expand(Wrong, sub, shapes)
