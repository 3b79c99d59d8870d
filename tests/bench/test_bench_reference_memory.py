"""The reference holds memory in proportion to the update and nothing
bigger: its client program embeds no constant as large as the update and
keeps its bytes per parameter, local training and the test loss take at
most the model's ``PASS_ROWS`` rows in one pass, and
``bench/reference_probe.py`` follows the toy LM end to end.  The 307 M-parameter fixture is only counted here; it runs on
the chip through the probe."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, reference_probe
from bench.paths import ROOT

TOY = ROOT / "tests" / "bench" / "fixtures" / "toy"
SIZES = {"7M": {"D": 512, "HEAD": 64, "FF": 2048, "VOCAB": 4096},
         "27M": {"D": 1024, "HEAD": 128, "FF": 4096, "VOCAB": 8192}}
ALPHA = 0.55
# temp, output and constant bytes per parameter of the compiled client
# program at alpha 0.55 on the CPU, at both sizes (40.7 with the
# whole-vector FGC and its segment-id constant)
BYTES_PER_PARAM = 22.07
CONSTANT = re.compile(r"= (\w+)\[([\d,]*)\]\S* constant\(")


def _toy(**widths):
    """toy-attn at other widths; each load is a module of its own."""
    model = reference.load_model("toy-attn", TOY)
    for k, v in widths.items():
        setattr(model, k, v)
    return model


def _kernels(model) -> int:
    return sum(s[-1] if len(s) >= 2 else 1
               for s in reference.flat(model.param_shapes()).values())


def _shapes(model) -> dict:
    return reference.nest({
        p: jax.ShapeDtypeStruct(s, jnp.float32)
        for p, s in reference.flat(model.param_shapes()).items()})


@pytest.fixture(scope="module")
def compiled():
    """Per size: (parameters, kernels, lowered text, compiled program)."""
    out = {}
    for name, widths in SIZES.items():
        model = _toy(**widths)
        batches = {"tokens": jax.ShapeDtypeStruct((4, 8, model.SEQ),
                                                  jnp.int32)}
        fn = reference._client_fn(model, ALPHA, 0.05, "float32", False,
                                  True, False)
        lowered = fn.lower(_shapes(model), batches,
                           jax.ShapeDtypeStruct((), jnp.float32),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
        out[name] = (reference.n_params(model), _kernels(model),
                     lowered.as_text(), lowered.compile())
    return out


def _constants(text: str) -> list:
    """(elements, bytes) of each constant in an HLO module's text."""
    out = []
    for dtype, dims in CONSTANT.findall(text):
        n = math.prod(int(d) for d in dims.split(",") if d)
        width = 1 if dtype == "pred" else int(re.sub(r"\D", "", dtype)) // 8
        out.append((n, n * width))
    return out


def test_client_program_embeds_nothing_the_size_of_the_update(compiled):
    for n, kernels, _, program in compiled.values():
        assert max(e for e, _ in _constants(program.as_text())) <= kernels
    small, large = (compiled[k][2] for k in SIZES)
    assert len(large) < 1.1 * len(small)


@pytest.mark.parametrize("size", SIZES)
def test_client_program_bytes_per_parameter(compiled, size):
    n, _, _, program = compiled[size]
    mem = program.memory_analysis()
    const = sum(b for _, b in _constants(program.as_text()))
    per = (mem.temp_size_in_bytes + mem.output_size_in_bytes + const) / n
    assert per <= 30
    assert abs(per / BYTES_PER_PARAM - 1) <= 0.1, per


def test_test_loss_blocks_bound_the_loss_program():
    """fmnist keeps blocks of 1,000 rows; the chip fixture's loss program,
    whose temporaries grow with its rows, stays under 2 GiB at its
    ``PASS_ROWS`` rows of 2,048 tokens."""
    assert not hasattr(reference.load_model("fmnist-cnn"), "PASS_ROWS")
    assert reference.PASS_ROWS == 1000
    lm = reference.load_model("toy-attn-300m", TOY)
    temp = {}
    for rows in (lm.PASS_ROWS, 2 * lm.PASS_ROWS):
        batch = {"tokens": jax.ShapeDtypeStruct((rows, lm.SEQ), jnp.int32)}
        temp[rows] = reference._loss_sum(lm).lower(
            _shapes(lm), batch).compile().memory_analysis().temp_size_in_bytes
    assert temp[lm.PASS_ROWS] <= 2**31
    assert temp[2 * lm.PASS_ROWS] >= 1.5 * temp[lm.PASS_ROWS]


def test_test_loss_is_the_same_in_any_blocks():
    model = _toy()
    params = jax.jit(model.init)(jax.random.PRNGKey(3))
    rows = {"tokens": jax.random.randint(jax.random.PRNGKey(4),
                                         (30, model.SEQ), 0, model.VOCAB)}
    whole = reference.test_loss(model, params, rows)
    model.PASS_ROWS = 7
    assert reference.test_loss(model, params, rows) == pytest.approx(
        whole, rel=1e-6)


def test_training_in_passes_matches_one_pass():
    """A minibatch of 8 rows in 4 passes of 2 gives one pass's local
    change, to rounding; rows that do not split evenly are refused."""
    whole, parts = _toy(), _toy(PASS_ROWS=2)
    params = jax.jit(whole.init)(jax.random.PRNGKey(5))
    batches = {"tokens": jax.random.randint(jax.random.PRNGKey(6),
                                            (3, 8, whole.SEQ), 0,
                                            whole.VOCAB)}
    args = (jnp.float32(0.3), jax.random.PRNGKey(8))
    outs = [reference._client_fn(m, 1.0, 0.05, "float32", False, True,
                                 True)(params, batches, *args)
            for m in (whole, parts)]
    a, b = (jax.tree.leaves(o[3]) for o in outs)
    for x, y in zip(a, b):
        np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-7)
    assert float(outs[1][2]) == pytest.approx(float(outs[0][2]), rel=1e-5)
    odd = _toy(PASS_ROWS=3)
    with pytest.raises(ValueError, match="equal passes"):
        reference._client_fn(odd, 1.0, 0.05, "float32", False, True, False
                             ).lower(params, batches, *args)


def test_the_probe_follows_the_toy_lm():
    out = reference_probe.run(TOY, "toy-lm", 2**33 + 11, rounds=2,
                              clients=3, steps=2)
    assert out["n_params"] == 2888
    assert len(out["losses"]) == 2
    assert all(math.isfinite(x) and x > 0 for x in out["losses"])
    assert all(x > 0 for x in out["change_sq"])
    with pytest.raises(ValueError, match="train rows"):
        reference_probe.run(TOY, "toy-lm", 1, rounds=3, clients=3, steps=2)


def test_the_chip_fixture_has_300m_parameters():
    model = reference.load_model("toy-attn-300m", TOY)
    assert reference.n_params(model) == 307_531_424
    assert _kernels(model) < 10**5


def test_the_chip_fixture_fits_one_v5e():
    """Compiled for a described v5e, without the chip: the fixture's
    alpha-1 client program on 2 steps of 8 rows of 2,048 tokens, with the
    round's ``num`` and ``den`` beside it, fits in 14 GB of the chip's
    16."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    lm = reference.load_model("toy-attn-300m", TOY)
    n = reference.n_params(lm)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        _shapes(lm))
    args = (params,
            {"tokens": jax.ShapeDtypeStruct((2, 8, lm.SEQ), jnp.int32,
                                            sharding=chip)},
            jax.ShapeDtypeStruct((), jnp.float32, sharding=chip),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        mem = reference._client_fn(lm, 1.0, 0.01, "float32", False, True,
                                   False).lower(*args).compile(
                                   ).memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    held = (mem.temp_size_in_bytes + mem.output_size_in_bytes
            + mem.argument_size_in_bytes + 2 * 4 * n)
    assert held <= 14e9, held
    # passes of one row keep the temporaries near 3.5 GB; one pass of all
    # 8 rows holds their attention scores at once, near 10 GB
    assert mem.temp_size_in_bytes < 4.5e9
