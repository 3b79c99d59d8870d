"""The CLI entry points: FL-mode architecture check and the placement of
JAX's persistent compilation cache."""
import os
import subprocess
import sys
import zlib

import pytest

from repro.launch import compile_cache
from repro.launch.train import build_parser, run_fl

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_fl_mode_refuses_non_cnn_arch():
    """A language model asked for in FL mode is refused, never swapped
    for the FMNIST CNN."""
    args = build_parser().parse_args(
        ["--mode", "fl", "--arch", "qwen2-7b", "--lr", "0.05"])
    with pytest.raises(SystemExit, match="qwen2-7b is a dense model"):
        run_fl(args)


_PROBE = ("import sys, jax; from repro.launch.compile_cache import "
          "enable_compile_cache; print(enable_compile_cache()); "
          "print(jax.config.jax_compilation_cache_dir); "
          "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
          " 0); c = float(sys.argv[1]); "
          "jax.jit(lambda x: x * 3 + c)(2.0).block_until_ready()")


def _probe(env_dir, nonce):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE, str(nonce)],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins where it is set; otherwise the
    cache sits at the fixed <checkout>/.jax_cache.  Either way the
    compiled program lands there: the probe compiles a program of its
    own (a constant taken from this test's temporary path), so the
    directory must gain an entry."""
    want = str(tmp_path / "cache") if from_env \
        else str(compile_cache.CHECKOUT / ".jax_cache")
    before = set(os.listdir(want)) if os.path.isdir(want) else set()
    nonce = zlib.crc32(str(tmp_path).encode())
    assert _probe(want if from_env else None, nonce) == [want, want]
    assert set(os.listdir(want)) - before


def test_jax_profile_writes_round_spans_without_telemetry(tmp_path):
    """--jax-profile DIR needs no --telemetry-dir: the round loop runs
    under the profiler and its trace, holding the fl.round spans, lands
    in DIR."""
    from bench import span_reduce, trace_reduce
    prof = tmp_path / "prof"
    args = build_parser().parse_args(
        ["--mode", "fl", "--method", "fedavg", "--devices", "3",
         "--rounds", "1", "--n-train", "96", "--n-test", "32",
         "--eval-every", "1", "--lr", "0.05", "--jax-profile", str(prof)])
    assert args.telemetry_dir is None
    run_fl(args)
    _, spans = span_reduce.read(trace_reduce.find_xplane(str(prof)))
    names = [s.name for s in spans]
    assert names.count("fl.round") == 1
    assert "fl.eval" in names
