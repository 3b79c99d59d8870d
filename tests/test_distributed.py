"""Numerics of the compressed cross-pod gradient sync (subprocess with 2
host devices acting as 2 pods) and the mesh-mapped edge-cell route."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.aggregation import aio_aggregate_stacked
from repro.core.distributed import (anycost_gradient_sync,
                                    mean_gradient_sync,
                                    mesh_cell_aggregate)
from jax import shard_map

mesh = jax.make_mesh((2,), ("pod",))
g = {"w": (jnp.arange(64, dtype=jnp.float32).reshape(2, 32) + 1.0) / 64.0,
     "b": jnp.asarray([[1.0, -2.0], [3.0, -4.0]])}
# leaves have a leading per-pod dim -> shard over pod
specs = jax.tree.map(lambda _: P("pod"), g)

def run(fn, tree=g):
    out = shard_map(fn, mesh=mesh,
                    in_specs=(jax.tree.map(lambda _: P("pod"), tree),),
                    out_specs=jax.tree.map(lambda _: P("pod"), tree),
                    check_vma=False)(tree)
    return jax.tree.map(np.asarray, out)

exact = run(lambda x: mean_gradient_sync(x, "pod"))
lossless = run(lambda x: anycost_gradient_sync(x, "pod", keep_frac=1.0,
                                               quantize=False))
quant = run(lambda x: anycost_gradient_sync(x, "pod", keep_frac=1.0,
                                            quantize=True))
sparse = run(lambda x: anycost_gradient_sync(x, "pod", keep_frac=0.25,
                                             quantize=False))
err_lossless = max(float(np.abs(exact[k] - lossless[k]).max()) for k in exact)
err_quant = max(float(np.abs(exact[k] - quant[k]).max()) for k in exact)
# sparse path: kept coordinates must match the exact mean where both pods
# kept them; everything is bounded by the max gradient magnitude
amax = max(float(np.abs(exact[k]).max()) for k in exact)
err_sparse = max(float(np.abs(exact[k] - sparse[k]).max()) for k in exact)

# ---- zero-collision: pod 0 keeps a coordinate whose int8 level rounds to
# zero (|g| << amax/254); the explicit keep mask must count it in the AIO
# denominator, so the aggregate at that coordinate is the *mean* of the
# two dequantized contributions, not pod 1's value alone.
z = {"w": jnp.stack([jnp.asarray([100.0, 0.05, 50.0, -25.0]),
                     jnp.asarray([100.0, 8.0, 50.0, -25.0])])}
qz = run(lambda x: anycost_gradient_sync(x, "pod", keep_frac=0.999999,
                                         quantize=True), z)
# pod 0's 0.05 quantizes to level 0 -> dequantized 0; pod 1 sends ~8.0.
# masked den = 2 -> aggregate ~= 4.0; den inferred from vals != 0 would
# have given ~8.0.
collision_val = float(qz["w"][0, 1])

# ---- mesh-mapped edge cells: shard-local absorb + psum monoid merge
# equals the flat stacked oracle (any device->cell split)
key = jax.random.PRNGKey(0)
ku, km, kw = jax.random.split(key, 3)
I, N = 8, 640
u = jax.random.normal(ku, (I, N), jnp.float32)
mk = (jax.random.uniform(km, (I, N)) > 0.4).astype(jnp.float32)
w = jax.random.uniform(kw, (I,), jnp.float32, 0.5, 1.5)
cmesh = jax.make_mesh((2,), ("cell",))
out_mesh = mesh_cell_aggregate(u, mk, w, cmesh)
out_flat = aio_aggregate_stacked(u, mk, w)
err_mesh = float(jnp.max(jnp.abs(out_mesh - out_flat)))
num, den = mesh_cell_aggregate(u, mk, w, cmesh, finalize=False)
err_part = float(jnp.max(jnp.abs(
    jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0) - out_flat)))

print(json.dumps({"err_lossless": err_lossless, "err_quant": err_quant,
                  "err_sparse": err_sparse, "amax": amax,
                  "collision_val": collision_val,
                  "err_mesh": err_mesh, "err_part": err_part}))
"""


@pytest.mark.slow
def test_anycost_sync_numerics():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # keep_frac=1, no quant -> exact AIO mean == psum mean
    assert res["err_lossless"] < 1e-6
    # int8 quantization error bounded by one step of the amax scale
    assert res["err_quant"] <= res["amax"] / 127.0 + 1e-6
    # sparsified sync stays bounded (drops only small coordinates)
    assert res["err_sparse"] <= res["amax"]
    # a kept-but-quantized-to-zero coordinate dilutes the mean (den counts
    # it via the explicit mask): mean(0, ~8) ~= 4, not pod 1's 8
    assert res["collision_val"] == pytest.approx(4.0, abs=0.5)
    # mesh-mapped cells == flat oracle (float-reordering tolerance)
    assert res["err_mesh"] < 1e-5
    assert res["err_part"] < 1e-5
