"""Pipeline parallelism + compressed psum on a multi-device debug mesh."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

# these tests need >1 device: run in a subprocess with forced host devices
SUBPROCESS_TEMPLATE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
{body}
"""


def run_with_devices(body: str):
    r = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_TEMPLATE.format(body=body)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": str(SRC)})
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_pipeline_matches_sequential():
    out = run_with_devices(r"""
from repro.parallel.pipeline import pipeline_forward, demo_stage_fn
mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(0)
D, B, S = 8, 16, 4
params = {"w": jnp.asarray(rng.standard_normal((S, D, D)), jnp.float32),
          "w2": jnp.asarray(rng.standard_normal((S, D, D)), jnp.float32)}
x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
pipe = pipeline_forward(mesh, demo_stage_fn, n_stages=S, microbatches=4)
got = jax.jit(pipe)(params, x)
want = x
for i in range(S):
    want = demo_stage_fn({"w": params["w"][i], "w2": params["w2"][i]}, want)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
print("PIPELINE_OK")
""")
    assert "PIPELINE_OK" in out


def test_compressed_psum_close_to_exact():
    out = run_with_devices(r"""
from jax.sharding import PartitionSpec as P
from repro.optim.compression import compressed_psum
from repro.parallel.sharding import shard_map_compat
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
f = shard_map_compat(lambda v: compressed_psum(v[0], "data"), mesh=mesh,
                     in_specs=P("data", None), out_specs=P(None))
got = jax.jit(f)(x)
want = np.asarray(x).sum(0)
err = np.abs(np.asarray(got) - want).max()
scale = np.abs(np.asarray(x)).max() / 127.0
assert err <= 4 * scale + 1e-6, (err, scale)
print("PSUM_OK")
""")
    assert "PSUM_OK" in out


def test_gnn_sharded_segment_sum_matches_local():
    out = run_with_devices(r"""
from repro.models.gnn import _sharded_segment_reduce
from repro.parallel.sharding import ShardingCtx
mesh = jax.make_mesh((4, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
m, n, d = 64, 10, 5
x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
seg = jnp.asarray(rng.integers(0, n, m), jnp.int32)
got = jax.jit(lambda a, b: _sharded_segment_reduce(a, b, n, ShardingCtx(mesh)))(x, seg)
want = np.zeros((n, d), np.float32)
np.add.at(want, np.asarray(seg), np.asarray(x))
np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
print("SEGSUM_OK")
""")
    assert "SEGSUM_OK" in out
