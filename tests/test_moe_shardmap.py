"""shard_map MoE (EP-local dispatch) vs the gather baseline.

Needs >1 device — run in a subprocess with forced host devices (the main
test process must keep seeing 1 device; see conftest).
"""
import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

TEMPLATE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
{body}
"""


def run_with_devices(body: str):
    r = subprocess.run(
        [sys.executable, "-c", TEMPLATE.format(body=body)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": str(SRC)})
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


COMMON = r"""
from dataclasses import replace
from repro.configs.base import LMConfig, MoESpec
from repro.models import transformer as tf
from repro.parallel.sharding import ShardingCtx

import pytest

# LLM-architecture lane — excluded from the reachability tier-1
# CI job, run by the arch-lane job instead (pytest.ini)
pytestmark = pytest.mark.arch

cfg = LMConfig(arch_id="t", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
               d_ff=32, vocab=64, dtype="float32", remat=False,
               moe=MoESpec(n_experts=8, top_k=2, capacity_factor=8.0,
                           dispatch="sort"))
rng = np.random.default_rng(0)
B, S, D = 8, 4, cfg.d_model
E, F = cfg.moe.n_experts, cfg.d_ff
lp = {
    "router": jnp.asarray(rng.standard_normal((D, E)), jnp.float32),
    "w_gate": jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32),
    "w_up":   jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32),
    "w_down": jnp.asarray(rng.standard_normal((E, F, D)) * 0.1, jnp.float32),
}
x = jnp.asarray(rng.standard_normal((B, S, D)), jnp.float32)
"""


def test_shardmap_matches_gather_tokens_sharded():
    """Train/prefill mode: batch over data, experts over model. With a
    capacity factor high enough that nothing drops, the EP-local dispatch
    must match the global-gather reference exactly."""
    out = run_with_devices(COMMON + r"""
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = ShardingCtx(mesh)
ref = jax.jit(lambda lp, x: tf._moe_ffn_gather(cfg, lp, x, ctx))(lp, x)
got = jax.jit(lambda lp, x: tf._moe_ffn_shardmap(cfg, lp, x, ctx))(lp, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("FWD_OK")

# gradients must match too (shard_map + psum transpose path)
def loss_ref(lp, x):
    return jnp.sum(tf._moe_ffn_gather(cfg, lp, x, ctx) ** 2)
def loss_sm(lp, x):
    return jnp.sum(tf._moe_ffn_shardmap(cfg, lp, x, ctx) ** 2)
g_ref = jax.jit(jax.grad(loss_ref))(lp, x)
g_sm = jax.jit(jax.grad(loss_sm))(lp, x)
for k in lp:
    np.testing.assert_allclose(np.asarray(g_sm[k]), np.asarray(g_ref[k]),
                               rtol=5e-4, atol=5e-4, err_msg=k)
print("GRAD_OK")
""")
    assert "FWD_OK" in out and "GRAD_OK" in out


def test_shardmap_matches_gather_tokens_replicated():
    """Decode mode: tokens replicated, expert mlp dim sharded over data
    (weight-capacity-bound serving). Combine psums over (model, data)."""
    out = run_with_devices(COMMON + r"""
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx_serve = ShardingCtx(mesh, {"mlp": "data"})
xb = x[:, :1]                                   # decode: [B, 1, D]
ref = jax.jit(lambda lp, x: tf._moe_ffn_gather(cfg, lp, x, ctx_serve))(lp, xb)
got = jax.jit(lambda lp, x: tf._moe_ffn_shardmap(cfg, lp, x, ctx_serve))(lp, xb)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("DECODE_OK")
""")
    assert "DECODE_OK" in out


def test_shardmap_drops_match_gshard_semantics():
    """With a tight capacity, per-shard dropping must still produce finite
    outputs and drop AT MOST as many tokens as the worst shard's overflow
    (sanity: no NaNs, zero rows only for dropped tokens)."""
    out = run_with_devices(COMMON + r"""
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = ShardingCtx(mesh)
cfg_tight = replace(cfg, moe=replace(cfg.moe, capacity_factor=0.5))
y = jax.jit(lambda lp, x: tf._moe_ffn_shardmap(cfg_tight, lp, x, ctx))(lp, x)
assert np.isfinite(np.asarray(y)).all()
print("TIGHT_OK")
""")
    assert "TIGHT_OK" in out
