"""Checkpointing, fault tolerance, optimizer, sharding rules, data, compression."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.checkpoint.checkpoint import (CheckpointManager, latest_step,
                                         restore_checkpoint, save_checkpoint)
from repro.data.tokens import TokenPipeline
from repro.optim.compression import (compress_with_feedback, init_error_state,
                                     quantize_int8)
from repro.optim.optimizer import OptConfig, adamw_init, adamw_update, schedule_lr
from repro.parallel.sharding import (DEFAULT_RULES, logical_to_spec,
                                     zero1_spec)
from repro.runtime.fault_tolerance import (FaultInjector, HeartbeatMonitor,
                                           StragglerDetector, WorkerFailure)

# ------------------------------------------------------------- checkpoint --

def _state():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                       "b": jnp.ones(4)},
            "opt": {"m": jnp.zeros(4), "step": jnp.int32(7)}}


def test_checkpoint_roundtrip(tmp_path):
    st = _state()
    save_checkpoint(tmp_path, 5, st, extra={"data_state": {"step": 5}})
    assert latest_step(tmp_path) == 5
    restored, manifest = restore_checkpoint(tmp_path, st)
    assert manifest["step"] == 5
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_manager_async_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=2)
    st = _state()
    for s in (1, 2, 3, 4):
        mgr.save(s, st)
    mgr.wait()
    steps = sorted(int(p.stem.split("_")[1])
                   for p in tmp_path.glob("step_*.done"))
    assert steps == [3, 4]
    restored, manifest = mgr.restore_latest(st)
    assert manifest["step"] == 4


def test_checkpoint_prefers_committed(tmp_path):
    st = _state()
    save_checkpoint(tmp_path, 3, st)
    # a torn save: directory without .done marker
    (tmp_path / "step_9").mkdir()
    assert latest_step(tmp_path) == 3


# --------------------------------------------------------- fault tolerance --

def test_trainer_recovers_from_injected_failure(tmp_path):
    from repro.launch.train import Trainer
    inj = FaultInjector.worker_failure_at(step=6)
    tr = Trainer("tinyllama-1.1b", smoke=True, ckpt_dir=str(tmp_path),
                 fault_injector=inj, batch_override=4, seq_override=32)
    tr.restore_or_init()
    hist = tr.run(10, ckpt_every=2, log_every=100)
    assert tr.recoveries == 1
    assert tr.step_idx == 10
    # rollback happened: some steps re-executed from checkpoint at 6
    assert len(hist) >= 10
    # loss decreased overall
    assert hist[-1]["loss"] < hist[0]["loss"] * 1.1


def test_straggler_detector():
    d = StragglerDetector(factor=3.0, min_samples=3)
    for _ in range(5):
        assert not d.observe(0, 1.0)
    assert d.observe(5, 10.0)          # 10x slower -> flagged
    assert not d.observe(6, 1.0)       # ewma not poisoned


def test_heartbeat_monitor():
    m = HeartbeatMonitor(n_workers=2, timeout_s=10.0)
    m.beat(0, t=0.0)
    m.beat(1, t=0.0)
    m.check(t=5.0)
    m.beat(0, t=9.0)
    with pytest.raises(WorkerFailure):
        m.check(t=11.0)
    assert m.alive_workers() == [0]


# -------------------------------------------------------------- optimizer --

def test_adamw_reduces_quadratic():
    cfg = OptConfig(lr=0.1, warmup_steps=0, total_steps=100,
                    weight_decay=0.0, schedule="constant")
    params = {"x": jnp.asarray([3.0, -2.0])}
    opt = adamw_init(params)
    for _ in range(60):
        grads = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
        params, opt, m = adamw_update(cfg, params, grads, opt)
    assert float(jnp.sum(params["x"] ** 2)) < 0.2
    assert int(opt["step"]) == 60


def test_schedule_warmup_and_decay():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
    assert float(schedule_lr(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(schedule_lr(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(schedule_lr(cfg, jnp.int32(100))) == pytest.approx(0.0, abs=1e-6)


# ---------------------------------------------------------------- sharding --

def _mesh22():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def test_logical_to_spec_divisibility_fallback():
    mesh = _mesh22()
    # sizes divide trivially on a 1x1 mesh
    spec = logical_to_spec(("batch", "embed"), (8, 16), mesh)
    assert spec is not None


def test_zero1_spec_adds_data_axis():
    from jax.sharding import PartitionSpec as P
    mesh = _mesh22()
    sp = zero1_spec(P(None, "model"), (16, 32), mesh)
    assert sp[0] in ("data", ("data",)) or sp[0] is None  # 16 % 1 == 0


# -------------------------------------------------------------------- data --

def test_token_pipeline_deterministic_and_resumable():
    p1 = TokenPipeline(vocab=100, batch=8, seq_len=16, seed=3)
    a1, b1 = p1.batch_at(7)
    p2 = TokenPipeline.resume(100, 8, 16, p1.state(7))
    a2, b2 = p2.batch_at(7)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(b1, b2)
    assert a1.min() >= 0 and a1.max() < 100
    # labels are next-token shifted
    a3, b3 = p1.batch_at(8)
    assert not np.array_equal(a1, a3)


def test_token_pipeline_worker_sharding():
    full = TokenPipeline(vocab=50, batch=8, seq_len=8, seed=0, n_workers=1)
    w0 = TokenPipeline(vocab=50, batch=8, seq_len=8, seed=0, n_workers=2,
                       worker=0)
    w1 = TokenPipeline(vocab=50, batch=8, seq_len=8, seed=0, n_workers=2,
                       worker=1)
    t0, _ = w0.batch_at(0)
    t1, _ = w1.batch_at(0)
    assert t0.shape == (4, 8)
    assert not np.array_equal(t0, t1)


# ------------------------------------------------------------- compression --

def test_int8_quantization_bounded_error():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000).astype(np.float32))
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(x) - np.asarray(q, np.float32) * float(s))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_accumulates():
    grads = {"w": jnp.asarray([1e-4, 2e-4, 0.5])}   # tiny grads vanish in int8
    err = init_error_state(grads)
    deq1, err1 = compress_with_feedback(grads, err)
    # error carried: after many steps the cumulative signal gets through
    total = jnp.zeros(3)
    e = err
    for _ in range(100):
        d, e = compress_with_feedback(grads, e)
        total = total + d["w"]
    # mean dequantized grad ≈ true grad (error feedback is unbiased-ish)
    np.testing.assert_allclose(np.asarray(total) / 100,
                               np.asarray(grads["w"]), rtol=0.1, atol=1e-5)
