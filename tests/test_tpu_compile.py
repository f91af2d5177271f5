"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler lowers each kernel at its deployment width
for a ``v5e:2x2`` topology that is described, not attached, and refuses
what the chip would refuse (unsupported Mosaic ops, scoped-VMEM overflow,
unaligned tiles) — faults that interpret mode on the CPU cannot see. Each
test asserts the kernel survived as a ``tpu_custom_call`` in the compiled
HLO.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.
"""
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist
from repro.kernels import ops
from repro.kernels.frontier import key_bits, max_batch
from repro.kernels.frontier_fused import (PROBE_BLOCK, _classify_call,
                                          _probe_kernel, _row_call,
                                          expand_frontier_fused)
from repro.kernels.interval_stab import (DEFAULT_BLOCK_Q,
                                         interval_stab_classify,
                                         interval_stab_classify_packed)
from repro.kernels.merge_cover import merge_cover_sorted_rows
from repro.reach import IndexSpec

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smoke():
    """chip_smoke.py's deployment constants (the module does no work on
    import)."""
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _compile(fn, *shapes, kernel=None):
    """Compile for the described chip; ``kernel`` is the op name the
    Pallas call must carry into a device profile."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if kernel is not None:
        assert f"%{kernel}." in text, kernel
    return compiled


@pytest.mark.parametrize("k2", [4, 16])
def test_stab_packed_compiles(one_chip, k2):
    q = 4 * DEFAULT_BLOCK_Q
    meta = jax.ShapeDtypeStruct((q, 4), jnp.int32, sharding=one_chip)
    slab = jax.ShapeDtypeStruct((q, k2), jnp.int32, sharding=one_chip)
    _compile(lambda ms, mt, s: interval_stab_classify_packed(
        ms, mt, s, block_q=DEFAULT_BLOCK_Q), meta, meta, slab,
        kernel="interval_stab_classify_packed")


def test_stab_12_array_compiles(one_chip):
    q, k, w = 4 * DEFAULT_BLOCK_Q, 8, 1

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    row = sds((q,), jnp.int32)
    slab = sds((q, k), jnp.int32)
    seed = sds((q, w), jnp.uint32)
    _compile(lambda *a: interval_stab_classify(*a, block_q=DEFAULT_BLOCK_Q),
             row, row, row, row, row, slab, slab, slab,
             seed, seed, seed, seed)


def test_frontier_probe_compiles(one_chip):
    c = 8 * PROBE_BLOCK
    lane = jax.ShapeDtypeStruct((c,), jnp.int32, sharding=one_chip)
    probe = lambda *r: _probe_kernel(*r, vbits=22)  # noqa: E731
    _compile(lambda *a: _row_call(probe, a, block=PROBE_BLOCK,
                                  interpret=False, name="frontier_probe"),
             lane, lane, lane, lane, lane, kernel="frontier_probe")


def test_frontier_classify_emit_compiles(one_chip):
    c = 4096 + 1

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(lambda ms, mt, s, key, eq: _classify_call(
        ms, mt, s, key, eq, block=PROBE_BLOCK, interpret=False),
        sds((c, 4)), sds((c, 4)), sds((c, 16)), sds((c,)),
        sds((c,), jnp.bool_), kernel="frontier_classify_emit")


@pytest.mark.parametrize("m", [17, 2049])
def test_merge_cover_compiles(one_chip, m):
    """m = 2049 is the widest single-shot merge (256 children × W = 8 + the
    tree interval) at the default block of 128 rows."""
    s = jax.ShapeDtypeStruct((1024, m), jnp.int32, sharding=one_chip)
    compiled = _compile(lambda b, e, x: merge_cover_sorted_rows(
        b, e, x, k=8, w_out=8), s, s, s, kernel="merge_cover")
    print(f"merge_cover m={m}: {compiled.memory_analysis()}")


def test_fused_frontier_loop_compiles(one_chip, smoke):
    """The whole jitted phase-2 while_loop at the smoke graph's node count,
    a full-width ELL slab and the serving default cap."""
    n, w, k2, m_t = smoke.N_NODES, 32, 16, 64
    q = min(IndexSpec().phase2_chunk, max_batch(n))
    assert key_bits(n) <= 30

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    packed = {"meta": sds((n, 4)), "slab": sds((n, k2))}
    compiled = _compile(
        lambda pk, ell, ts, td, hub, cs, ct, pad: expand_frontier_fused(
            pk, ell, ts, td, hub, cs, ct, pad, max_steps=smoke.LAYERS + 1,
            cap=4096, interpret=False),
        packed, sds((n, w)), sds((m_t,)), sds((m_t,)), sds((n,), jnp.bool_),
        sds((q,)), sds((q,)), sds((q,), jnp.bool_), kernel="frontier_probe")
    print(f"expand_frontier_fused n={n}: {compiled.memory_analysis()}")


@pytest.mark.parametrize("placement,shape", [("replicated", (4, 1)),
                                             ("sharded", (1, 4))])
def test_four_chip_serving_compiles(topo, smoke, monkeypatch, placement,
                                    shape):
    """Phase-1 classify and the phase-2 fused expansion under shard_map on
    the four described chips, at the smoke graph's node count."""
    # the wrappers pick the interpreter from the (CPU) default backend;
    # this compile targets the described TPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:4], dtype=object).reshape(shape),
                ("data", "model"))
    n = -(-smoke.N_NODES // shape[1]) * shape[1]
    rows = NamedSharding(mesh, P("model", None))
    rep = NamedSharding(mesh, P(None))
    qs = NamedSharding(mesh, P("data"))

    def sds(s, dt=jnp.int32, sh=rows):
        return jax.ShapeDtypeStruct(s, dt, sharding=sh)
    slab, meta = sds((n, 16)), sds((n, 4))
    q = IndexSpec().max_batch
    _compile(lambda sl, me, cs, ct: dist.classify_sharded(
        mesh, {"slab": sl, "meta": me}, cs, ct, use_pallas=True),
        slab, meta, sds((q,), sh=qs), sds((q,), sh=qs))
    chunk = min(IndexSpec().phase2_chunk, max_batch(n)) * shape[0]
    _compile(lambda sl, me, ell, ts, td, hub, cs, ct, pad:
             dist.expand_frontier_sharded(
                 mesh, sl, me, ell, ts, td, hub, cs, ct, pad, n_nodes=n,
                 max_steps=smoke.LAYERS + 1, cap=4096, step_impl="pallas",
                 interpret=False),
             slab, meta, sds((n, 32)), sds((64,), sh=rep),
             sds((64,), sh=rep), sds((n,), jnp.bool_, rep),
             sds((chunk,), sh=qs), sds((chunk,), sh=qs),
             sds((chunk,), jnp.bool_, qs))
