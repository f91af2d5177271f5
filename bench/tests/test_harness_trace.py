"""The reduction from trace to numbers, on a synthetic trace shaped like a
TPU profile, and on a profile recorded here; the peaks table."""
from __future__ import annotations

import pytest

from tiny_cells import BENCH  # noqa: F401  (puts bench/ on the path)

from harness import kernels, peaks  # noqa: E402
from harness import trace as tr  # noqa: E402

MS = 1_000_000                     # ns

STAB = ("%interval_stab_classify_packed.1 = s32[1,16384]{1,0:T(1,128)S(1)} "
        "custom-call(s32[4,16384]{1,0:T(4,128)S(1)} %bitcast.5, "
        "s32[4,16384]{1,0:T(4,128)S(1)} %bitcast.6, "
        "s32[12,16384]{1,0:T(8,128)S(1)} %bitcast.7), "
        "custom_call_target=\"tpu_custom_call\"")
GATHER = ("%fusion.1 = s32[16384,4]{0,1:T(4,128)} fusion(s32[693947,4]"
          "{0,1:T(4,128)} %meta, s32[16384]{0:T(1024)S(1)} %f.4), "
          "kind=kCustom, calls=%fused_computation.1")
LOOP = "%while.4 = (s32[4096]{0:T(1024)}, pred[]{:T(512)}) while(%tuple)"
PROBE = ("%custom-call.43 = u32[256,31250]{0,1:T(8,128)} custom-call("
         "u32[256,31250]{0,1:T(8,128)} %gte.1, s32[4096]{0:T(1024)} %gte.2)")


def synthetic():
    device = {"/device:TPU:0": {
        "modules": [("jit__unknown(1)", 10 * MS, 14 * MS),
                    ("jit_expand_frontier_fused(2)", 20 * MS, 30 * MS)],
        "ops": [(GATHER, 10 * MS, 12 * MS), (STAB, 12 * MS, 13 * MS),
                (LOOP, 20 * MS, 30 * MS), (PROBE, 21 * MS, 24 * MS),
                (PROBE, 25 * MS, 28 * MS)]}}
    host = [("bench.window", 0, 100 * MS), ("bench.query", 5 * MS, 40 * MS),
            ("phase1", 6 * MS, 15 * MS), ("phase2", 16 * MS, 35 * MS),
            ("phase2.host_fallback", 31 * MS, 35 * MS),
            ("bench.wait", 50 * MS, 90 * MS)]
    return device, host


def test_reduce_synthetic_trace():
    red = tr.reduce_trace(*synthetic())
    assert red["window_s"] == pytest.approx(0.1)
    # busy: 10-13 ms and the loop 20-30 ms
    assert red["busy_s"] == pytest.approx(0.013)
    ops = dict(red["device_ops"])
    assert ops["jit__unknown/interval_stab_classify_packed.1"] == \
        pytest.approx(0.001)
    assert ops["jit_expand_frontier_fused/custom-call.43"] == \
        pytest.approx(0.006)
    assert not any("while" in k for k in ops)
    gaps = dict(red["idle_gaps"])
    assert gaps["untracked"] == pytest.approx(0.005 + 0.010 + 0.010)
    assert gaps["bench.query"] == pytest.approx(0.001 + 0.001 + 0.005)
    assert gaps["phase1"] == pytest.approx(0.004 + 0.002)
    assert gaps["phase2"] == pytest.approx(0.004 + 0.001)
    assert gaps["phase2.host_fallback"] == pytest.approx(0.004)
    assert gaps["bench.wait"] == pytest.approx(0.040)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.013)
    stab = [k for k in red["kernels"]
            if k["op"].startswith("interval_stab")]
    assert stab == [{"module": "jit__unknown",
                     "op": "interval_stab_classify_packed.1",
                     "traffic_per_call": (0, 4 * 16384 * (4 + 4 + 12),
                                          4 * 16384),
                     "seconds": pytest.approx(0.001), "calls": 1}]
    assert red["ops_s"]["jit__unknown/fusion.1"] == pytest.approx(0.002)


def test_call_traffic_reads_shapes_and_memory_spaces():
    assert kernels.call_traffic(PROBE) == \
        (2 * 256 * 31250 * 4 + 4096 * 4, 0, 0)
    assert kernels.call_traffic(STAB) == (0, 4 * 16384 * 20, 4 * 16384)
    assert kernels.call_traffic(GATHER) is None
    assert kernels.op_kind(LOOP) == "while"
    peak = peaks.peaks_for("TPU v5 lite")
    least = kernels.least_seconds(kernels.call_traffic(STAB), peak)
    assert least == pytest.approx(4 * 16384 * 20 / 18432.00247463936e9)


def test_merge_and_gaps():
    busy = tr.merge([(5, 8), (1, 3), (2, 4), (9, 20)], 0, 10)
    assert busy == [[1, 4], [5, 8], [9, 10]]
    assert tr.gaps(busy, 0, 10) == [(0, 1), (4, 5), (8, 9)]


def test_window_span_required():
    with pytest.raises(ValueError):
        tr.reduce_trace({}, [("phase1", 0, 1)])


def test_recorded_profile_has_its_window(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.query"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    device, host = tr.read_xplane(tr.find_xplane(str(tmp_path)),
                                  host_names=tr.HOST_SPANS)
    names = {h[0] for h in host}
    assert {tr.WINDOW_SPAN, "bench.query"} <= names
    lo, hi = tr.window_of(host)
    assert hi > lo


def test_peaks_table():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")
