"""The per-layer metrics read from the program's spans, on synthetic span
lists, and the naming of idle time by a dotted child span."""
from __future__ import annotations

import json

import pytest

from tiny_cells import BENCH, fake_chips, make_root

import run as bench  # noqa: E402
from harness import trace as tr  # noqa: E402

MS = 1_000_000                     # ns

READERS = {"engine.dispatch_ms.qps": "dispatch",
           "engine.dispatch_ms.p50": "dispatch",
           "frontend.queue_wait_ms.p50": "queue_wait",
           "frontend.deliver_ms.p50": "finish.deliver"}


def _span(name, dur):
    return {"name": name, "ts": 0.0, "dur": dur, "id": 1, "parent": None,
            "track": None, "args": {}}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_span_reader_reads_the_median(metric):
    read = bench.load_module(bench.reader_path(metric)).read
    span = READERS[metric]
    spans = [_span(span, d) for d in (0.004, 0.001, 0.002)] + \
        [_span("other", 9.0), _span(span + "x", 9.0)]
    assert read(metric, {"spans": spans}) == pytest.approx(2.0)
    assert read(metric, {"spans": [_span("other", 1.0)]}) is None
    assert read(metric, {"spans": []}) is None


def test_idle_time_goes_to_the_dotted_child():
    host = [("dispatch", 0, 10 * MS), ("dispatch.gather", 2 * MS, 6 * MS),
            ("phase1", 12 * MS, 20 * MS), ("phase1.wait", 12 * MS, 18 * MS)]
    named = tr.name_gaps([(0, 20 * MS)], host)
    assert named["dispatch.gather"] == pytest.approx(0.004)
    assert named["dispatch"] == pytest.approx(0.006)
    assert named["phase1.wait"] == pytest.approx(0.006)
    assert named["phase1"] == pytest.approx(0.002)
    assert named["untracked"] == pytest.approx(0.002)


def test_trace_filter_keeps_dotted_children():
    kept = {"dispatch.gather", "stage.pad", "coalesce.take",
            "finish.deliver", "phase2.chunk", "cache_probe.commit"}
    for name in kept:
        assert name.split(".")[0] in tr.HOST_SPANS
    assert "queue_wait" not in tr.HOST_SPANS


@pytest.mark.parametrize("kind,metrics", [
    ("closed", {"engine.dispatch_ms.qps"}),
    ("open", {"engine.dispatch_ms.p50", "frontend.queue_wait_ms.p50",
              "frontend.deliver_ms.p50"})])
def test_traced_tiny_run_reports_span_metrics(kind, metrics, tmp_path,
                                              capsys):
    root = make_root(tmp_path)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    moves = "qps" if kind == "closed" else "p50_ms"
    entries = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in entries["per_layer"]:
        if m["name"] in metrics:
            doc["per_layer"].append(dict(m, moves=moves,
                                         workloads=[f"tiny.{kind}"]))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    rc = bench.main(["--workload", f"tiny.{kind}", "--seed", "4294967311",
                     "--seconds", "1", "--trace", "1"],
                    root=root, devices=fake_chips, compile_cache=False)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    for name in metrics:
        assert line["metrics"][name]["unit"] == "ms"
        assert line["metrics"][name]["value"] > 0


def test_program_spans_reach_the_trace_reader(tmp_path):
    """The program's dotted spans are in the profile the harness reads,
    and pass its filter of host spans."""
    import jax
    import numpy as np

    from repro import obs
    from repro.graphs.generators import random_dag
    from repro.reach import IndexSpec, QuerySession, build

    spec = IndexSpec()
    sess = QuerySession(build(random_dag(200, 1.5, seed=2), spec), spec)
    s = np.arange(100, dtype=np.int64)
    sess.query(s, s[::-1].copy())                  # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    obs.enable_tracing(True)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            sess.query(s, s[::-1].copy())
    finally:
        obs.enable_tracing(False)
        obs.get_tracer().clear()
        jax.profiler.stop_trace()
    _, host = tr.read_xplane(tr.find_xplane(str(tmp_path)),
                             host_names=tr.HOST_SPANS)
    names = {h[0] for h in host}
    assert {"dispatch", "dispatch.h2d", "dispatch.classify", "phase1",
            "phase1.wait", "phase1.tally", "stage.pad"} <= names, names
