"""Unified telemetry layer (repro.obs, DESIGN.md §8): histogram bucket
semantics + merge, Prometheus exposition golden, collector GC, span
nesting under double-buffered slab overlap, Chrome trace export, and the
LatencyTrack / IndexSpec.latency_window degenerate cases."""
import gc
import json
from dataclasses import dataclass

import numpy as np
import pytest

from repro.obs import enable_tracing, get_tracer
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import Tracer
from repro.reach import IndexSpec
from repro.reach.frontend.stats import LatencyTrack


# ------------------------------------------------------------- histograms
def test_histogram_bucket_boundaries_are_inclusive():
    h = Histogram("h", buckets=(0.25, 1.0, 4.0))
    # le buckets: a value EQUAL to a boundary counts in that bucket
    for v, want in [(0.1, 0), (0.25, 0), (0.26, 1), (1.0, 1),
                    (4.0, 2), (4.5, 3)]:
        before = list(h.counts)
        h.observe(v)
        diff = [a - b for a, b in zip(h.counts, before)]
        assert diff[want] == 1 and sum(diff) == 1, (v, diff)
    assert h.count == 6
    assert h.sum == pytest.approx(0.1 + 0.25 + 0.26 + 1.0 + 4.0 + 4.5)


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram("h", buckets=())
    with pytest.raises(ValueError):
        Histogram("h", buckets=(1.0, 1.0, 2.0))      # not strictly increasing
    with pytest.raises(ValueError):
        Histogram("h", buckets=(2.0, 1.0))


def test_histogram_merge_bucketwise():
    a = Histogram("h", buckets=(1.0, 2.0))
    b = Histogram("h", buckets=(1.0, 2.0))
    for v in (0.5, 1.5, 9.0):
        a.observe(v)
    for v in (0.25, 0.75):
        b.observe(v)
    a.merge(b)
    assert a.counts == [3, 1, 1]
    assert a.count == 5
    assert a.sum == pytest.approx(0.5 + 1.5 + 9.0 + 0.25 + 0.75)


def test_histogram_merge_rejects_different_boundaries():
    a = Histogram("h", buckets=(1.0, 2.0))
    b = Histogram("h", buckets=(1.0, 3.0))
    with pytest.raises(ValueError, match="identical boundaries"):
        a.merge(b)


# --------------------------------------------------------------- registry
def test_counter_monotone_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7)
    g.dec(2)
    assert g.value == 5.0
    # get-or-make returns the same object; a type conflict is an error
    assert reg.counter("c") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("c")


def test_labeled_counter_children():
    reg = MetricsRegistry()
    c = reg.counter("req", labelnames=("tenant",))
    c.labels(tenant="a").inc(3)
    c.labels(tenant="b").inc()
    assert c.labels(tenant="a").value == 3.0
    with pytest.raises(ValueError):
        c.labels(nope="x")
    got = {tuple(sorted(lbl.items())): v for _, lbl, v in c.samples()}
    assert got == {(("tenant", "a"),): 3.0, (("tenant", "b"),): 1.0}


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    h = reg.histogram("demo_latency_seconds", help="latency",
                      buckets=(0.25, 1.0))
    for v in (0.125, 0.5, 5.0):
        h.observe(v)
    c = reg.counter("demo_requests", help="total requests")
    c.inc(3)

    @dataclass
    class MiniStats:
        hits: int = 2
        misses: int = 1

    owner = MiniStats()
    reg.register_stats("mini", owner, labels={"instance": "t0"})
    want = "\n".join([
        "# HELP demo_latency_seconds latency",
        "# TYPE demo_latency_seconds histogram",
        'demo_latency_seconds_bucket{le="0.25"} 1',
        'demo_latency_seconds_bucket{le="1.0"} 2',
        'demo_latency_seconds_bucket{le="+Inf"} 3',
        "demo_latency_seconds_sum 5.625",
        "demo_latency_seconds_count 3",
        "# HELP demo_requests total requests",
        "# TYPE demo_requests counter",
        "demo_requests 3.0",
        "# TYPE mini_hits counter",
        'mini_hits{instance="t0"} 2',
        "# TYPE mini_misses counter",
        'mini_misses{instance="t0"} 1',
    ]) + "\n"
    assert reg.prometheus_text() == want


def test_snapshot_shape_and_dict_field_flattening():
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)

    @dataclass
    class BucketStats:
        n: int = 4
        buckets: dict = None

    owner = BucketStats(buckets={64: 3, 128: 1})
    reg.register_stats("sess", owner, labels={"instance": "x"})
    snap = reg.snapshot()
    assert snap["metrics"]["c"]["series"][0]["value"] == 2.0
    hs = snap["metrics"]["h"]["series"][0]
    assert hs["counts"] == [1, 0] and hs["count"] == 1
    stats = snap["stats"]
    assert stats["sess_n"][0]["value"] == 4
    by_key = {s["labels"]["key"]: s["value"] for s in stats["sess_buckets"]}
    assert by_key == {"64": 3, "128": 1}


def test_dead_collector_dropped_after_gc():
    reg = MetricsRegistry()

    @dataclass
    class S:
        x: int = 1

    owner = S()
    reg.register_stats("tmp", owner)
    assert "tmp_x" in reg.snapshot()["stats"]
    del owner
    gc.collect()
    assert "tmp_x" not in reg.snapshot()["stats"]


# ------------------------------------------------------------ trace spans
def test_ctx_span_nesting_and_ordering():
    tr = Tracer()
    tr.enabled = True
    with tr.span("outer", a=1):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    ev = tr.events()
    # completion order: inner, inner2, outer
    assert [e["name"] for e in ev] == ["inner", "inner2", "outer"]
    outer = ev[2]
    assert outer["parent"] is None and outer["args"] == {"a": 1}
    assert ev[0]["parent"] == outer["id"]
    assert ev[1]["parent"] == outer["id"]
    assert tr.children_of(outer["id"]) == ev[:2]


def test_explicit_span_never_adopts_ambient_stack():
    """Double-buffered overlap: while slab N's classify span is on the
    ambient stack, slab N+1's staging begin() must NOT parent into it."""
    tr = Tracer()
    tr.enabled = True
    slab0 = tr.begin("slab", track="slab-0", slab=0)
    with tr.span("classify"):
        slab1 = tr.begin("slab", track="slab-1", slab=1)
        tr.end(slab1)                 # completes inside classify's scope
    tr.end(slab0)
    ev = {e["args"].get("slab"): e for e in tr.events()
          if e["name"] == "slab"}
    classify = next(e for e in tr.events() if e["name"] == "classify")
    assert ev[1]["parent"] is None          # not classify.id
    assert ev[0]["parent"] is None
    assert ev[0]["track"] == "slab-0" and ev[1]["track"] == "slab-1"
    assert classify["parent"] is None


def test_explicit_span_takes_handed_parent():
    tr = Tracer()
    tr.enabled = True
    a = tr.begin("a")
    b = tr.begin("b", parent=a.id)
    tr.end(b)
    tr.end(a)
    ev = {e["name"]: e for e in tr.events()}
    assert ev["b"]["parent"] == a.id


def test_disabled_tracing_is_noop_and_straddle_records_nothing():
    tr = Tracer()
    assert tr.begin("x") is None
    assert tr.end(None) is None
    with tr.span("y"):
        pass
    tr.instant("z")
    assert tr.events() == []
    # token begun while disabled, ended after enable: still nothing
    tok = tr.begin("straddle")
    tr.enabled = True
    assert tr.end(tok) is None
    assert tr.events() == []


def test_ring_capacity_and_drop_count():
    tr = Tracer(capacity=4)
    tr.enabled = True
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 4
    assert tr.n_recorded == 10
    assert tr.n_dropped == 6
    assert [e["name"] for e in tr.events()] == ["e6", "e7", "e8", "e9"]


def test_span_set_attaches_attrs_at_exit():
    tr = Tracer()
    tr.enabled = True
    with tr.span("phase2.chunk", q=4) as sp:
        sp.set(retries=2)
    assert tr.events()[0]["args"] == {"q": 4, "retries": 2}
    tr.enabled = False
    with tr.span("off") as sp:
        sp.set(retries=1)                 # the shared no-op takes it too
    assert len(tr.events()) == 1


class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: counts constructions
    and checks every entered annotation is exited once."""
    made = []

    def __init__(self, name):
        self.name = name
        self.state = "made"
        _CountingAnnotation.made.append(self)

    def __enter__(self):
        assert self.state == "made"
        self.state = "open"

    def __exit__(self, *exc):
        assert self.state == "open"
        self.state = "closed"


def _drive(tr):
    with tr.span("ctx"):
        tok = tr.begin("explicit")
    tr.end(tok)
    tr.instant("mark")


def test_disabled_tracing_opens_no_annotation():
    tr = Tracer()
    tr._annotate = _CountingAnnotation
    _CountingAnnotation.made = []
    _drive(tr)
    assert _CountingAnnotation.made == [] and tr.events() == []
    tr.enabled = True
    _drive(tr)
    assert [a.name for a in _CountingAnnotation.made] == \
        ["ctx", "explicit", "mark"]
    assert all(a.state == "closed" for a in _CountingAnnotation.made)


def test_spans_share_the_profilers_clock(tmp_path):
    """Context spans, explicit spans that interleave (A opens, B opens, A
    closes, B closes) and an instant all land in the jax profile, each
    at the ring's times plus one constant offset."""
    import time

    import jax
    from jax.profiler import ProfileData

    tr = Tracer()
    tr.enabled = True
    gap = 0.02
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("obs.outer"):
            time.sleep(gap)
            with tr.span("obs.inner"):
                time.sleep(gap)
        a = tr.begin("obs.a", track="slab-0")
        time.sleep(gap)
        b = tr.begin("obs.b", track="slab-1")
        time.sleep(gap)
        tr.end(a)
        time.sleep(gap)
        tr.end(b)
        time.sleep(gap)
        tr.instant("obs.mark")
    finally:
        jax.profiler.stop_trace()
    [path] = list(tmp_path.glob("**/*.xplane.pb"))
    prof = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("obs."):
                        prof[e.name] = (e.start_ns * 1e-9, e.end_ns * 1e-9)
    ring = {e["name"]: (e["ts"], e["ts"] + e["dur"]) for e in tr.events()}
    assert set(prof) == set(ring) == {"obs.outer", "obs.inner", "obs.a",
                                      "obs.b", "obs.mark"}
    offsets = [p - r for name in ring
               for p, r in zip(prof[name], ring[name])]
    # a misplaced start or end would be off by a multiple of the gap
    assert max(offsets) - min(offsets) < gap / 4, offsets
    assert prof["obs.a"][0] < prof["obs.b"][0] < prof["obs.a"][1] \
        < prof["obs.b"][1]


def test_chrome_trace_tracks_map_to_tids(tmp_path):
    tr = Tracer()
    tr.enabled = True
    with tr.span("host_thing"):
        pass
    tr.end(tr.begin("slab", track="slab-0"))
    tr.end(tr.begin("slab", track="slab-1"))
    doc = tr.chrome_trace()
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    tids = {e["cat"]: e["tid"] for e in xs}
    assert tids["host"] == 0
    assert tids["slab-0"] != tids["slab-1"] != 0
    names = {e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert {"repro.reach", "slab-0", "slab-1"} <= names
    p = tmp_path / "trace.json"
    tr.export_chrome_trace(str(p))
    assert json.loads(p.read_text())["traceEvents"]


def test_global_enable_disable_roundtrip():
    assert not get_tracer().enabled
    try:
        enable_tracing(True)
        assert get_tracer().enabled
    finally:
        enable_tracing(False)
        get_tracer().clear()


# --------------------------------------- latency window degenerate cases
def test_latency_track_empty_reports_none():
    lt = LatencyTrack(8)
    assert lt.percentile(50) is None
    assert lt.percentile(99) is None
    assert lt.mean is None
    assert lt.window == 0


def test_latency_track_cap_validation():
    with pytest.raises(ValueError):
        LatencyTrack(0)
    with pytest.raises(ValueError):
        LatencyTrack(-5)
    assert LatencyTrack(1).cap == 1


def test_latency_track_percentile_range_checked():
    lt = LatencyTrack(8)
    lt.add(1.0)
    with pytest.raises(ValueError):
        lt.percentile(-1)
    with pytest.raises(ValueError):
        lt.percentile(101)


def test_latency_track_unordered_window_sorts_every_call():
    # fewer samples than the window: exact percentiles, any insert order
    lt = LatencyTrack(8)
    for v in (5.0, 1.0, 9.0, 3.0):
        lt.add(v)
    assert lt.percentile(0) == 1.0
    assert lt.percentile(100) == 9.0
    assert lt.window == 4
    assert lt.mean == pytest.approx(4.5)


def test_latency_track_wraparound_stays_bounded_and_sane():
    lt = LatencyTrack(4)
    vals = [float(v) for v in range(100, 0, -1)]      # descending arrivals
    for v in vals:
        lt.add(v)
    assert lt.window == 4                              # bounded by cap
    assert lt.count == 100
    assert lt.mean == pytest.approx(sum(vals) / 100)   # mean is exact
    # retained window is an unordered bag of real samples
    lo, hi = lt.percentile(0), lt.percentile(100)
    assert 1.0 <= lo <= hi <= 100.0


def test_spec_latency_window_knob():
    with pytest.raises(ValueError):
        IndexSpec(latency_window=0)
    spec = IndexSpec(latency_window=123)
    argv = spec.to_cli_args()
    i = argv.index("--latency-window")
    assert argv[i + 1] == "123"
    import argparse
    ap = argparse.ArgumentParser()
    IndexSpec.add_cli_args(ap)
    rt = IndexSpec.from_args(ap.parse_args(argv))
    assert rt.latency_window == 123
    assert rt == spec
