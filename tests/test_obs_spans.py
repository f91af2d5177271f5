"""The served path's spans (DESIGN.md §8.2): ``query()``, the staged path
and the ``Frontend`` each emit their layer's spans, with every dotted
child parented under its boundary span (``phase2.chunk`` included, for
both device phase-2 engines), and the phase-1 program carries a name a
device profile can show."""
import numpy as np
import pytest

from repro import obs
from repro.graphs.generators import random_dag
from repro.reach import Frontend, IndexSpec, QuerySession, build

N = 300


@pytest.fixture(scope="module", params=["sparse", "dense"])
def session(request):
    # k=1 and no seeds leave pairs UNKNOWN after phase 1, so phase 2 runs
    g = random_dag(N, 1.5, seed=5)
    spec = IndexSpec(k=1, use_seeds=False, phase2_mode=request.param)
    return QuerySession(build(g, spec), spec)


def _pairs(q, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, N, q).astype(np.int64),
            rng.integers(0, N, q).astype(np.int64))


def _traced(fn):
    """Events recorded while ``fn`` runs, and ``fn``'s result."""
    tr = obs.get_tracer()
    tr.clear()
    obs.enable_tracing(True)
    try:
        out = fn()
    finally:
        obs.enable_tracing(False)
    ev = tr.events()
    tr.clear()
    return ev, out


def _by_name(ev):
    out = {}
    for e in ev:
        out.setdefault(e["name"], []).append(e)
    return out


def _children(by, parent, names):
    for name in names:
        assert name in by, (name, sorted(by))
        for e in by[name]:
            assert e["parent"] in {p["id"] for p in by[parent]}, name


def _check_engine_spans(by, sess):
    _children(by, "dispatch", ("dispatch.h2d", "dispatch.classify"))
    assert "dispatch.gather" not in by     # the lookup is in the program
    _children(by, "phase1", ("phase1.wait", "phase1.tally"))
    assert sess.stats.phase2_queries > 0
    _children(by, "phase2", ("phase2.chunk",))
    chunks = by["phase2.chunk"]
    assert sum(c["args"]["q"] for c in chunks) == sess.stats.phase2_queries
    if sess.engine.phase2_mode == "sparse":
        assert all({"cap", "retries"} <= set(c["args"]) for c in chunks)
        assert sum(c["args"]["retries"] for c in chunks) \
            == sess.stats.sparse_retries
    assert "phase2.overflow_retry" not in by


def test_query_path_spans(session):
    s, t = _pairs(100, 1)              # pads to the 256 bucket
    session.query(s, t)                # compile outside the trace
    session.reset_stats()
    ev, _ = _traced(lambda: session.query(s, t))
    by = _by_name(ev)
    assert len(by["dispatch"]) == 1 and len(by["stage.pad"]) == 1
    assert by["stage.pad"][0]["args"] == {"q": 100, "bucket": 256}
    _check_engine_spans(by, session)


def test_staged_path_spans(session):
    s, t = _pairs(100, 2)
    session.finish(session.begin(session.stage(s, t)))
    session.reset_stats()
    ev, _ = _traced(
        lambda: session.finish(session.begin(session.stage(s, t))))
    by = _by_name(ev)
    _children(by, "stage", ("stage.pad",))
    _children(by, "finish", ("phase1",))
    assert len(by["dispatch"]) == 1 and by["dispatch"][0]["parent"] is None
    _check_engine_spans(by, session)


def test_frontend_spans(session):
    fe = Frontend(session, batch_target=64, cache_entries=256)
    (s1, t1), (s2, t2) = _pairs(16, 3), _pairs(16, 4)
    fe.query("warm", *_pairs(16, 5))

    def serve():
        a = fe.submit("a", s1, t1)
        b = fe.submit("b", s2, t2)
        fe.drain()
        fe.submit("a", s1, t1)         # answered whole from the cache
        return a, b

    ev, (a, b) = _traced(serve)
    by = _by_name(ev)
    assert len(by["coalesce.admit"]) == 2
    assert len(by["cache_probe.commit"]) == 3
    _children(by, "coalesce", ("coalesce.take", "stage"))
    waits = {e["args"]["ticket"]: e for e in by["queue_wait"]}
    assert set(waits) == {a, b}
    slabs = {e["args"]["slab"] for e in by["slab"]}
    for tenant, ticket in (("a", a), ("b", b)):
        w = waits[ticket]
        assert w["track"] == "requests" and w["parent"] is None
        assert w["args"]["tenant"] == tenant and w["args"]["slab"] in slabs
    deliver = by["finish.deliver"]
    assert sum(d["args"]["n_reqs"] for d in deliver) == 2
    assert sum(d["args"]["q"] for d in deliver) == 32


def test_phase1_program_is_named(session):
    eng = session.engine
    ids = np.zeros(256, np.int32)
    text = eng._classify_exec.lower(eng.dev, eng.comp, ids, ids).as_text()
    assert "jit_phase1_classify" in text
