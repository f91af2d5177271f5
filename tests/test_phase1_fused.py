"""The phase-1 executor takes original node ids and looks up their
components inside its one compiled program: the same components and
verdicts as the eager lookup followed by ``classify_queries``, one trace
per padding bucket whichever path feeds it, and the component table an
argument of the program rather than a constant baked into it."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graphs.generators import scale_free_digraph
from repro.kernels import ops
from repro.reach import IndexSpec, QuerySession, build

N = 777                      # original nodes; cycles condense them to fewer


def _session(k=2):
    g = scale_free_digraph(N, 2.0, seed=3)
    spec = IndexSpec(k=k)
    return QuerySession(build(g, spec), spec)


@pytest.fixture(scope="module")
def session():
    return _session()


def _pairs(q, bucket, seed):
    """``q`` random pairs padded to ``bucket`` with (0, 0) self-queries, as
    the session pads them."""
    rng = np.random.default_rng(seed)
    s = np.zeros(bucket, np.int64)
    t = np.zeros(bucket, np.int64)
    s[:q] = rng.integers(0, N, q)
    t[:q] = rng.integers(0, N, q)
    return s, t


@pytest.mark.parametrize("path", ["query", "staged"])
@pytest.mark.parametrize("q,bucket", [(100, 256), (512, 512)])
def test_fused_matches_eager_lookup(session, path, q, bucket):
    eng = session.engine
    assert eng.packed.n < N          # the lookup is not the identity
    s, t = _pairs(q, bucket, seed=q)
    ids = (s, t) if path == "query" else eng.stage_queries(s, t)
    verdict, cs, ct = eng.classify(*ids)
    ref_cs, ref_ct = eng.packed.comp[s], eng.packed.comp[t]
    ref_v = ops.classify_queries(eng.dev, jnp.asarray(ref_cs),
                                 jnp.asarray(ref_ct),
                                 use_pallas=eng.use_pallas)
    np.testing.assert_array_equal(np.asarray(cs), ref_cs)
    np.testing.assert_array_equal(np.asarray(ct), ref_ct)
    np.testing.assert_array_equal(np.asarray(verdict), np.asarray(ref_v))
    assert (np.asarray(verdict)[q:] == ops.POS).all()   # the padding


@pytest.mark.parametrize("sizes", [(100,), (100, 300), (5, 100, 300, 900)])
def test_one_trace_per_bucket_on_both_paths(sizes):
    sess = _session()
    buckets = set()
    for i, q in enumerate(sizes):
        s, t = _pairs(q, q, seed=i)
        want = sess.query(s, t)
        got = sess.finish(sess.begin(sess.stage(s, t)))
        np.testing.assert_array_equal(got, want)
        buckets.add(sess._bucket(q))
    assert sess.trace_count == len(buckets)


def test_component_table_is_an_argument(session):
    eng = session.engine
    table = f"tensor<{N}xi32>"
    ids = np.zeros(256, np.int32)
    text = eng._classify_exec.lower(eng.dev, eng.comp, ids, ids).as_text()
    main = next(line for line in text.splitlines() if "@main(" in line)
    assert table in main
    baked = [line for line in text.splitlines()
             if "stablehlo.constant" in line and table in line]
    assert not baked, baked[0][:200]
