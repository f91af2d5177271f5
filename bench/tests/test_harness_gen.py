"""The benchmark's copies of the generators give the program's edges and
pairs for a fixed seed, and the reference agrees with a plain search."""
from __future__ import annotations

import numpy as np
import pytest

from tiny_cells import BENCH  # noqa: F401  (puts bench/ and src/ on the path)

from harness import gen  # noqa: E402
from harness.reference import Reference  # noqa: E402
from repro.core import workload  # noqa: E402
from repro.graphs import generators  # noqa: E402


def _edges(csr):
    src = np.repeat(np.arange(csr.n), np.diff(csr.indptr))
    return src, csr.indices.astype(np.int64)


@pytest.mark.parametrize("make,args", [
    ("layered_dag", dict(n=3000, n_layers=30, avg_deg=4.38)),
    ("random_dag", dict(n=5000, avg_deg=0.45)),
])
def test_graph_copies_match(make, args):
    want = getattr(generators, make)(**args, seed=17)
    s, d = getattr(gen, make)(**args, seed=17)
    ws, wd = _edges(want)
    assert np.array_equal(s, ws) and np.array_equal(d, wd)
    indptr, indices = gen.csr(args["n"], s, d)
    assert np.array_equal(indptr, want.indptr)
    assert np.array_equal(indices, want.indices)


def test_query_copies_match():
    g = generators.layered_dag(2000, 20, 3.0, seed=4)
    s, d = gen.layered_dag(2000, 20, 3.0, seed=4)
    indptr, indices = gen.csr(2000, s, d)
    for a, b in zip(workload.random_queries(g, 500, seed=9),
                    gen.random_queries(2000, 500, 9)):
        assert np.array_equal(a, b)
    for a, b in zip(workload.positive_queries(g, 300, seed=9),
                    gen.positive_queries(indptr, indices, 300, 9)):
        assert np.array_equal(a, b)


def test_arrivals_copy_matches():
    import sys
    from tiny_cells import REPO
    sys.path.insert(0, str(REPO))
    from benchmarks.serving_perf import _make_arrivals
    g = generators.layered_dag(500, 5, 2.0, seed=1)
    arr = _make_arrivals(g, n_requests=50, req_size=8, n_tenants=4,
                         offered_qps=800.0, seed=21)
    want = np.array([a[0] for a in arr])
    assert np.allclose(gen.poisson_arrivals(50, 100.0, 21), want)


def test_mixed_pairs_fixed_counts():
    s, d = gen.layered_dag(2000, 20, 3.0, seed=4)
    indptr, indices = gen.csr(2000, s, d)
    ref = Reference(2000, s, d)
    for seed in (1, 2**31 + 7):
        qs, qt = gen.mixed_pairs(2000, indptr, indices, 800, 0.25, seed)
        assert qs.size == 800
        assert ref.reachable(qs, qt).sum() >= 200


def _dfs(indptr, indices, a, b):
    seen, stack = {a}, [a]
    while stack:
        v = stack.pop()
        if v == b:
            return True
        for w in indices[indptr[v]:indptr[v + 1]]:
            if int(w) not in seen:
                seen.add(int(w))
                stack.append(int(w))
    return False


@pytest.mark.parametrize("pair_cap", [8, 1 << 22])
@pytest.mark.parametrize("make,args", [
    ("layered_dag", dict(n=1500, n_layers=15, avg_deg=3.0)),
    ("random_dag", dict(n=1500, avg_deg=0.9)),
])
def test_reference_matches_search(make, args, pair_cap):
    n = args["n"]
    s, d = getattr(gen, make)(**args, seed=3)
    indptr, indices = gen.csr(n, s, d)
    qs, qt = gen.mixed_pairs(n, indptr, indices, 1500, 0.3, 5)
    ref = Reference(n, s, d, words=2, pair_cap=pair_cap)
    want = [_dfs(indptr, indices, int(a), int(b)) for a, b in zip(qs, qt)]
    assert np.array_equal(ref.reachable(qs, qt), np.array(want))


def test_reference_refuses_a_cycle():
    with pytest.raises(ValueError):
        Reference(3, np.array([0, 1, 2]), np.array([1, 2, 0]))
