"""The benchmark's copies of the generators, found by name under
``bench/graphs/``, give the program's edges and pairs for a fixed seed, and
the reference agrees with a plain search."""
from __future__ import annotations

import json

import numpy as np
import pytest

from tiny_cells import BENCH, TINY, fake_chips, make_root

from harness import gen  # noqa: E402
from harness.reference import Reference  # noqa: E402
from repro.core import workload  # noqa: E402
from repro.graphs import generators  # noqa: E402
import run as bench  # noqa: E402


def _edges(csr):
    src = np.repeat(np.arange(csr.n), np.diff(csr.indptr))
    return src, csr.indices.astype(np.int64)


def _graph(name, seed, **params):
    """(src, dst) of the generator ``name``, through the configuration's
    lookup by name."""
    cfg = {"generator": {"name": name, "params": params}, "graph_seed": seed}
    n, src, dst = gen.make_graph(cfg)
    assert n == params["n"]
    return src, dst


@pytest.mark.parametrize("make,args", [
    ("layered_dag", dict(n=3000, n_layers=30, avg_deg=4.38)),
    ("random_dag", dict(n=5000, avg_deg=0.45)),
])
def test_graph_copies_match(make, args):
    want = getattr(generators, make)(**args, seed=17)
    s, d = _graph(make, 17, **args)
    ws, wd = _edges(want)
    assert np.array_equal(s, ws) and np.array_equal(d, wd)
    indptr, indices = gen.csr(args["n"], s, d)
    assert np.array_equal(indptr, want.indptr)
    assert np.array_equal(indices, want.indices)


def test_query_copies_match():
    g = generators.layered_dag(2000, 20, 3.0, seed=4)
    s, d = _graph("layered_dag", 4, n=2000, n_layers=20, avg_deg=3.0)
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
    s, d = _graph("layered_dag", 4, n=2000, n_layers=20, avg_deg=3.0)
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
    s, d = _graph(make, 3, **args)
    indptr, indices = gen.csr(n, s, d)
    qs, qt = gen.mixed_pairs(n, indptr, indices, 1500, 0.3, 5)
    ref = Reference(n, s, d, words=2, pair_cap=pair_cap)
    want = [_dfs(indptr, indices, int(a), int(b)) for a, b in zip(qs, qt)]
    assert np.array_equal(ref.reachable(qs, qt), np.array(want))


def test_reference_refuses_a_cycle():
    with pytest.raises(ValueError):
        Reference(3, np.array([0, 1, 2]), np.array([1, 2, 0]))


def test_citeseer_graph_is_the_programs():
    """The configuration's graph, at its published size, is the program's
    ``random_dag`` edge for edge, and its index is keyed as before."""
    from harness.index import graph_identity
    cfg = json.loads((BENCH / "configs" / "citeseer.json").read_text())
    n, s, d = gen.make_graph(cfg)
    ws, wd = _edges(generators.random_dag(693947, 0.45, seed=5))
    assert n == 693947 and s.dtype == d.dtype == np.int64
    assert np.array_equal(s, ws) and np.array_equal(d, wd)
    assert graph_identity(cfg) == {
        "generator": {"name": "random_dag",
                      "params": {"n": 693947, "avg_deg": 0.45}},
        "graph_seed": 5,
        "index_spec": {"k": 2, "variant": "G", "builder": "host"}}


@pytest.mark.parametrize("name,params", [
    ("layered_dag", dict(n=1200, n_layers=12, avg_deg=2.5)),
    ("random_dag", dict(n=1200, avg_deg=1.5)),
])
def test_generators_give_sorted_unique_dag_edges(name, params):
    s, d = _graph(name, 2**31 + 11, **params)
    assert s.dtype == d.dtype == np.int64 and s.size > 0
    key = s * params["n"] + d
    assert np.all(np.diff(key) > 0)
    assert s.min() >= 0 and d.max() < params["n"]
    Reference(params["n"], s, d)                    # raises on a cycle


def test_unknown_generator_is_refused_before_the_chips(tmp_path):
    root = make_root(tmp_path)
    cfg = dict(TINY["config"], generator={"name": "no_such_graph",
                                          "params": {"n": 10}})
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    asked = []

    def devices(chips):
        asked.append(chips)
        return fake_chips(chips)

    with pytest.raises(SystemExit) as e:
        bench.prepare("tiny.closed", root=root, devices=devices,
                      compile_cache=False)
    assert str(e.value) == ("bench: no generator 'no_such_graph' under "
                            "bench/graphs/")
    assert asked == []
