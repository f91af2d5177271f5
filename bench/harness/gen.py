"""The benchmark's own data: graphs, query pairs and arrival times.

A configuration's graph comes from the generator it names:
``generator.name`` picks ``bench/graphs/<name>.py``, whose one function
``generate(seed, **params)`` returns the edges (``params["n"]`` is the node
count) as int64 arrays of a DAG, deduplicated and sorted by (src, dst), as
``dedup`` gives them: the order the program's ``build_csr`` produces. A new
graph shape is a new file there.

The generators, pairs and arrivals are copied from the program
(``repro.graphs.generators``, ``repro.core.workload`` and
``benchmarks/serving_perf.py``) so that a change to the program cannot move
the data it is measured on. ``bench/tests/test_harness_gen.py`` checks that
the copies give the same edges and pairs as the originals for a fixed seed.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from harness.loader import load_module

GRAPHS = Path(__file__).resolve().parents[1] / "graphs"


def dedup(n: int, src, dst):
    """Edges without duplicates, sorted by (src, dst), as int64 arrays."""
    key = np.unique(np.asarray(src, np.int64) * np.int64(n)
                    + np.asarray(dst, np.int64))
    return key // n, key % n


def csr(n: int, src, dst):
    """(indptr [n+1] int64, indices [m] int32) of edges sorted by src."""
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, np.asarray(src, np.int64) + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, np.asarray(dst, np.int32)


def generator_path(name: str) -> Path:
    """The file of the graph generator ``name``, or exit before any work."""
    path = GRAPHS / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"bench: no generator {name!r} under bench/graphs/")
    return path


def make_graph(cfg: dict):
    """(n, src, dst) of a configuration's graph, from its ``graph_seed``."""
    gen = cfg["generator"]
    src, dst = load_module(generator_path(gen["name"])).generate(
        int(cfg["graph_seed"]), **gen["params"])
    return int(gen["params"]["n"]), src, dst


def random_queries(n: int, q: int, seed: int):
    """Uniform random pairs (paper section 7.2)."""
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n, size=q, dtype=np.int64),
            rng.integers(0, n, size=q, dtype=np.int64))


def positive_queries(indptr, indices, q: int, seed: int, max_walk: int = 32):
    """Reachable pairs from random forward walks of 1 to ``max_walk`` steps
    (paper section 7.2); a walk that meets a sink stops there."""
    rng = np.random.default_rng(seed)
    n = indptr.size - 1
    deg = np.diff(indptr)
    src = rng.integers(0, n, size=q, dtype=np.int64)
    has_out = np.flatnonzero(deg > 0)
    if has_out.size:
        redirect = rng.integers(0, has_out.size, size=q)
        src = np.where(deg[src] > 0, src, has_out[redirect])
    dst = src.copy()
    steps = rng.integers(1, max_walk + 1, size=q)
    for i in range(q):
        v = int(src[i])
        for _ in range(int(steps[i])):
            lo, hi = indptr[v], indptr[v + 1]
            if hi == lo:
                break
            v = int(indices[lo + rng.integers(0, hi - lo)])
        dst[i] = v
    return src, dst


def poisson_arrivals(n_requests: int, rate: float, seed: int):
    """Arrival times in seconds of a Poisson stream at ``rate`` requests
    per second (the exponential gaps of serving_perf's ``_make_arrivals``)."""
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n_requests))


def mixed_pairs(n: int, indptr, indices, q: int, positive_share: float,
                seed: int, max_walk: int = 32):
    """``q`` pairs of which ``round(q * positive_share)`` are positive walks
    and the rest uniform, at positions shuffled by ``seed``. Every seed gets
    the same number of each kind."""
    n_pos = int(round(q * positive_share))
    rs, rt = random_queries(n, q - n_pos, seed)
    ps, pt = positive_queries(indptr, indices, n_pos, seed + 1, max_walk)
    s = np.concatenate([rs, ps])
    t = np.concatenate([rt, pt])
    perm = np.random.default_rng(seed + 2).permutation(q)
    return s[perm], t[perm]
