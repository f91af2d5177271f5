"""Erdos-Renyi-style DAG, edges from lower to higher id.

Copied from the program's ``repro.graphs.generators.random_dag``. Params:
``n`` nodes and ``avg_deg``, the edges drawn a node, of which duplicates
are dropped.
"""
from __future__ import annotations

import numpy as np

from harness.gen import dedup


def generate(seed: int, n: int, avg_deg: float):
    rng = np.random.default_rng(seed)
    m = int(n * avg_deg)
    src = rng.integers(0, n - 1, size=2 * m, dtype=np.int64)
    dst = rng.integers(1, n, size=2 * m, dtype=np.int64)
    lo = np.minimum(src, dst)
    hi = np.maximum(src, dst)
    keep = lo != hi
    return dedup(n, lo[keep][:m], hi[keep][:m])
