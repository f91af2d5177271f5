"""Citation-like DAG: nodes in layers, edges to later layers; a ``skip_p``
share of edges skips two or more layers.

Copied from the program's ``repro.graphs.generators.layered_dag``. Params:
``n`` nodes, ``n_layers``, ``avg_deg``, the edges drawn a node, of which
those past the last layer and duplicates are dropped, and ``skip_p``.
"""
from __future__ import annotations

import numpy as np

from harness.gen import dedup


def generate(seed: int, n: int, n_layers: int, avg_deg: float,
             skip_p: float = 0.1):
    rng = np.random.default_rng(seed)
    layer = np.sort(rng.integers(0, n_layers, size=n))
    m = int(n * avg_deg)
    src = rng.integers(0, n, size=2 * m, dtype=np.int64)
    jump = np.where(rng.random(2 * m) < skip_p,
                    rng.integers(2, max(3, n_layers // 3), size=2 * m), 1)
    tgt_layer = layer[src] + jump
    lo = np.searchsorted(layer, tgt_layer, side="left")
    hi = np.searchsorted(layer, tgt_layer, side="right")
    ok = hi > lo
    src, lo, hi = src[ok], lo[ok], hi[ok]
    dst = lo + (rng.random(src.size) * (hi - lo)).astype(np.int64)
    src, dst = src[:m], dst[:m]
    keep = src != dst
    return dedup(n, src[keep], dst[keep])
