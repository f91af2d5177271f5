"""Plain reachability reference: is t reachable from s in a DAG?

It reads only the benchmark's own edge list, never the program's index.
Distinct sources are answered in blocks by one of two exact searches:

* a breadth-first search over (source, node) pairs, level by level, which
  costs the sum of the sources' descendant counts: right for sparse graphs,
  whose descendant sets are small;
* once that search would hold more than ``pair_cap`` pairs, bit-parallel
  propagation: each of 64 * ``words`` sources sets its own bit, and the bits
  flow along every edge in topological order (longest-path levels), so
  ``reach[v]`` ends as the set of those sources that reach ``v``.

Every node reaches itself.
"""
from __future__ import annotations

import numpy as np


class Reference:
    def __init__(self, n: int, src, dst, words: int = 16,
                 pair_cap: int = 1 << 22):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        self.n = n
        self.words = words
        self.pair_cap = pair_cap
        self._dense = False         # set once a pair search overflows
        order = np.argsort(src, kind="stable")
        self._out_dst = dst[order]
        self._out_ptr = np.searchsorted(src[order], np.arange(n + 1))
        level = self._levels(n, dst, self._out_ptr, self._out_dst)
        # edges grouped by the level of their head, heads sorted within
        order = np.lexsort((dst, level[dst]))
        self._src = src[order]
        self._dst = dst[order]
        bounds = np.searchsorted(level[self._dst], np.arange(level.max() + 2))
        self._groups = []
        for lv in range(1, level.max() + 1):
            lo, hi = bounds[lv], bounds[lv + 1]
            if hi > lo:
                d = self._dst[lo:hi]
                starts = np.flatnonzero(np.r_[True, d[1:] != d[:-1]])
                self._groups.append((lo, hi, d[starts], starts))

    def _children(self, nodes):
        """(index into ``nodes``, child) for every out-edge of ``nodes``."""
        cnt = self._out_ptr[nodes + 1] - self._out_ptr[nodes]
        first = self._out_ptr[nodes] - (np.cumsum(cnt) - cnt)
        idx = np.repeat(first, cnt) + np.arange(cnt.sum())
        return np.repeat(np.arange(nodes.size), cnt), self._out_dst[idx]

    @staticmethod
    def _levels(n: int, dst, out_ptr, out_dst) -> np.ndarray:
        """Longest-path level of every node; raises on a cycle."""
        indeg = np.bincount(dst, minlength=n)
        level = np.zeros(n, np.int64)
        front = np.flatnonzero(indeg == 0)
        done, lv = front.size, 0
        while front.size:
            level[front] = lv
            cnt = out_ptr[front + 1] - out_ptr[front]
            first = out_ptr[front] - (np.cumsum(cnt) - cnt)
            heads = out_dst[np.repeat(first, cnt) + np.arange(cnt.sum())]
            np.subtract.at(indeg, heads, 1)
            heads = np.unique(heads)
            front = heads[indeg[heads] == 0]
            done += front.size
            lv += 1
        if done != n:
            raise ValueError("the reference needs a DAG; the graph has a cycle")
        return level

    def reachable(self, s, t) -> np.ndarray:
        """Answers for pairs (s[i], t[i]), as a bool array."""
        s = np.asarray(s, np.int64)
        t = np.asarray(t, np.int64)
        out = np.zeros(s.size, bool)
        uniq, inv = np.unique(s, return_inverse=True)
        block = 64 * self.words
        for lo in range(0, uniq.size, block):
            srcs = uniq[lo:lo + block]
            sel = np.flatnonzero((inv >= lo) & (inv < lo + srcs.size))
            local = inv[sel] - lo
            keys = None if self._dense else self._pair_search(srcs)
            if keys is not None:
                out[sel] = np.isin(local * self.n + t[sel], keys)
                continue
            self._dense = True
            reach = self._propagate(srcs)
            word = reach[t[sel], local // 64]
            out[sel] = (word >> (local % 64).astype(np.uint64)) \
                & np.uint64(1) == 1
        return out

    def _pair_search(self, srcs):
        """Sorted keys ``i * n + v`` of every node v that source i reaches,
        or None once more than ``pair_cap`` pairs are held."""
        n = self.n
        seen = np.arange(srcs.size) * n + srcs
        front_q, front_v = np.arange(srcs.size), srcs
        while front_v.size:
            which, child = self._children(front_v)
            keys = np.unique(front_q[which] * n + child)
            keys = keys[~np.isin(keys, seen)]
            seen = np.union1d(seen, keys)
            if seen.size > self.pair_cap:
                return None
            front_q, front_v = keys // n, keys % n
        return seen

    def _propagate(self, srcs) -> np.ndarray:
        reach = np.zeros((self.n, self.words), np.uint64)
        bit = np.arange(srcs.size)
        reach[srcs, bit // 64] |= np.uint64(1) << (bit % 64).astype(np.uint64)
        for lo, hi, heads, starts in self._groups:
            acc = np.bitwise_or.reduceat(reach[self._src[lo:hi]], starts,
                                         axis=0)
            reach[heads] |= acc
        return reach
