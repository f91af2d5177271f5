"""Batched device query engine — the production serving path.

Two phases (DESIGN.md §3):

  Phase 1  (`kernels.interval_stab`): one fused Pallas pass classifies every
  query as POS / NEG / UNKNOWN using the source's interval slab + all paper
  §5 filters. On real workloads this resolves the overwhelming majority
  (measured in benchmarks/query_*).

  Phase 2  (this module): UNKNOWN queries run the *guided online search* on
  device. Three engines, selected by ``phase2_mode``:

    dense   [Q, n] frontier row-vectors stepped with ``frontier @ A`` on the
            MXU — unbeatable at small n, but the n×n adjacency and [Q, n]
            verdict planes cap it at n ≤ n_dense_max (default 8192).
    sparse  the default at scale (`kernels.frontier`): the condensed DAG is
            packed into a fixed-width ELL slab + COO heavy tail
            (`PackedIndex.ell_layout`), and a chunk of queries expands in
            lockstep under one ``jax.lax.while_loop`` — per step the
            compacted frontier gathers its ELL rows, candidates are deduped
            with a fixed-size ``jnp.unique``, classified against their
            targets with the same interval + filter + seed rules, and
            visited bits are segment-OR'd into a [Q, ⌈n/32⌉] bitset. Same
            visited-set semantics and answers as the host guided DFS, no
            n×n anywhere, no per-query host Python in the loop. A frontier
            that outgrows its capacity sets an overflow flag; the driver
            retries unresolved queries with 4× capacity (positives found
            under overflow are already sound) and falls back to the host
            engine only past ``frontier_cap_max``.
    host    per-query guided DFS on `core.query.QueryEngine` — the paper-
            faithful reference, kept for comparison and as the terminal
            fallback.

  ``phase2_mode="auto"`` picks dense for n ≤ n_dense_max and sparse above.

  Memory model (per phase-2 chunk of Q queries): dense is Q·n verdict
  planes + n² adjacency; sparse is n·W·4 B ELL slab (shared, W ≈ 32) +
  Q·⌈n/32⌉·4 B visited bitset + cap·4 B frontier — at n = 10⁶, W = 16,
  Q = 256 that is 64 MB + 32 MB + KBs, vs 4 TB for the dense adjacency.
  Query-id key packing bounds a sparse chunk at 2^(31-⌈log₂n⌉) - 1
  queries; the driver chunks accordingly (32767 at n = 50k, 127 at
  n = 16M).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..obs import register_stats, span
from .ferrari import FerrariIndex
from .packed import PackedIndex, pack_index
from .query import QueryEngine, ResettableStats


@dataclass
class ServeStats(ResettableStats):
    n_queries: int = 0
    phase1_pos: int = 0
    phase1_neg: int = 0
    phase2_queries: int = 0
    phase2_dense: int = 0
    phase2_sparse: int = 0
    phase2_host: int = 0
    sparse_retries: int = 0
    # live-update path (reach.dynamic, DESIGN.md §6)
    n_updates: int = 0           # delta edges accepted into the overlay
    n_overlay_hits: int = 0      # base-NEG queries flipped POS by the overlay
    n_compactions: int = 0       # overlay folds into the index


@partial(jax.jit, static_argnames=("max_steps",))
def _dense_bfs(front0, expandable, definite_pos, adj, max_steps: int):
    """Batched masked BFS. front0/expandable/definite_pos: [Q, n] bool;
    adj: [n, n] f32 (adj[u, w] = 1 iff edge u->w). Returns pos [Q] bool."""

    pos0 = jnp.any(front0 & definite_pos, axis=1)
    front0 = front0 & expandable & ~pos0[:, None]

    def cond(state):
        front, visited, pos, step = state
        return jnp.logical_and(step < max_steps, jnp.any(front))

    def body(state):
        front, visited, pos, step = state
        reached = jnp.dot(front.astype(jnp.float32), adj,
                          preferred_element_type=jnp.float32) > 0.5
        new = reached & ~visited
        pos = pos | jnp.any(new & definite_pos, axis=1)
        visited = visited | new
        front = new & expandable & ~pos[:, None]
        return front, visited, pos, step + 1

    front, visited, pos, _ = jax.lax.while_loop(
        cond, body, (front0, front0 | front0, pos0, jnp.int32(0)))
    # note: visited initialized to front0 (sources are visited)
    return pos


class DeviceQueryEngine:
    """answer(srcs, dsts) with identical semantics to core.query.QueryEngine.

    Prefer constructing through the ``repro.reach`` facade (``IndexSpec`` +
    ``QuerySession``): it owns bucketed batching, statistics and
    persistence. This class stays as the low-level two-phase executor.

    ``packed`` / ``ell`` inject pre-built layouts (e.g. from a persisted
    artifact — ``reach.persist``) so construction skips the O(n) host
    packing loops.
    """

    def __init__(self, index: FerrariIndex, n_dense_max: int = 8192,
                 phase2_chunk: int = 256, use_pallas: bool = True,
                 phase2_mode: str = "auto", ell_width: Optional[int] = None,
                 frontier_cap: int = 4096, frontier_cap_max: int = 1 << 18,
                 packed: Optional[PackedIndex] = None, ell=None,
                 overlay_cap: int = 4096, kernel_impl: str = "xla"):
        if phase2_mode not in ("auto", "dense", "sparse", "host"):
            raise ValueError(f"unknown phase2_mode {phase2_mode!r}")
        self.index = index
        self.packed: PackedIndex = pack_index(index) if packed is None else packed
        self._dev_cache = None        # lazy: distributed subclasses never
        self.comp = jnp.asarray(self.packed.comp)  # replicate the full table
        self.use_pallas = use_pallas
        # resolved fused-kernel core of the sparse frontier step ("auto" →
        # pallas on TPU, xla elsewhere); needs the gather-fused layout,
        # ops.expand_frontier falls back to the XLA loop without it
        self.kernel_impl = ops.resolve_kernel_impl(kernel_impl)
        self.phase2_chunk = phase2_chunk
        self.ell_width = ell_width
        self.frontier_cap = frontier_cap
        self.frontier_cap_max = frontier_cap_max
        self.stats = ServeStats()
        register_stats("reach_engine", self, provider=lambda e: e.stats)
        # wall-clock of the LAST finish_answer's two phases — always on
        # (two clock reads per slab), feeds the frontend's slow-slab log
        # without requiring tracing
        self.last_phase1_s = 0.0
        self.last_phase2_s = 0.0
        n = self.packed.n
        self.max_steps = int(index.tl.blevel[:n].max(initial=0)) + 1
        if phase2_mode == "auto":
            phase2_mode = "dense" if n <= n_dense_max else "sparse"
        self.phase2_mode = phase2_mode
        self.adj_dense = None
        if phase2_mode == "dense":
            a = np.zeros((n, n), dtype=np.float32)
            src, dst = index.cond.dag.edges()
            a[src, dst] = 1.0
            self.adj_dense = jnp.asarray(a)
        self._ell_host = ell          # optional injected (ell, tsrc, tdst)
        self._ell_dev = None          # built lazily on first sparse use
        self._host_engine = None      # built lazily on first host use
        # live-update overlay (reach.dynamic): created on first insert
        self.overlay_cap = overlay_cap
        self.overlay = None
        self._overlay_cache = None    # (version, device state) per add batch
        self._union_adj_cache = None  # (version, adj, crt) — dense mode
        # One jitted phase-1 executor per engine: its compile cache is keyed
        # by batch shape, so _cache_size() counts traces — the serving
        # session asserts this stays at one per padding bucket. It takes
        # original node ids and looks up their components itself, so a
        # batch is one compiled program. The table is an argument, as the
        # slab is: as a closed-over constant it would be baked into the
        # program text. A named function, so its device ops read
        # jit_phase1_classify/... in a profile.
        def phase1_classify(dev, comp, srcs, dsts):
            cs, ct = comp[srcs], comp[dsts]
            verdict = ops.classify_queries(dev, cs, ct, use_pallas=use_pallas)
            return verdict, cs, ct

        self._classify_exec = jax.jit(phase1_classify)

    # ------------------------------------------------------ lazy structures
    @property
    def dev(self) -> dict:
        """The replicated single-device table dict (PackedIndex.to_device),
        materialized on first use. DistributedQueryEngine overrides every
        path that touches it, so a sharded placement never pays for a full
        replicated copy here."""
        if self._dev_cache is None:
            self._dev_cache = self.packed.to_device()
        return self._dev_cache

    @property
    def _host(self) -> QueryEngine:
        if self._host_engine is None:
            self._host_engine = QueryEngine(self.index)
        return self._host_engine

    def _ell(self):
        if self._ell_dev is None:
            if self._ell_host is not None:
                ell, tsrc, tdst = self._ell_host
            else:
                ell, tsrc, tdst = self.packed.ell_layout(width=self.ell_width)
            is_hub = np.zeros(self.packed.n, dtype=bool)
            is_hub[tsrc] = True
            self._ell_dev = (jnp.asarray(ell), jnp.asarray(tsrc),
                             jnp.asarray(tdst), jnp.asarray(is_hub))
        return self._ell_dev

    # --------------------------------------------------------------- phase 1
    @property
    def trace_count(self) -> int:
        """Phase-1 jit traces so far (grows only on unseen batch shapes)."""
        return self._classify_exec._cache_size()

    def classify(self, srcs, dsts):
        with span("dispatch.h2d"):
            srcs, dsts = jnp.asarray(srcs), jnp.asarray(dsts)
        with span("dispatch.classify"):
            return self._classify_exec(self.dev, self.comp, srcs, dsts)

    def stage_queries(self, srcs, dsts):
        """Start the host→device transfer of a query batch (asynchronous)
        and return arrays ``classify`` accepts. The serving frontend
        stages batch N+1 here while batch N's classify is in flight
        (double-buffered query slabs)."""
        return (jax.device_put(np.asarray(srcs, np.int64)),
                jax.device_put(np.asarray(dsts, np.int64)))

    # ------------------------------------------------------- live updates
    def apply_updates(self, csrc, cdst) -> int:
        """Append condensed-id edges to the delta overlay (creating it on
        first use). Returns how many edges were actually new; subsequent
        ``answer()`` calls are sound and complete over the union graph.
        Raises ``reach.dynamic.OverlayFull`` when the batch does not fit —
        callers compact (``QuerySession`` automates this) and retry."""
        if self.overlay is None:
            from ..reach.dynamic.overlay import DeltaOverlay
            self.overlay = DeltaOverlay(self.index.cond.dag, self.overlay_cap)
        applied = self.overlay.add(csrc, cdst)
        self.stats.n_updates += applied
        return applied

    def _overlay_dev(self):
        """Device state of the overlay union adjacency, rebuilt once per
        add batch: the base COO tail with the delta slab appended (fixed
        [m_t + cap] shapes — no retrace across updates), the hub mask
        extended to delta tails, and the can-reach-tail pruning gate."""
        ov = self.overlay
        if self._overlay_cache is None or self._overlay_cache[0] != ov.version:
            ell, tsrc, tdst, is_hub = self._ell()
            self._overlay_cache = (
                ov.version, (ell,) + ov.union_tail_state(tsrc, tdst, is_hub))
        return self._overlay_cache[1]

    @property
    def _overlay_live(self) -> bool:
        return self.overlay is not None and self.overlay.n_edges > 0

    # ------------------------------------------------------------------ API
    def answer(self, srcs, dsts) -> np.ndarray:
        return self.finish_answer(self.start_answer(srcs, dsts))

    def start_answer(self, srcs, dsts):
        """Dispatch phase 1 without blocking on its result.

        jax dispatch is asynchronous: the returned verdict is a device
        future, so the caller can overlap host work (staging the NEXT
        batch's host→device transfer — see ``QuerySession.begin``/
        ``finish`` and the frontend's double-buffered slabs) against the
        classify compute before calling ``finish_answer``. The
        ``dispatch`` span covers it, on ``query()``'s path and the staged
        one alike.
        """
        with span("dispatch", bucket=len(srcs)):
            return self.classify(srcs, dsts)

    def finish_answer(self, handle) -> np.ndarray:
        """Block on a ``start_answer`` handle and run phase 2 on the
        UNKNOWN residue. ``answer()`` is exactly start + finish.

        The ``phase1`` span covers blocking on the classify verdict (i.e.
        the device compute start_answer dispatched and the copy back,
        ``phase1.wait``) plus the residue bookkeeping (``phase1.tally``);
        ``phase2`` covers the residue driver. Their
        wall-clock also lands in ``last_phase1_s``/``last_phase2_s``
        regardless of tracing (the frontend's slow-slab log reads them)."""
        verdict, cs, ct = handle
        t0 = time.perf_counter()
        with span("phase1", q=int(verdict.shape[0])):
            with span("phase1.wait"):
                verdict = np.asarray(verdict)
            with span("phase1.tally"):
                out = verdict == ops.POS
                neg_mask = verdict == ops.NEG
                unknown = np.flatnonzero(verdict == ops.UNKNOWN)
                self.stats.n_queries += len(verdict)
                self.stats.phase1_pos += int(out.sum())
                overlay = self._overlay_live
                if overlay:
                    # base-NEG is no longer final when the source can reach
                    # a delta tail: those queries join the union-graph
                    # expansion (and leave the phase-1 mix — phase1_pos/
                    # neg/phase2_queries stay a partition of n_queries
                    # under churn)
                    reopened = np.flatnonzero(
                        neg_mask
                        & self.overlay.can_reach_tail[np.asarray(cs)])
                    residue = np.union1d(unknown, reopened)
                    self.stats.phase1_neg += (int(neg_mask.sum())
                                              - reopened.size)
                else:
                    residue = unknown
                    self.stats.phase1_neg += int(neg_mask.sum())
                self.stats.phase2_queries += residue.size
        t1 = time.perf_counter()
        self.last_phase1_s = t1 - t0
        self.last_phase2_s = 0.0
        if residue.size == 0:
            return out
        with span("phase2", mode=self.phase2_mode,
                  residue=int(residue.size)):
            cs_u = np.asarray(cs)[residue]
            ct_u = np.asarray(ct)[residue]
            if self.phase2_mode == "dense":
                self.stats.phase2_dense += residue.size
                res = (self._phase2_dense_overlay(cs_u, ct_u) if overlay
                       else self._phase2_dense(cs_u, ct_u))
            elif self.phase2_mode == "sparse":
                res = (self._phase2_sparse_overlay(cs_u, ct_u) if overlay
                       else self._phase2_sparse(cs_u, ct_u))
            else:
                self.stats.phase2_host += residue.size
                res = (self._phase2_host_overlay(cs_u, ct_u) if overlay
                       else self._phase2_host(cs_u, ct_u))
            out[residue] = res
            if overlay:
                self.stats.n_overlay_hits += int(
                    (res & neg_mask[residue]).sum())
        self.last_phase2_s = time.perf_counter() - t1
        return out

    # --------------------------------------------------------------- phase 2
    def _phase2_host(self, cs_u: np.ndarray, ct_u: np.ndarray) -> np.ndarray:
        return np.fromiter(
            (self._host._reachable_condensed(int(a), int(b))
             for a, b in zip(cs_u, ct_u)), dtype=bool, count=cs_u.size)

    def _phase2_host_overlay(self, cs_u: np.ndarray,
                             ct_u: np.ndarray) -> np.ndarray:
        """Union-graph host BFS (terminal fallback under an active overlay:
        the base guided DFS cannot traverse delta edges)."""
        ov = self.overlay
        return np.fromiter(
            (ov.host_reachable(int(a), int(b))
             for a, b in zip(cs_u, ct_u)), dtype=bool, count=cs_u.size)

    def _dense_driver(self, cs_u: np.ndarray, ct_u: np.ndarray, adj,
                      max_steps: int, can_reach_tail=None) -> np.ndarray:
        n = self.packed.n
        chunk = self.phase2_chunk
        res = np.zeros(cs_u.size, dtype=bool)
        for lo in range(0, cs_u.size, chunk):
            hi = min(lo + chunk, cs_u.size)
            q = hi - lo
            # fixed chunk shape: a ragged tail would retrace the BFS; pad
            # with (0, 0) self-queries, which resolve at step 0
            cs_h = np.zeros(chunk, dtype=np.int32)
            ct_h = np.zeros(chunk, dtype=np.int32)
            cs_h[:q] = cs_u[lo:hi]
            ct_h[:q] = ct_u[lo:hi]
            with span("phase2.chunk", q=q):
                cs = jnp.asarray(cs_h)
                ct = jnp.asarray(ct_h)
                expandable, definite_pos = ops.classify_all_nodes_vs_target(
                    self.dev, ct, can_reach_tail=can_reach_tail)
                front0 = jax.nn.one_hot(cs, n, dtype=jnp.bool_)
                pos = _dense_bfs(front0, expandable, definite_pos,
                                 adj, max_steps)
                res[lo:hi] = np.asarray(pos)[:q]
        return res

    def _phase2_dense(self, cs_u: np.ndarray, ct_u: np.ndarray) -> np.ndarray:
        return self._dense_driver(cs_u, ct_u, self.adj_dense, self.max_steps)

    def _phase2_dense_overlay(self, cs_u: np.ndarray,
                              ct_u: np.ndarray) -> np.ndarray:
        """Dense BFS over the union adjacency: the delta slab is scattered
        into the base n×n matrix (padding writes a harmless (0, 0)
        self-loop — node 0 is visited before it could re-front), base-NEG
        nodes stay expandable while they can reach a delta tail, and the
        step bound grows to n (delta edges may cycle across the DAG)."""
        ov = self.overlay
        if self._union_adj_cache is None \
                or self._union_adj_cache[0] != ov.version:
            adj = self.adj_dense.at[jnp.asarray(ov.src),
                                    jnp.asarray(ov.dst)].set(1.0)
            self._union_adj_cache = (ov.version, adj,
                                     jnp.asarray(ov.can_reach_tail))
        _, adj, crt = self._union_adj_cache
        return self._dense_driver(cs_u, ct_u, adj, self.packed.n,
                                  can_reach_tail=crt)

    def _phase2_chunk_size(self) -> int:
        """Queries per sparse expansion call (key packing bounds it)."""
        return min(self.phase2_chunk, ops.frontier_max_batch(self.packed.n))

    def _expand_chunk(self, cs_j, ct_j, pad: np.ndarray, cap: int):
        """One frontier expansion; returns (pos [chunk] np.bool_, overflow
        bool). DistributedQueryEngine swaps in the shard_map'd expansion."""
        ell, tsrc, tdst, is_hub = self._ell()
        p, ovf = ops.expand_frontier(
            self.dev, ell, tsrc, tdst, is_hub, cs_j, ct_j,
            jnp.asarray(pad), max_steps=self.max_steps, cap=cap,
            kernel_impl=self.kernel_impl)
        return np.asarray(p), bool(ovf)

    def _residue_perm(self, q: int) -> Optional[np.ndarray]:
        """Optional permutation of the phase-2 residue before chunking
        (results are scattered back through it). The multi-device engine
        interleaves here so a difficulty-skewed residue spreads evenly
        over the data shards instead of idling all but one of them."""
        return None

    def _sparse_driver(self, cs_u: np.ndarray, ct_u: np.ndarray,
                       expand_fn, host_fn) -> np.ndarray:
        """Chunked expansion with the overflow-retry / terminal-host-
        fallback policy. ``expand_fn(cs_j, ct_j, pad, cap)`` runs one
        frontier expansion; ``host_fn(cs, ct)`` resolves queries past
        ``frontier_cap_max`` (the base guided DFS, or the union-graph BFS
        when an overlay is live)."""
        perm = self._residue_perm(cs_u.size)
        if perm is not None:
            cs_u, ct_u = cs_u[perm], ct_u[perm]
        chunk = self._phase2_chunk_size()
        res = np.zeros(cs_u.size, dtype=bool)
        self.stats.phase2_sparse += cs_u.size
        for lo in range(0, cs_u.size, chunk):
            hi = min(lo + chunk, cs_u.size)
            q = hi - lo
            cs = np.zeros(chunk, np.int32)
            ct = np.zeros(chunk, np.int32)
            cs[:q] = cs_u[lo:hi]
            ct[:q] = ct_u[lo:hi]
            pad = np.ones(chunk, bool)
            pad[:q] = False
            cap = max(self.frontier_cap, chunk)
            pos = np.zeros(chunk, bool)
            retries = 0
            with span("phase2.chunk", q=q, cap=cap) as sp:
                cs_j, ct_j = jnp.asarray(cs), jnp.asarray(ct)
                while True:
                    p, ovf = expand_fn(cs_j, ct_j, pad, cap)
                    pos |= p
                    if not ovf:
                        break
                    # overflow: POS answers are sound, only non-positives
                    # need the retry — mask them out and rerun with 4x the
                    # capacity
                    cap *= 4
                    retries += 1
                    if cap > self.frontier_cap_max:
                        unresolved = np.flatnonzero(~pos & ~pad)
                        self.stats.phase2_host += unresolved.size
                        self.stats.phase2_sparse -= unresolved.size
                        with span("phase2.host_fallback",
                                  q=int(unresolved.size)):
                            pos[unresolved] = host_fn(cs[unresolved],
                                                      ct[unresolved])
                        break
                    pad = pad | pos
                    if pad.all():
                        break   # every live query already proved positive
                sp.set(retries=retries)
            self.stats.sparse_retries += retries
            res[lo:hi] = pos[:q]
        if perm is not None:
            out = np.empty_like(res)
            out[perm] = res
            return out
        return res

    def _phase2_sparse(self, cs_u: np.ndarray, ct_u: np.ndarray) -> np.ndarray:
        return self._sparse_driver(cs_u, ct_u, self._expand_chunk,
                                   self._phase2_host)

    def _phase2_sparse_overlay(self, cs_u: np.ndarray,
                               ct_u: np.ndarray) -> np.ndarray:
        return self._sparse_driver(cs_u, ct_u, self._expand_chunk_overlay,
                                   self._phase2_host_overlay)

    def _expand_chunk_overlay(self, cs_j, ct_j, pad: np.ndarray, cap: int):
        """One union-graph frontier expansion (kernels.frontier overlay
        variant). DistributedQueryEngine swaps in the shard_map'd one."""
        ell, tsrc_u, tdst_u, hub_u, crt = self._overlay_dev()
        p, ovf = ops.expand_frontier_overlay(
            self.dev, ell, tsrc_u, tdst_u, hub_u, crt, cs_j, ct_j,
            jnp.asarray(pad), max_steps=self.packed.n, cap=cap,
            kernel_impl=self.kernel_impl)
        return np.asarray(p), bool(ovf)
