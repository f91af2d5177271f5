"""Serving front-end benchmark — open-loop multi-tenant serving through
``reach.frontend`` vs the closed-loop session baseline of BENCH_query.
Emits ``BENCH_serve.json`` (consumed by CI, tier1-serving job).

Three experiments on one index:

  * **closed loop** — ``QuerySession.query`` over the whole workload at
    once: the BENCH_query methodology, the throughput ceiling.
  * **open loop** — requests arrive on a Poisson-ish schedule at a fixed
    offered load (a fraction of the closed-loop capacity), spread over
    several tenants, and are served by the deadline-aware coalescing
    loop. Run twice at the SAME offered load: coalesced (default
    ``batch_target``) vs single-request submit (``batch_target=1`` —
    every request becomes its own slab). The occupancy gap is the win
    the frontend exists to deliver; per-tenant p50/p99 and deadline
    misses quantify what the deadline bound costs.
  * **hot-pair cache** — a skewed workload (most requests re-ask a small
    hot set) with the answer cache on: fully-cached requests complete at
    submit without touching the device (``short_circuits``).

The open-loop driver is hybrid-time: compute runs in real time, but idle
gaps between arrivals/deadlines are fast-forwarded through the injected
clock — offered load is honored without wall-clock sleeping, so the
bench runs in seconds while latencies still include real device time
plus (virtual) queueing delay.
"""
from __future__ import annotations

import argparse
import json
import time

from .common import Timer, emit, get_graph, quick_mode


class HybridClock:
    """perf_counter plus a fast-forwardable offset (idle-gap skipping)."""

    def __init__(self):
        self.offset = 0.0

    def __call__(self) -> float:
        return time.perf_counter() + self.offset

    def fast_forward_to(self, t: float) -> None:
        now = self()
        if t > now:
            self.offset += t - now


def _make_arrivals(g, *, n_requests, req_size, n_tenants, offered_qps,
                   seed, hot_frac=0.0, hot_pool=32):
    """(t_arrival, tenant, srcs, dsts) sorted by arrival; exponential
    inter-arrival gaps at ``offered_qps`` queries/second aggregate."""
    import numpy as np

    from repro.core.workload import random_queries
    rng = np.random.default_rng(seed)
    qs, qt = random_queries(g, n_requests * req_size, seed=seed + 1)
    if hot_frac > 0.0:
        hs, ht = random_queries(g, hot_pool, seed=seed + 2)
        hot = rng.random(qs.size) < hot_frac
        pick = rng.integers(0, hot_pool, size=qs.size)
        qs = np.where(hot, hs[pick], qs)
        qt = np.where(hot, ht[pick], qt)
    gaps = rng.exponential(req_size / offered_qps, size=n_requests)
    t_arr = np.cumsum(gaps)
    out = []
    for i in range(n_requests):
        lo = i * req_size
        out.append((float(t_arr[i]), f"tenant-{i % n_tenants}",
                    qs[lo:lo + req_size], qt[lo:lo + req_size]))
    return out


def _drive_open_loop(fe, arrivals, clock):
    """Feed ``arrivals`` at their offered-load schedule; poll the
    coalescing loop; fast-forward idle gaps. Returns (wall_compute_s,
    rejected_count, answers{ticket: np.ndarray})."""
    from repro.reach import Rejected
    i, rejected = 0, 0
    answers = {}
    t0 = clock()
    real0 = time.perf_counter()
    while i < len(arrivals) or fe.router.pending_queries or fe.busy:
        now = clock()
        while i < len(arrivals) and t0 + arrivals[i][0] <= now:
            _, tenant, qs, qt = arrivals[i]
            try:
                fe.submit(tenant, qs, qt)
            except Rejected:
                rejected += 1
            i += 1
        fe.poll(now=clock())
        answers.update(fe.results())
        if fe.busy or fe.router.pending_queries >= fe.batch_target:
            continue                      # more work is ready right now
        nxt = []
        if i < len(arrivals):
            nxt.append(t0 + arrivals[i][0])
        d = fe.next_deadline()
        if d is not None:
            nxt.append(d)
        if nxt:
            clock.fast_forward_to(min(nxt))
        elif not (fe.router.pending_queries or fe.busy):
            break
    answers.update(fe.drain())
    compute = time.perf_counter() - real0       # real compute time only
    return compute, rejected, answers


def _open_loop_entry(sess_factory, arrivals, *, batch_target,
                     deadline_us, cache_entries, service_hint_us=None):
    import numpy as np

    from repro.reach import Frontend
    sess = sess_factory()
    # pre-trace every bucket a slab can land in, and run a real workload
    # prefix so the lazy phase-2 executors compile too (a tiny warm batch
    # can have an empty residue and leave the multi-second BFS compile
    # inside the driven run): compiles must not count against deadlines
    sizes, b = [], sess.spec.min_bucket
    while b <= sess.spec.max_batch:
        sizes.append(b)
        b *= 2
    cat_s = np.concatenate([a[2] for a in arrivals])
    cat_t = np.concatenate([a[3] for a in arrivals])
    m = min(1024, cat_s.size)
    sess.query(cat_s[:m], cat_t[:m])
    sess.warmup(*sizes)
    clock = HybridClock()
    fe = Frontend(sess, batch_target=batch_target,
                  deadline_us=deadline_us, cache_entries=cache_entries,
                  service_hint_us=service_hint_us, clock=clock)
    n_q = sum(a[2].size for a in arrivals)
    compute_s, rejected, answers = _drive_open_loop(fe, arrivals, clock)
    st = fe.stats
    served = sum(a.size for a in answers.values())
    return fe, {
        "batch_target": batch_target,
        "deadline_us": deadline_us,
        "offered_queries": int(n_q),
        "served_queries": int(served),
        "rejected_requests": int(rejected),
        "compute_seconds": compute_s,
        "ns_per_query": 0.0 if served == 0 else compute_s / served * 1e9,
        "occupancy": st.occupancy,
        "queries_per_slab": (0.0 if st.n_batches == 0
                             else st.batch_queries / st.n_batches),
        "deadline_misses": st.deadline_misses,
        "flushes": {"deadline": st.deadline_flushes,
                    "full": st.full_flushes, "forced": st.forced_flushes},
        "occupancy_hist": {str(k): v for k, v in
                           sorted(st.occupancy_hist.items())},
        "tenants": {k: v.as_dict() for k, v in st.tenants.items()},
    }


def run_bench_json(out_path: str = "BENCH_serve.json",
                   dataset: str = "go-like", n_requests: int | None = None,
                   req_size: int = 8, n_tenants: int = 4,
                   load_factor: float = 0.25, deadline_us: float = 20_000.0,
                   k: int = 2, seed: int = 0):
    import numpy as np

    from repro.core.workload import random_queries
    from repro.reach import IndexSpec, QuerySession, build
    n_requests = n_requests or (512 if quick_mode() else 4_096)
    g = get_graph(dataset)
    spec = IndexSpec(k=k, variant="G", phase2_mode="auto")
    with Timer() as tb:
        ix = build(g, spec)

    def sess_factory():
        return QuerySession(ix, spec)

    # ---------------------------------------------------- closed loop
    n_closed = n_requests * req_size
    qs, qt = random_queries(g, n_closed, seed=seed + 7)
    sess = sess_factory()
    sess.query(qs[:256], qt[:256])
    sess.warmup(min(n_closed, spec.max_batch), n_closed % spec.max_batch)
    with Timer() as t:
        want_closed = sess.query(qs, qt)
    closed_ns = t.seconds / n_closed * 1e9
    emit(f"serve/{dataset}/closed-loop", t.seconds / n_closed * 1e6,
         f"ns_per_q={closed_ns:.0f}")
    # a deadline below the platform's one-slab service floor is
    # unmeetable by construction (CPU interpret-mode pallas serves a
    # small slab in seconds; an accelerator in microseconds), and would
    # report 100% misses that say nothing about the frontend — floor
    # the effective SLO at 4x the measured warm service time of a
    # representative slab so deadline_misses measures scheduling, not
    # the platform. The same measurement seeds the loop's service EWMA.
    with Timer() as tf:
        sess.query(qs[:256], qt[:256])
    service_floor_us = tf.seconds * 1e6
    deadline_eff = max(deadline_us, 4.0 * service_floor_us)
    out = {"dataset": dataset, "n_nodes": int(g.n), "n_edges": int(g.m),
           "k": k, "build_seconds": tb.seconds,
           "n_requests": n_requests, "req_size": req_size,
           "n_tenants": n_tenants,
           "deadline_us_requested": deadline_us,
           "deadline_us_effective": deadline_eff,
           "service_floor_us": service_floor_us,
           "closed_loop": {"n_queries": n_closed,
                           "ns_per_query": closed_ns}}

    # ------------------------------------------------------ open loop
    # offered load = load_factor × the closed-loop capacity, same for
    # both submit policies — the comparison the frontend is judged on
    offered_qps = load_factor * 1e9 / closed_ns
    out["offered_qps"] = offered_qps
    arrivals = _make_arrivals(g, n_requests=n_requests, req_size=req_size,
                              n_tenants=n_tenants, offered_qps=offered_qps,
                              seed=seed)
    fe, coalesced = _open_loop_entry(
        sess_factory, arrivals, batch_target=spec.max_batch,
        deadline_us=deadline_eff, cache_entries=0,
        service_hint_us=service_floor_us)
    # correctness spot-check against the session's own closed-loop path
    probe_s = np.concatenate([a[2] for a in arrivals[:16]])
    probe_t = np.concatenate([a[3] for a in arrivals[:16]])
    assert np.array_equal(fe.session.query(probe_s, probe_t),
                          sess.query(probe_s, probe_t))
    _, single = _open_loop_entry(
        sess_factory, arrivals[: max(64, n_requests // 8)],
        batch_target=1, deadline_us=deadline_eff, cache_entries=0,
        service_hint_us=service_floor_us)
    out["open_loop"] = {"coalesced": coalesced, "single_submit": single}
    emit(f"serve/{dataset}/open-coalesced",
         coalesced["ns_per_query"] / 1e3,
         f"occ={coalesced['occupancy']:.3f};"
         f"q_per_slab={coalesced['queries_per_slab']:.1f};"
         f"misses={coalesced['deadline_misses']}")
    emit(f"serve/{dataset}/open-single",
         single["ns_per_query"] / 1e3,
         f"occ={single['occupancy']:.3f};"
         f"q_per_slab={single['queries_per_slab']:.1f}")

    # ------------------------------------------------- hot-pair cache
    hot = _make_arrivals(g, n_requests=n_requests, req_size=req_size,
                         n_tenants=n_tenants, offered_qps=offered_qps,
                         seed=seed + 11, hot_frac=0.9, hot_pool=32)
    fe, hot_entry = _open_loop_entry(
        sess_factory, hot, batch_target=spec.max_batch,
        deadline_us=deadline_eff, cache_entries=spec.cache_entries,
        service_hint_us=service_floor_us)
    st = fe.stats
    out["cache"] = {
        "hot_frac": 0.9, "hot_pool": 32,
        "served_queries": hot_entry["served_queries"],
        "compute_seconds": hot_entry["compute_seconds"],
        "ns_per_query": hot_entry["ns_per_query"],
        "deadline_misses": hot_entry["deadline_misses"],
        "short_circuits": sum(t.cache_short_circuits
                              for t in st.tenants.values()),
        **(st.cache or {}),
    }
    emit(f"serve/{dataset}/cache-hot",
         out["cache"]["ns_per_query"] / 1e3,
         f"hit_rate={out['cache'].get('hit_rate', 0.0):.3f};"
         f"short_circuits={out['cache']['short_circuits']}")

    from ._bench_schema import attach_envelope
    attach_envelope(out, bench="serve")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {out_path}", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_serve.json")
    ap.add_argument("--dataset", default="go-like")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--req-size", type=int, default=8)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--load", type=float, default=0.25,
                    help="offered load as a fraction of closed-loop "
                         "capacity")
    ap.add_argument("--deadline-us", type=float, default=20_000.0,
                help="requested SLO; the bench floors the effective "
                     "deadline at 4x the measured min-slab service "
                     "time so misses measure scheduling, not the "
                     "platform (no-op on real accelerators)")
    args = ap.parse_args()
    run_bench_json(args.json, dataset=args.dataset,
                   n_requests=args.requests, req_size=args.req_size,
                   n_tenants=args.tenants, load_factor=args.load,
                   deadline_us=args.deadline_us)


if __name__ == "__main__":
    main()
