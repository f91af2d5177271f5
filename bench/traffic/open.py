"""Open loop on the real clock: requests arrive on a schedule, whether or not
the service keeps up, and go through ``Frontend.submit`` / ``poll``.

Parameters (the mix's file): ``rate`` requests per second, each of
``pairs_per_request`` pairs (``positive_share`` of them from random forward
walks of at most ``max_walk`` steps, the rest uniform), from ``tenants``
tenants; ``warm_requests`` requests of a fixed warm-up stream go through a
throwaway frontend before the window.

Every seed gets the same number of requests, ``rate * --seconds``, and the
same set of gaps between them (Poisson gaps drawn once from a fixed seed),
in an order drawn from the seed, as are the pairs and each request's
tenant. After the last arrival the loop runs on until every request is
answered, a minute at most.

End to end: ``p50_ms`` and ``p95_ms`` of the latency of every request due
in the window, from when it was due to when its answers reached the client
(a cell reports those its entry in ``BENCHMARK.json`` names; the earlier
line prints both).
A refused request, or one still unanswered at the end, counts as failed and
takes the whole run's length as its latency, beyond any limit. An earlier
line reports how late the generator ran, the longest single ``submit`` and
``poll`` call (where a stall of the loop sits), and the compiles inside the
window.
"""
from __future__ import annotations

import time

import numpy as np

GAP_SEED = 104_729          # the one set of gaps every seed reorders
DRAIN_S = 60.0
SPIN_S = 2e-4               # below this, spin instead of sleeping


def schedule(rate: float, seconds: float, seed: int):
    """Due times (s from the window's start) of ``round(rate * seconds)``
    requests: a fixed set of Poisson gaps in an order drawn from ``seed``."""
    from harness.gen import poisson_arrivals
    n = max(1, int(round(rate * seconds)))
    gaps = np.diff(poisson_arrivals(n, rate, GAP_SEED), prepend=0.0)
    return np.cumsum(np.random.default_rng(seed).permutation(gaps))


def _buckets(spec) -> list:
    b, out = spec.min_bucket, []
    while b < spec.max_batch:
        out.append(b)
        b <<= 1
    return out + [spec.max_batch]


def traffic(ctx):
    """(srcs, dsts, pairs per request) of the requests due, from the seed."""
    k = int(ctx.params["pairs_per_request"])
    n_req = schedule(float(ctx.params["rate"]), ctx.seconds, ctx.seed).size
    s, t = ctx.pairs(n_req * k, ctx.seed)
    return s, t, k


def run(ctx) -> dict:
    from repro.reach import Frontend, Rejected

    p = ctx.params
    sess = ctx.session
    due = schedule(float(p["rate"]), ctx.seconds, ctx.seed)
    n_req = due.size
    s, t, k = traffic(ctx)
    tenant = np.random.default_rng(ctx.seed + 5).integers(
        0, int(p["tenants"]), size=n_req)

    # warm-up: every bucket a slab can take, then the frontend path with
    # the phase-2 programs the mix reaches
    sess.warmup(*_buckets(sess.spec))
    warm = int(p["warm_requests"])
    ws, wt = ctx.warm_pairs(warm * k)
    fe = Frontend(sess)
    for i in range(warm):
        fe.submit(f"tenant-{i % int(p['tenants'])}", ws[i * k:(i + 1) * k],
                  wt[i * k:(i + 1) * k])
    fe.drain()
    sess.reset_stats()

    fe = Frontend(sess)
    clock = time.perf_counter
    tickets = {}                   # ticket -> request
    done_at = np.full(n_req, np.nan)
    answers = [None] * n_req
    lateness = np.zeros(n_req)
    rejected = 0
    slowest_submit = slowest_poll = 0.0
    i = 0
    with ctx.window():
        t0 = clock()
        while True:
            now = clock() - t0
            while i < n_req and due[i] <= now:
                lateness[i] = now - due[i]
                t_call = clock()
                with ctx.span("bench.submit"):
                    try:
                        tk = fe.submit(f"tenant-{tenant[i]}",
                                       s[i * k:(i + 1) * k],
                                       t[i * k:(i + 1) * k])
                        tickets[tk] = i
                    except Rejected:
                        rejected += 1
                slowest_submit = max(slowest_submit, clock() - t_call)
                i += 1
            if fe.busy or fe.router.pending_queries:
                t_call = clock()
                with ctx.span("bench.poll"):
                    fe.poll()
                slowest_poll = max(slowest_poll, clock() - t_call)
            # every completed request, those the answer cache served
            # whole at submit among them
            got = fe.results()
            if got:
                t_done = clock() - t0
                for tk, ans in got.items():
                    r = tickets.pop(tk)
                    done_at[r] = t_done
                    answers[r] = ans
            now = clock() - t0
            if i >= n_req and not tickets:
                break
            if now > ctx.seconds + DRAIN_S:
                break
            if fe.busy:
                continue
            wake = due[i] if i < n_req else np.inf
            flush = fe.next_deadline()
            if flush is not None:
                wake = min(wake, flush - t0)
            wait = wake - now
            if wait > SPIN_S:
                with ctx.span("bench.wait"):
                    time.sleep(wait - SPIN_S)
        elapsed = clock() - t0

    answered = ~np.isnan(done_at)
    lat = np.where(answered, done_at - due, elapsed)
    failed = int(n_req - answered.sum())
    p50_ms, p95_ms = (float(x) * 1e3 for x in np.percentile(lat, [50, 95]))
    half = n_req // 2
    backlog = {"p50_first_half_ms": float(np.median(lat[:half]) * 1e3),
               "p50_second_half_ms": float(np.median(lat[half:]) * 1e3),
               "late_p50_ms": float(np.median(lateness) * 1e3),
               "late_max_ms": float(lateness.max() * 1e3),
               "slowest_submit_ms": slowest_submit * 1e3,
               "slowest_poll_ms": slowest_poll * 1e3}
    print(f"open loop: {n_req} requests due in {ctx.seconds} s, "
          f"{int(answered.sum())} answered, {rejected} refused, "
          f"{len(tickets)} unanswered; generator late p50 "
          f"{backlog['late_p50_ms']:.4f} ms, max "
          f"{backlog['late_max_ms']:.4f} ms; slowest submit "
          f"{backlog['slowest_submit_ms']:.4f} ms, slowest poll "
          f"{backlog['slowest_poll_ms']:.4f} ms; latency p50 {p50_ms:.4f} ms, "
          f"p95 {p95_ms:.4f} ms; p50 "
          f"{backlog['p50_first_half_ms']:.4f} ms in the first half of the "
          f"requests, {backlog['p50_second_half_ms']:.4f} ms in the second; "
          f"{ctx.compiles_in_window} compiles inside the window", flush=True)
    fs = fe.stats
    return {
        "attempted": n_req, "failed": failed,
        "end_to_end": {"p50_ms": p50_ms, "p95_ms": p95_ms},
        "unit": "request",
        "unanswered": len(tickets),
        "backlog": backlog,
        "groups": [(s[r * k:(r + 1) * k], t[r * k:(r + 1) * k], answers[r])
                   for r in np.flatnonzero(answered)],
        "counters": {"session": sess.stats.as_dict(),
                     "frontend": {"n_batches": fs.n_batches,
                                  "batch_queries": fs.batch_queries,
                                  "batch_slots": fs.batch_slots}},
    }
