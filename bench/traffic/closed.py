"""Closed loop: one client sends a batch of pairs through
``QuerySession.query``, waits for the answers, and sends the next.

Parameters (the mix's file): ``batch`` pairs per call, ``pool_batches``
distinct batches drawn from the seed and sent in turn, ``positive_share``
of the pairs taken from random forward walks (``max_walk`` steps at most),
the rest uniform; ``warm_batches`` batches of a fixed warm-up stream are
sent before the window, so that every program the window runs (the bucket
of ``batch`` and the phase-2 expansions the mix reaches) is compiled or
loaded by then.

End to end: ``qps``, the pairs answered in the window over the window,
which closes when the batch in flight at ``--seconds`` is answered.
"""
from __future__ import annotations

import time


def traffic(ctx):
    """(srcs, dsts, pairs per call) the client sends, from the seed."""
    batch = int(ctx.params["batch"])
    s, t = ctx.pairs(batch * int(ctx.params["pool_batches"]), ctx.seed)
    return s, t, batch


def run(ctx) -> dict:
    p = ctx.params
    s, t, batch = traffic(ctx)
    pool = int(p["pool_batches"])
    warm = int(p["warm_batches"])
    sess = ctx.session
    ws, wt = ctx.warm_pairs(batch * warm)
    for i in range(warm):
        sess.query(ws[i * batch:(i + 1) * batch], wt[i * batch:(i + 1) * batch])
    sess.reset_stats()
    sent = []
    with ctx.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            j = len(sent) % pool
            with ctx.span("bench.query"):
                ans = sess.query(s[j * batch:(j + 1) * batch],
                                 t[j * batch:(j + 1) * batch])
            sent.append((j, ans))
        elapsed = time.perf_counter() - t0
    pairs = len(sent) * batch
    return {
        "attempted": pairs, "failed": 0,
        "end_to_end": {"qps": pairs / elapsed},
        "unit": "pair",
        "groups": [(s[j * batch:(j + 1) * batch], t[j * batch:(j + 1) * batch],
                    ans) for j, ans in sent],
        "counters": {"session": sess.stats.as_dict()},
    }

