"""Serving drivers.

Two serving paths, matching the paper's kind (index serving) plus LM decode:

  * reachability: obtain a FERRARI index (build it — ``--builder host``
    or the staged ``wavefront`` device pipeline with tree-reduction merge
    fan-in, DESIGN.md §2 — or load a persisted artifact in seconds), then
    serve batched query streams through the
    ``repro.reach.QuerySession`` facade — bucketed micro-batching, unified
    SessionStats, no jit retraces after warmup. The production analogue of
    the paper's §7 query-processing experiments. ``--placement`` scales the
    session out over every visible device: ``replicated`` shards the query
    stream (zero collectives), ``sharded`` also shards the index rows over
    the model axis of ``--mesh`` (DESIGN.md §3.6) — answers stay
    bit-identical to the single-device engine.
  * lm: prefill + decode loop over a smoke-scale LM (batched requests).

    PYTHONPATH=src python -m repro.launch.serve --mode reachability \
        --nodes 20000 --queries 100000 --k 2 --index-dir /tmp/ferrari-idx

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --mode reachability \
        --index-dir /tmp/ferrari-idx --placement sharded --mesh 2x4
"""
from __future__ import annotations

import argparse
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

from .. import obs
from ..core.workload import (positive_queries, random_edge_inserts,
                             random_queries)
from ..graphs.generators import scale_free_digraph
from ..reach import IndexSpec, QuerySession, build, save_index
from ..reach.persist import load_manifest
from ..reach.spec import BUILD_FIELDS


def serve_reachability(n_nodes: int, avg_deg: float, n_queries: int,
                       k: int = 2, variant: str = "G", batch: int = 16384,
                       seed: int = 0, workload: str = "random",
                       phase2: str = "auto", n_dense_max: int = 8192,
                       ell_width: int | None = None, n_seeds: int = 32,
                       use_seeds: bool = True,
                       spec: IndexSpec | None = None,
                       index_dir: str | None = None,
                       n_updates: int = 0, update_batch: int = 256,
                       n_tenants: int = 0, request_size: int = 64,
                       metrics_dump: str | None = None,
                       trace_out: str | None = None):
    """Serve a synthetic reachability workload through the facade.

    ``spec`` is the one source of truth; the individual knob kwargs
    (k/variant/phase2/...) are the pre-facade signature, kept as a thin
    deprecation shim and folded into an IndexSpec when ``spec`` is None.
    ``index_dir``: load the index artifact from there if one is committed,
    else build and save there (first run builds, reruns load).

    ``n_updates`` streams that many random edge inserts through
    ``session.apply_updates`` in batches of ``update_batch``, interleaved
    with query batches — the live-graph serving loop of DESIGN.md §6.
    Bound sessions (--index-dir) log every batch to the artifact's delta
    log; a rerun replays them on load, so the served graph keeps growing
    across restarts.

    ``n_tenants > 0`` re-serves the workload through the async frontend
    (DESIGN.md §7): the stream is chopped into ``request_size``-pair
    requests spread round-robin over the tenants and pushed through the
    deadline-aware coalescing loop — admission backpressure drives the
    loop instead of growing a queue — and the FrontendStats snapshot
    (per-tenant p50/p99, deadline misses, occupancy, cache hit rate) is
    printed and returned. ``spec.deadline_us`` / ``spec.tenant_queue_cap``
    / ``spec.cache_entries`` are the knobs (``--deadline-us``,
    ``--tenant-queue-cap``, ``--cache``).
    """
    if trace_out is not None:
        # spans record from here on: build stages, every slab's lifecycle,
        # phase-1/phase-2 splits — exported Perfetto-loadable at the end
        obs.enable_tracing()
    if spec is None:
        spec = IndexSpec(k=(None if variant == "full" else k),
                         variant=variant, n_seeds=n_seeds,
                         use_seeds=use_seeds, phase2_mode=phase2,
                         n_dense_max=n_dense_max, ell_width=ell_width,
                         max_batch=batch, min_bucket=min(256, batch))
    batch = spec.max_batch            # the session's actual micro-batch size
    print(f"building graph n={n_nodes} avg_deg={avg_deg} ...", flush=True)
    g = scale_free_digraph(n_nodes, avg_deg, seed=seed)
    graph_meta = {"generator": "scale_free_digraph", "n_nodes": n_nodes,
                  "avg_deg": avg_deg, "seed": seed}
    t0 = time.perf_counter()
    loaded = False
    if index_dir is not None and any(Path(index_dir).glob("step_*.done")):
        # build knobs are baked into the artifact — take them from its
        # manifest (the CLI defaults would silently misreport k/variant/...
        # in stats otherwise); CLI engine/session/placement knobs still
        # apply. ell_width additionally adopts the saved value when the
        # CLI leaves it None, so the persisted ELL layout is reused.
        saved = load_manifest(index_dir)["extra"].get("spec")
        if saved is not None:
            saved_spec = IndexSpec.from_dict(saved)
            merged = {f: getattr(saved_spec, f) for f in BUILD_FIELDS}
            if spec.ell_width is None:
                merged["ell_width"] = saved_spec.ell_width
            dropped = {f: (getattr(spec, f), v) for f, v in merged.items()
                       if getattr(spec, f) != v}
            if dropped:
                print("note: taking build knobs from the artifact: "
                      + ", ".join(f"{f}: {cli!r} -> {art!r}"
                                  for f, (cli, art) in dropped.items()),
                      flush=True)
            spec = replace(spec, **merged)
        sess = QuerySession.load(index_dir, spec)
        # an index is only valid for the graph it was built over: answers
        # against any other graph are silently garbage (gather clamping),
        # so reject mismatched artifacts outright
        saved_graph = sess.artifact_manifest["extra"].get(
            "user_meta", {}).get("graph")
        if saved_graph is not None and saved_graph != graph_meta:
            raise ValueError(
                f"index artifact at {index_dir} was built over "
                f"{saved_graph}, not {graph_meta}; rebuild it or point "
                f"--index-dir elsewhere")
        if sess.index.cond.comp.shape[0] != g.n:
            raise ValueError(
                f"index artifact at {index_dir} covers "
                f"{sess.index.cond.comp.shape[0]} nodes, graph has {g.n}")
        t_build = time.perf_counter() - t0
        loaded = True
        print(f"index loaded from {index_dir} in {t_build:.2f}s", flush=True)
    else:
        ix = build(g, spec)
        t_build = time.perf_counter() - t0
        print(f"index built in {t_build:.2f}s ({spec.builder}): "
              f"{ix.stats.n_comp} SCCs, "
              f"{ix.stats.total_intervals} intervals "
              f"({ix.byte_size() / 2**20:.1f} MiB)", flush=True)
        if spec.builder == "wavefront":
            # the DESIGN.md §2 contract: hub fan-in stays on device
            print(f"wavefront build: {ix.stats.hub_nodes} hub nodes, "
                  f"{ix.stats.merge_rounds} merge rounds, "
                  f"{ix.stats.host_fallbacks} host fallbacks, "
                  f"peak slab {ix.stats.peak_slab_bytes / 2**20:.1f} MiB",
                  flush=True)
        # pack once, share between the artifact and the session — both
        # pack_index and ell_layout are O(n) host loops. The ELL layout is
        # only built when something will consume it (a saved artifact, or
        # a session whose phase 2 resolves to the sparse engine).
        from ..core.packed import pack_index
        pk = pack_index(ix)
        p2 = spec.phase2_mode
        if p2 == "auto":
            p2 = ("sparse" if spec.placement != "single"
                  else ("dense" if pk.n <= spec.n_dense_max else "sparse"))
        ell = (pk.ell_layout(width=spec.ell_width)
               if index_dir is not None or p2 == "sparse" else None)
        sess = QuerySession(ix, spec, packed=pk, ell=ell)
        if index_dir is not None:
            save_index(index_dir, ix, spec, meta={"graph": graph_meta},
                       packed=pk, ell=ell)
            sess.bind_artifact(index_dir)     # updates log + replay on rerun
            print(f"index saved to {index_dir}", flush=True)
    if spec.placement != "single":
        mesh = sess.engine.mesh
        print(f"placement: {spec.placement} over mesh "
              f"{dict(mesh.shape)} ({mesh.size} devices)", flush=True)
    print(f"phase-2 engine: {sess.engine.phase2_mode}", flush=True)
    qs, qt = (random_queries if workload == "random"
              else positive_queries)(g, n_queries, seed=seed + 1)
    # warmup: a real first batch compiles phase 1 + the phase-2 path it
    # exercises; then pre-trace the ragged-tail bucket so the timed loop
    # never compiles (asserted by tests via trace_count)
    first = min(batch, n_queries)
    sess.query(qs[:first], qt[:first])
    sess.warmup(n_queries % batch)        # no-op when the stream divides
    t0 = time.perf_counter()
    ans = sess.query(qs, qt)              # session chops into micro-batches
    dt = time.perf_counter() - t0
    pos = int(ans.sum())
    stats = sess.stats
    print(f"{n_queries} {workload} queries in {dt * 1e3:.1f} ms "
          f"({dt / n_queries * 1e9:.0f} ns/query), {pos} positive, "
          f"{sess.trace_count} phase-1 traces")
    print(f"phase stats: {stats}")
    frontend_stats = None
    if n_tenants > 0:
        from ..reach import Frontend, Rejected
        # a request larger than min(queue_cap, max_batch) is rejected
        # "too_large" on EVERY submit — no amount of polling makes it
        # admissible, so validate up front instead of spinning forever
        admissible = min(spec.tenant_queue_cap, spec.max_batch)
        if request_size > admissible:
            raise ValueError(
                f"--request-size {request_size} exceeds the admissible "
                f"bound min(tenant_queue_cap={spec.tenant_queue_cap}, "
                f"max_batch={spec.max_batch}) = {admissible}; shrink the "
                "request or raise --tenant-queue-cap/--max-batch")
        fe = Frontend(sess)
        backpressure = 0
        t0 = time.perf_counter()
        for i, lo in enumerate(range(0, n_queries, request_size)):
            tenant = f"tenant-{i % n_tenants}"
            s, d = qs[lo:lo + request_size], qt[lo:lo + request_size]
            while True:
                try:
                    fe.submit(tenant, s, d)
                    break
                except Rejected as e:
                    if e.reason != "queue_full":
                        raise      # permanent: polling can't fix it
                    # bounded queues: drain the loop instead of growing
                    backpressure += 1
                    fe.poll()
        served = sum(a.size for a in fe.drain().values())
        dt_f = time.perf_counter() - t0
        frontend_stats = fe.stats
        print(f"frontend: {served} queries over {n_tenants} tenants "
              f"({request_size}/request) in {dt_f * 1e3:.1f} ms "
              f"({dt_f / max(served, 1) * 1e9:.0f} ns/query), "
              f"{backpressure} backpressure stalls, "
              f"occupancy {frontend_stats.occupancy:.3f}, "
              f"{frontend_stats.deadline_misses} deadline misses")
        for name in sorted(frontend_stats.tenants):
            t = frontend_stats.tenants[name]
            # percentiles are None until a tenant completes a request
            p50 = "n/a" if t.p50_us is None else f"{t.p50_us:.0f}us"
            p99 = "n/a" if t.p99_us is None else f"{t.p99_us:.0f}us"
            print(f"  {name}: {t.completed}/{t.requests} requests "
                  f"p50={p50} p99={p99} "
                  f"misses={t.deadline_misses} "
                  f"cache_hits={t.cache_short_circuits}")
        print(fe.slowlog.format_report())
        if frontend_stats.cache is not None:
            c = frontend_stats.cache
            print(f"  cache: {c['entries']}/{c['capacity']} entries, "
                  f"hit_rate={c['hit_rate']:.3f}, "
                  f"{c['evictions']} evictions, "
                  f"{c['invalidations']} invalidations")
    update_stats = None
    if n_updates > 0:
        # live-graph churn loop: insert a batch, then answer a query slice
        # against the mutated graph — no restart, no rebuild (DESIGN.md §6)
        if sess.epoch or sess.stats.overlay_edges:
            print(f"resumed at epoch {sess.epoch} with "
                  f"{sess.stats.overlay_edges} replayed overlay edges",
                  flush=True)
        # fold the resume point into the seed: a rerun extends the replayed
        # graph with FRESH edges instead of re-drawing (and deduping) the
        # previous run's stream
        rng = np.random.default_rng(
            (seed + 2, sess.epoch, sess.stats.overlay_edges))
        sess.reset_stats()
        qcur = 0
        t0 = time.perf_counter()
        for lo in range(0, n_updates, update_batch):
            b = min(update_batch, n_updates - lo)
            # orient by the condensed topological order: inserts never
            # close a condensed cycle, so auto-compactions stay on the
            # bounded incremental path even on cyclic graphs
            sess.apply_updates(*random_edge_inserts(
                g.n, b, rng, order=sess.index.cond.comp))
            hi_q = min(qcur + batch, n_queries)
            if hi_q > qcur:
                sess.query(qs[qcur:hi_q], qt[qcur:hi_q])
                qcur = hi_q
        dt_u = time.perf_counter() - t0
        update_stats = sess.stats
        print(f"{n_updates} edge inserts in {dt_u:.2f}s "
              f"({n_updates / dt_u:.0f} updates/s interleaved with "
              f"{qcur} queries), {update_stats.n_compactions} compactions, "
              f"overlay fill {update_stats.overlay_edges}/"
              f"{spec.overlay_cap}, epoch {sess.epoch}")
        print(f"churn stats: {update_stats}")
    if metrics_dump is not None:
        import json
        snap = obs.metrics_snapshot()
        if n_tenants > 0:
            snap["slowlog"] = fe.slowlog.as_dict()
        with open(metrics_dump, "w") as f:
            json.dump(snap, f, indent=2, default=str)
        print(f"metrics snapshot written to {metrics_dump}", flush=True)
    if trace_out is not None:
        tr = obs.get_tracer()
        obs.export_chrome_trace(trace_out)
        print(f"trace written to {trace_out} "
              f"({len(tr.events())} spans, {tr.n_dropped} dropped) — "
              "load it at https://ui.perfetto.dev", flush=True)
    return {"seconds": dt, "ns_per_query": dt / n_queries * 1e9,
            "positive": pos, "stats": stats, "build_seconds": t_build,
            "loaded": loaded, "trace_count": sess.trace_count,
            "update_stats": update_stats, "epoch": sess.epoch,
            "frontend_stats": frontend_stats, "spec": spec}


def serve_lm(arch: str, batch: int, prompt_len: int, gen_len: int):
    import jax
    import jax.numpy as jnp
    from ..configs.registry import get_smoke
    from ..models import transformer as tf
    cfg = get_smoke(arch)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                              0, cfg.vocab)
    max_seq = prompt_len + gen_len
    t0 = time.perf_counter()
    logits, cache = tf.prefill(cfg, params, toks, max_seq)
    # pad cache to max_seq already handled by prefill
    decode = jax.jit(lambda p, c, t, pos: tf.decode_step(cfg, p, c, t, pos))
    cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [cur]
    for i in range(gen_len - 1):
        logits, cache = decode(params, cache, cur, jnp.int32(prompt_len + i))
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(cur)
    dt = time.perf_counter() - t0
    toks_out = jnp.concatenate(out, axis=1)
    print(f"served {batch} requests x {gen_len} tokens in {dt:.2f}s "
          f"({batch * gen_len / dt:.0f} tok/s)")
    return np.asarray(toks_out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["reachability", "lm"],
                    default="reachability")
    ap.add_argument("--nodes", type=int, default=20_000)
    ap.add_argument("--avg-deg", type=float, default=4.0)
    ap.add_argument("--queries", type=int, default=100_000)
    ap.add_argument("--workload", default="random",
                    choices=["random", "positive"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--index-dir", default=None,
                    help="load the index artifact from here if committed, "
                         "else build and save here")
    ap.add_argument("--updates", type=int, default=0,
                    help="stream this many random edge inserts through the "
                         "live session, interleaved with query batches "
                         "(logged + replayed when --index-dir is set)")
    ap.add_argument("--update-batch", type=int, default=256,
                    help="edge inserts per apply_updates() batch")
    ap.add_argument("--tenants", type=int, default=0,
                    help="also serve the stream through the async "
                         "frontend (DESIGN.md §7) spread over this many "
                         "tenants (0 = skip)")
    ap.add_argument("--request-size", type=int, default=64,
                    help="query pairs per frontend request")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="write the obs metrics-registry snapshot (JSON: "
                         "all counters/histograms/stat views + the "
                         "frontend slow-slab log) here on exit")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable trace spans and write a Chrome "
                         "trace-event JSON here on exit (load at "
                         "ui.perfetto.dev)")
    IndexSpec.add_cli_args(ap)       # --k --variant --phase2 --max-batch ...
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=4,
                    help="lm mode: decode batch size")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    args = ap.parse_args()
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "reachability":
        # clamp before construction: IndexSpec validates max_batch >= min_bucket
        args.min_bucket = min(args.min_bucket, args.max_batch)
        spec = IndexSpec.from_args(args)
        serve_reachability(args.nodes, args.avg_deg, args.queries,
                           seed=args.seed, workload=args.workload,
                           spec=spec, index_dir=args.index_dir,
                           n_updates=args.updates,
                           update_batch=args.update_batch,
                           n_tenants=args.tenants,
                           request_size=args.request_size,
                           metrics_dump=args.metrics_dump,
                           trace_out=args.trace_out)
    else:
        serve_lm(args.arch, args.batch, args.prompt_len, args.gen_len)


if __name__ == "__main__":
    main()
