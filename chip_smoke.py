#!/usr/bin/env python3
"""Smoke run of the FERRARI reachability serving path on a TPU.

Drives, once, the paths a user calls — build → save → load through
``repro.reach``, closed-loop ``QuerySession.query`` streams, the async
multi-tenant ``Frontend``, and the ``builder="wavefront"`` device build with
the compiled merge-cover kernel — over the paper's Cit-Patents deployment
(3,774,768 nodes, ~16.5M edges; paper Table 3), generated from a seed as the
``citpatents-like`` layered DAG with its 200 layers and average degree 4.38,
its node count cut to fit the run in 1200 s (``N_NODES``; printed as the
``cut:`` line). Every answer checked is compared with the host guided DFS
(``core.query.QueryEngine``).

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # replicated + sharded placements

It exits non-zero before any work unless JAX's first device is a TPU, and
on any failed check. Measurements go to earlier lines of stdout; the last
line is one JSON object ``{"ok": true, "device": {...}}``. Times printed
here are smoke readings of one run, not benchmark numbers.

The host-built index is saved under ``.smoke_index/`` in the checkout and
loaded from there, so a rerun on the same machine skips the build.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Cit-Patents (paper Table 3) as the citpatents-like generator of
# benchmarks/common.py. Its published 3,774,768 nodes do not fit the smoke's
# 1200 s budget on one v5e host: at 1,000,000 nodes the run took 1510 s, of
# which most grows with n (host build, the wavefront build's host side, the
# guided-DFS fallback behind phase 2). At 300,000 nodes one v5e ran the
# whole smoke in 630 s, leaving room for a slower shared host. Layers and
# average degree stay Cit-Patents'.
PUBLISHED_NODES = 3_774_768
N_NODES = 300_000
LAYERS = 200
AVG_DEG = 4.38
GRAPH_SEED = 6
N_QUERIES = 100_000          # the paper's query sets (§7.2)
N_SAMPLE = 2_000             # pairs per set checked against the host DFS
N_TENANTS = 3
REQUEST_SIZE = 64
INDEX_DIR = ROOT / ".smoke_index"


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# --------------------------------------------------------------- device --

def require_tpu(chips: int):
    """The device check, before any work: the first device must be a TPU
    (no silent CPU fallback, no interpreted kernels), and the Pallas
    kernels must be the ones ``kernel_impl="auto"`` picks."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (first device: "
                         f"{devs[0].platform}); refusing to run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX sees {len(devs)}")
    from repro.kernels import ops
    if not ops._on_tpu() or ops.resolve_kernel_impl("auto") != "pallas":
        raise SystemExit("chip_smoke: kernels would not compile for the TPU")
    return devs


class CompileClock:
    """Seconds JAX spends in backend compiles while the ``with`` block
    runs (jax.monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.seconds += secs
            self.count += 1


# ---------------------------------------------------------------- phases --

def make_graph(n_nodes: int):
    from repro.graphs.generators import layered_dag
    return layered_dag(n_nodes, LAYERS, AVG_DEG, seed=GRAPH_SEED)


def graph_meta(n_nodes: int) -> dict:
    return {"generator": "layered_dag", "n_nodes": n_nodes,
            "layers": LAYERS, "avg_deg": AVG_DEG, "seed": GRAPH_SEED}


def host_build_saved(g, meta: dict, index_dir: Path):
    """Host build → save_index, unless ``index_dir`` already holds the
    artifact of this graph. Returns the build spec."""
    from repro.core.packed import pack_index
    from repro.reach import IndexSpec, build, save_index
    from repro.reach.persist import load_manifest
    spec = IndexSpec(k=2, variant="G")
    if any(index_dir.glob("step_*.done")):
        saved = load_manifest(index_dir)["extra"].get("user_meta", {})
        if saved.get("graph") == meta:
            log(f"index artifact found at {index_dir}: build skipped")
            return spec
        shutil.rmtree(index_dir)
    t0 = time.perf_counter()
    ix = build(g, spec)
    t_build = time.perf_counter() - t0
    log(f"host build: {t_build:.2f} s, {ix.stats.n_comp} SCCs, "
        f"{ix.stats.total_intervals} intervals "
        f"({ix.byte_size() / 2**20:.1f} MiB)")
    t0 = time.perf_counter()
    pk = pack_index(ix)
    save_index(index_dir, ix, spec, meta={"graph": meta}, packed=pk,
               ell=pk.ell_layout(width=spec.ell_width))
    log(f"index saved to {index_dir} in {time.perf_counter() - t0:.2f} s")
    return spec


def load_session(index_dir: Path, spec):
    from repro.reach import QuerySession
    t0 = time.perf_counter()
    sess = QuerySession.load(index_dir, spec)
    log(f"index loaded in {time.perf_counter() - t0:.2f} s "
        f"(placement={spec.placement})")
    return sess


def query_sets(g, n_queries: int, n_sample: int):
    """The random and positive query sets plus one fixed sample of
    indices into them."""
    import numpy as np
    from repro.core.workload import positive_queries, random_queries
    sets = {"random": random_queries(g, n_queries, seed=1),
            "positive": positive_queries(g, n_queries, seed=2)}
    sample = np.random.default_rng(3).choice(
        n_queries, size=min(n_sample, n_queries), replace=False)
    return sets, sample


def host_reference(index, sets, sample) -> dict:
    from repro.core.query import QueryEngine
    host = QueryEngine(index)
    t0 = time.perf_counter()
    ref = {name: host.batch(qs[sample], qt[sample])
           for name, (qs, qt) in sets.items()}
    log(f"host DFS reference: {2 * sample.size} pairs in "
        f"{time.perf_counter() - t0:.2f} s")
    return ref


def check_sample(label: str, sess, sets, sample, ref) -> None:
    for name, (qs, qt) in sets.items():
        got = sess.query(qs[sample], qt[sample])
        bad = int((got != ref[name]).sum())
        log(f"{label} {name} sample: {bad} of {sample.size} answers differ "
            f"from the host DFS")
        check(bad == 0, f"{label} {name} answers differ from the host DFS")


def closed_loop(sess, sets, sample, ref, clock: CompileClock) -> dict:
    """Both query sets through QuerySession.query after warmup; returns
    the answers per set."""
    spec = sess.spec
    qs, qt = sets["random"]
    t0 = time.perf_counter()
    c0 = clock.seconds
    # a real first batch compiles phase 1 and the phase-2 loop it reaches;
    # then the ragged-tail bucket
    first = min(spec.max_batch, qs.size)
    sess.query(qs[:first], qt[:first])
    sess.warmup(qs.size % spec.max_batch)
    log(f"warmup: {time.perf_counter() - t0:.2f} s, "
        f"{clock.seconds - c0:.2f} s compiling")
    answers, sparse = {}, 0
    for name, (qs, qt) in sets.items():
        sess.reset_stats()
        c0 = clock.seconds
        t0 = time.perf_counter()
        ans = sess.query(qs, qt)
        dt = time.perf_counter() - t0
        st = sess.stats
        log(f"{name}: {qs.size} queries in {dt:.3f} s "
            f"({st.ns_per_query:.0f} ns/query), {int(ans.sum())} positive, "
            f"{sess.trace_count} phase-1 traces, "
            f"{clock.seconds - c0:.2f} s compiling")
        log(f"{name} phase mix: phase1_pos={st.phase1_pos} "
            f"phase1_neg={st.phase1_neg} "
            f"phase2_queries={st.phase2_queries} "
            f"phase2_sparse={st.phase2_sparse} "
            f"phase2_host={st.phase2_host} "
            f"sparse_retries={st.sparse_retries}")
        bad = int((ans[sample] != ref[name]).sum())
        log(f"{name} sample: {bad} of {sample.size} answers differ from "
            f"the host DFS")
        check(bad == 0, f"{name} answers differ from the host DFS")
        answers[name] = ans
        sparse += st.phase2_sparse
    check(sparse > 0, "phase2_sparse == 0: the frontier engine never ran")
    return answers


def frontend(sess, qs, qt, want, n_tenants: int, request_size: int) -> None:
    """The random set through the async Frontend; every request must
    complete with the closed-loop answers."""
    import numpy as np
    from repro.reach import Frontend, Rejected
    fe = Frontend(sess)
    reqs = {}
    t0 = time.perf_counter()
    for i, lo in enumerate(range(0, qs.size, request_size)):
        s, d = qs[lo:lo + request_size], qt[lo:lo + request_size]
        while True:
            try:
                reqs[fe.submit(f"tenant-{i % n_tenants}", s, d)] = lo
                break
            except Rejected as e:
                check(e.reason == "queue_full", f"request rejected: {e}")
                fe.poll()
    results = fe.drain()
    dt = time.perf_counter() - t0
    fs = fe.stats
    log(f"frontend: {len(results)} of {len(reqs)} requests over {n_tenants} "
        f"tenants in {dt:.3f} s, occupancy {fs.occupancy:.3f}, "
        f"{fs.deadline_misses} deadline misses")
    for name in sorted(fs.tenants):
        t = fs.tenants[name]
        log(f"  {name}: {t.completed}/{t.requests} requests "
            f"p50_us={t.p50_us} p99_us={t.p99_us}")
    check(set(results) == set(reqs), "frontend left requests incomplete")
    got = np.concatenate([results[t] for t in sorted(reqs, key=reqs.get)])
    bad = int((got != want).sum())
    log(f"frontend: {bad} of {want.size} answers differ from closed loop")
    check(bad == 0, "frontend answers differ from the closed loop")


def device_build(g, sets, sample, ref) -> None:
    """builder="wavefront" with the compiled merge-cover kernel."""
    from repro.reach import IndexSpec, QuerySession, build
    spec = IndexSpec(k=2, variant="G", cover_method="topgap",
                     builder="wavefront", kernel_impl="auto",
                     phase2_mode="sparse")
    t0 = time.perf_counter()
    ix = build(g, spec)
    st = ix.stats
    log(f"wavefront build: {time.perf_counter() - t0:.2f} s, "
        f"merge_rounds={st.merge_rounds} host_fallbacks={st.host_fallbacks} "
        f"peak_slab_bytes={st.peak_slab_bytes} hub_nodes={st.hub_nodes}")
    check_sample("wavefront", QuerySession(ix, spec), sets, sample, ref)


def shard_devices(sess) -> set:
    slab = sess.engine._state["slab"]
    for sh in slab.addressable_shards:
        log(f"  slab shard on {sh.device}: rows {sh.index[0]}")
    return {sh.device for sh in slab.addressable_shards}


# ------------------------------------------------------------------ main --

def one_chip(g, index_dir: Path, spec, sets, sample,
             clock: CompileClock) -> None:
    sess = load_session(index_dir, spec)
    ref = host_reference(sess.index, sets, sample)
    answers = closed_loop(sess, sets, sample, ref, clock)
    frontend(sess, *sets["random"], answers["random"], N_TENANTS,
             REQUEST_SIZE)
    device_build(g, sets, sample, ref)


def placements(index_dir: Path, spec, sets, sample, chips: int) -> None:
    """The host-built index served replicated (chips x 1) and sharded
    (1 x chips); rows must land on every chip."""
    import jax
    ref = None
    for placement, mesh in (("replicated", f"{chips}x1"),
                            ("sharded", f"1x{chips}")):
        sess = load_session(index_dir, replace(spec, placement=placement,
                                               mesh=mesh))
        if ref is None:
            ref = host_reference(sess.index, sets, sample)
        log(f"{placement} over mesh {dict(sess.engine.mesh.shape)}:")
        check(shard_devices(sess) == set(jax.devices()[:chips]),
              f"{placement}: rows did not reach all {chips} chips")
        check_sample(placement, sess, sets, sample, ref)


def run(n_nodes: int, chips: int, index_dir: Path, n_queries: int,
        n_sample: int) -> None:
    t0 = time.perf_counter()
    if n_nodes < PUBLISHED_NODES:
        log(f"cut: {n_nodes} of Cit-Patents' {PUBLISHED_NODES} nodes "
            f"({LAYERS} layers and average degree {AVG_DEG} kept)")
    with CompileClock() as clock:
        g = make_graph(n_nodes)
        log(f"graph: layered_dag n={g.n} m={g.m} in "
            f"{time.perf_counter() - t0:.2f} s")
        spec = replace(host_build_saved(g, graph_meta(n_nodes), index_dir),
                       phase2_mode="sparse")
        sets, sample = query_sets(g, n_queries, n_sample)
        if chips == 1:
            one_chip(g, index_dir, spec, sets, sample, clock)
        else:
            placements(index_dir, spec, sets, sample, chips)
    log(f"compiling: {clock.seconds:.2f} s over {clock.count} compiles; "
        f"total {time.perf_counter() - t0:.2f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve the host-built index replicated (4x1) "
                         "and sharded (1x4) instead of the one-chip phases")
    ap.add_argument("--index-dir", type=Path, default=INDEX_DIR,
                    help="where the host-built index artifact is kept")
    ap.add_argument("--nodes", type=int, default=N_NODES,
                    help="graph node count (layers and average degree "
                         "stay those of Cit-Patents)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    devs = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {devs[0].device_kind} x{len(devs)}, compile cache at "
        f"{enable_compile_cache()}")
    try:
        run(args.nodes, args.chips, args.index_dir, N_QUERIES, N_SAMPLE)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
