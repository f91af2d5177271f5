"""Markdown report generator for EXPERIMENTS.md §Dry-run / §Roofline,
plus the cross-benchmark trend report over ``BENCH_*.json`` artifacts.

    PYTHONPATH=src python -m benchmarks.report [--mesh single|multi]
    PYTHONPATH=src python -m benchmarks.report --table bench \\
        [--bench-dir .] [--json trend.json]

The bench table aggregates every BENCH_*.json the emitters produce
(query/build/serve/dynamic/distributed) into one markdown summary —
per-dataset ns/query, build seconds, kernel roofline ratios, serving
occupancy — and fails soft: a missing or unparsable artifact becomes a
"missing" row, never a crash, so the report works at any point of a
partially-run benchmark sweep.
"""
from __future__ import annotations

import argparse
import json
import os

from .roofline import load_cells, roofline_terms

#: artifact name -> short bench id (mirrors each emitter's default --json)
BENCH_FILES = {
    "BENCH_query.json": "query",
    "BENCH_build.json": "build",
    "BENCH_serve.json": "serve",
    "BENCH_dynamic.json": "dynamic",
    "BENCH_distributed.json": "distributed",
}


def dryrun_table(mesh: str) -> str:
    rows = []
    for r in sorted(load_cells(mesh), key=lambda r: (r["arch"], r["shape"])):
        if not r.get("ok"):
            rows.append(f"| {r['arch']} | {r['shape']} | FAIL | | | | |")
            continue
        c = r["collectives"]
        sched = " ".join(f"{k}x{v['count']}" for k, v in c.items()
                         if isinstance(v, dict) and v["count"])
        rows.append(
            f"| {r['arch']} | {r['shape']} "
            f"| {r['memory']['peak_bytes'] / 2**30:.2f} "
            f"| {r['flops']:.3g} | {r['bytes_accessed']:.3g} "
            f"| {c['total_bytes']:.3g} | {sched} |")
    hdr = ("| arch | shape | peak GiB/dev | HLO FLOPs | HLO bytes "
           "| coll bytes | collective schedule |\n|---|---|---|---|---|---|---|")
    return hdr + "\n" + "\n".join(rows)


def roofline_table(mesh: str, full: bool = True) -> str:
    rows = [roofline_terms(r) for r in load_cells(mesh) if r.get("ok")]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    if full:
        hdr = ("| arch | shape | kind | compute (s) | memory (s) "
               "| collective (s) | dominant | MODEL_FLOPS | useful "
               "| roofline | peak GiB |\n" + "|---" * 11 + "|")
        lines = [hdr]
        for r in rows:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['kind']} "
                f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
                f"| {r['collective_s']:.3e} | **{r['dominant']}** "
                f"| {r['model_flops']:.3g} | {r['useful_frac']:.3f} "
                f"| {r['roofline_frac']:.4f} | {r['peak_gib']:.2f} |")
    else:
        hdr = ("| arch | shape | compute (s) | memory (s) | collective (s) "
               "| dominant | peak GiB |\n" + "|---" * 7 + "|")
        lines = [hdr]
        for r in rows:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} "
                f"| {r['memory_s']:.3e} | {r['collective_s']:.3e} "
                f"| {r['dominant']} | {r['peak_gib']:.2f} |")
    return "\n".join(lines)


def load_bench_artifacts(bench_dir: str = "."):
    """{short_name: {"data": dict|None, "error": str|None, "path": str}}.
    Never raises — missing/corrupt artifacts are recorded, not fatal."""
    out = {}
    for fname, short in BENCH_FILES.items():
        path = os.path.join(bench_dir, fname)
        rec = {"path": path, "data": None, "error": None}
        try:
            with open(path) as f:
                rec["data"] = json.load(f)
        except FileNotFoundError:
            rec["error"] = "missing"
        except (OSError, json.JSONDecodeError) as e:
            rec["error"] = f"unreadable: {e}"
        else:
            try:
                from ._bench_schema import validate
                validate(rec["data"], path=path)
            except ValueError as e:
                # pre-envelope artifact: still report, but flag the drift
                rec["error"] = f"schema: {e}"
        out[short] = rec
    return out


def bench_trend(bench_dir: str = "."):
    """Distill the artifact set into one flat trend dict (JSON-ready)."""
    arts = load_bench_artifacts(bench_dir)
    trend = {"artifacts": {}, "query": {}, "build": {}, "serve": {},
             "dynamic": {}, "kernels": {}}
    for short, rec in arts.items():
        trend["artifacts"][short] = {
            "present": rec["data"] is not None,
            "error": rec["error"],
            "timestamp": (rec["data"] or {}).get("timestamp"),
            "device_kind": (rec["data"] or {}).get("device_kind"),
        }
    q = (arts["query"]["data"] or {})
    for name, e in q.get("datasets", {}).items():
        trend["query"][name] = {
            "build_seconds": e.get("build_seconds"),
            "random_ns_per_query": e.get("random", {}).get("ns_per_query"),
            "positive_ns_per_query": e.get("positive", {}).get("ns_per_query"),
            "index_bytes": e.get("index_bytes"),
        }
    for group, recs in q.get("kernels", {}).items():
        if not isinstance(recs, dict):
            continue
        trend["kernels"][group] = {
            impl: r.get("roofline_frac")
            for impl, r in recs.items()
            if isinstance(r, dict) and "roofline_frac" in r}
    b = (arts["build"]["data"] or {})
    for name, e in b.get("datasets", {}).items():
        trend["build"][name] = {
            "host_seconds": e.get("host_build_seconds"),
            "device_seconds": e.get("device_build_seconds"),
            "device_over_host": e.get("device_over_host_ratio"),
        }
    s = (arts["serve"]["data"] or {})
    if s:
        co = s.get("open_loop", {}).get("coalesced", {})
        trend["serve"] = {
            "dataset": s.get("dataset"),
            "closed_ns_per_query": s.get("closed_loop", {}).get("ns_per_query"),
            "open_ns_per_query": co.get("ns_per_query"),
            "occupancy": co.get("occupancy"),
            "deadline_misses": co.get("deadline_misses"),
            "cache_ns_per_query": s.get("cache", {}).get("ns_per_query"),
        }
    dy = (arts["dynamic"]["data"] or {})
    for name, e in dy.get("datasets", {}).items():
        trend["dynamic"][name] = {
            k: v for k, v in e.items()
            if isinstance(v, (int, float)) and "ns_per_query" in k}
    return trend


def _fmt(v, spec=".0f"):
    return "—" if v is None else format(v, spec)


def bench_table(bench_dir: str = ".") -> str:
    """One markdown trend report over every BENCH_*.json present."""
    t = bench_trend(bench_dir)
    lines = ["## Benchmark trend report", "", "### Artifacts", "",
             "| bench | status | timestamp | device |", "|---|---|---|---|"]
    for short, a in t["artifacts"].items():
        status = "ok" if (a["present"] and not a["error"]) else \
                 (a["error"] or "missing")
        lines.append(f"| {short} | {status} | {a['timestamp'] or '—'} "
                     f"| {a['device_kind'] or '—'} |")
    if t["query"]:
        lines += ["", "### Query serving (closed loop)", "",
                  "| dataset | build (s) | random ns/q | positive ns/q "
                  "| index bytes |", "|---|---|---|---|---|"]
        for name, e in sorted(t["query"].items()):
            lines.append(
                f"| {name} | {_fmt(e['build_seconds'], '.3f')} "
                f"| {_fmt(e['random_ns_per_query'])} "
                f"| {_fmt(e['positive_ns_per_query'])} "
                f"| {_fmt(e['index_bytes'], ',.0f')} |")
    if t["build"]:
        lines += ["", "### Device build pipeline", "",
                  "| dataset | host (s) | device (s) | device/host |",
                  "|---|---|---|---|"]
        for name, e in sorted(t["build"].items()):
            lines.append(f"| {name} | {_fmt(e['host_seconds'], '.3f')} "
                         f"| {_fmt(e['device_seconds'], '.3f')} "
                         f"| {_fmt(e['device_over_host'], '.2f')} |")
    if t["kernels"]:
        lines += ["", "### Kernel roofline fractions", "",
                  "| kernel | impl | roofline frac |", "|---|---|---|"]
        for group, impls in sorted(t["kernels"].items()):
            for impl, frac in sorted(impls.items()):
                lines.append(f"| {group} | {impl} | {_fmt(frac, '.3e')} |")
    if t["serve"]:
        s = t["serve"]
        lines += ["", "### Serving frontend "
                  f"(dataset: {s.get('dataset') or '—'})", "",
                  "| metric | value |", "|---|---|",
                  f"| closed-loop ns/query | {_fmt(s['closed_ns_per_query'])} |",
                  f"| open-loop ns/query | {_fmt(s['open_ns_per_query'])} |",
                  f"| occupancy | {_fmt(s['occupancy'], '.3f')} |",
                  f"| deadline misses | {_fmt(s['deadline_misses'], '.0f')} |",
                  f"| cache-hot ns/query | {_fmt(s['cache_ns_per_query'])} |"]
    if t["dynamic"]:
        lines += ["", "### Dynamic updates", "",
                  "| dataset | metric | ns/query |", "|---|---|---|"]
        for name, e in sorted(t["dynamic"].items()):
            for k, v in sorted(e.items()):
                lines.append(f"| {name} | {k} | {_fmt(v)} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--table", choices=["dryrun", "roofline", "bench"],
                    default="roofline")
    ap.add_argument("--bench-dir", default=".",
                    help="directory holding BENCH_*.json artifacts")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="with --table bench: also write the trend dict "
                         "as JSON here")
    args = ap.parse_args()
    if args.table == "dryrun":
        print(dryrun_table(args.mesh))
    elif args.table == "bench":
        print(bench_table(args.bench_dir))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(bench_trend(args.bench_dir), f, indent=1)
            print(f"\nwrote {args.json}")
    else:
        print(roofline_table(args.mesh, full=(args.mesh == "single")))


if __name__ == "__main__":
    main()
