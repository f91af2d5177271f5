"""Reduction of a profiler trace to the numbers the benchmark reports.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes: device planes
(``/device:TPU:<i>``) whose op line holds one event per device operation,
and host planes whose threads hold the ``TraceAnnotation`` spans of the
program (``stage``, ``phase1``, ``phase2``, ...) and of the benchmark
(``bench.*``). Everything here works on plain tuples
``(name, start_ns, end_ns)``, so it can be checked on a synthetic trace.

* busy: the union of a device's op intervals inside the window, averaged
  over the devices that ran anything; idle share is 1 - busy / window.
* ops: seconds of each device operation, by module and op name; a loop op
  (while, conditional, call) is left out, as its body's ops are counted.
* kernels: the custom calls (Pallas kernels) with their bytes per call.
* idle gaps: every stretch of the window in which the device ran nothing,
  cut where host spans open or close, each piece named by the innermost
  host span open over it (``untracked`` where none is), summed by name.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

from . import kernels

WINDOW_SPAN = "bench.window"
# host spans kept from the trace: the benchmark's own (bench.*) and the
# program's serving spans (phase2.host_fallback is kept under phase2)
HOST_SPANS = ("bench", "stage", "dispatch", "finish", "phase1", "phase2",
              "coalesce", "cache_probe")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str, host_names=None):
    """({device plane: {"ops": [...], "modules": [...]}}, host spans), each
    event a tuple (name, start_ns, end_ns). Host spans are kept when
    ``host_names`` is None or holds their name or their name's prefix
    before the first dot."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                device[plane.name] = {
                    key: [(e.name, e.start_ns, e.end_ns)
                          for e in lines[name].events]
                    if name in lines else []
                    for key, name in (("ops", OPS_LINE),
                                      ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if (host_names is None or e.name in host_names
                            or e.name.split(".")[0] in host_names):
                        host.append((e.name, e.start_ns, e.end_ns))
    return device, host


def window_of(host) -> tuple:
    """(start, end) ns of the benchmark's measured-window span."""
    spans = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def merge(intervals, lo, hi) -> list:
    """Sorted, disjoint union of ``intervals`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy, lo, hi) -> list:
    """The stretches of [lo, hi] that the sorted, disjoint ``busy`` leaves
    free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def name_gaps(free, host) -> dict:
    """Seconds of the ``free`` stretches by the innermost host span open
    over each piece (the one that opened last). Stretches of several
    devices that overlap each count."""
    points = []                       # (time, order, kind, span index)
    for i, (_, s, e) in enumerate(host):
        points.append((s, 1, "open", i))
        points.append((e, 0, "close", i))
    for j, (s, e) in enumerate(free):
        points.append((s, 2, "gap_open", j))
        points.append((e, 0, "gap_close", j))
    points.sort()
    open_spans = {}                   # span index -> start, insertion order
    in_gap = 0
    t_prev = None
    out = defaultdict(float)
    for t, _, kind, i in points:
        if in_gap and t_prev is not None and t > t_prev:
            name = (host[max(open_spans, key=open_spans.get)][0]
                    if open_spans else "untracked")
            out[name] += (t - t_prev) * 1e-9 * in_gap
        t_prev = t
        if kind == "open":
            open_spans[i] = (host[i][1], i)
        elif kind == "close":
            open_spans.pop(i, None)
        elif kind == "gap_open":
            in_gap += 1
        else:
            in_gap -= 1
    return dict(out)


def modules_of(ops, modules) -> list:
    """The name, without its hash, of the module each op ran in."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for _, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        out.append(mods[i][0].split("(", 1)[0]
                   if i >= 0 and s < mods[i][2] else "")
    return out


def reduce_trace(device: dict, host: list, top: int = 10) -> dict:
    """The numbers of one traced window (see the module docstring).
    ``kernels`` lists every custom call (a Pallas kernel) by module, op
    and traffic per call (``kernels.call_traffic``), with its seconds and
    calls."""
    lo, hi = window_of(host)
    window_s = (hi - lo) * 1e-9
    ops_s = defaultdict(float)
    kern = defaultdict(lambda: [0.0, 0])
    busy_s, free_all = [], []
    for plane in sorted(device):
        ops = [o for o in device[plane]["ops"] if o[2] > lo and o[1] < hi]
        if not ops:
            continue
        for (name, s, e), mod in zip(ops, modules_of(
                ops, device[plane]["modules"])):
            if kernels.op_kind(name) in kernels.CONTAINERS:
                continue            # a loop's time is its body's ops'
            secs = (min(e, hi) - max(s, lo)) * 1e-9
            ops_s[f"{mod}/{kernels.op_name(name)}"] += secs
            if kernels.is_custom_call(name):
                k = kern[(mod, kernels.op_name(name),
                          kernels.call_traffic(name))]
                k[0] += secs
                k[1] += 1
        busy = merge([(s, e) for _, s, e in ops], lo, hi)
        busy_s.append(sum(e - s for s, e in busy) * 1e-9)
        free_all.extend(gaps(busy, lo, hi))
    named = name_gaps(free_all, [h for h in host if h[0] != WINDOW_SPAN])
    if busy_s:
        named = {k: v / len(busy_s) for k, v in named.items()}
    by_time = sorted(ops_s.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_s,
        "busy_s": sum(busy_s) / len(busy_s) if busy_s else 0.0,
        "devices": len(busy_s),
        "ops_s": dict(ops_s),
        "kernels": [{"module": m, "op": o, "traffic_per_call": b,
                     "seconds": v[0], "calls": v[1]}
                    for (m, o, b), v in kern.items()],
        "device_ops": [[k, v] for k, v in by_time[:top]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(named.items(), key=lambda kv: -kv[1])[:top]],
    }
