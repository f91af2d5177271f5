#!/usr/bin/env python3
"""Chip benchmark of the FERRARI reachability service.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell of ``BENCHMARK.json`` on the chips of the machine
it starts on. Everything is found by name: the cell's configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, the generator of its graph in
``bench/graphs/<generator.name>.py``, the driver of that mix's ``kind`` in
``bench/traffic/<kind>.py``, and the reader of each per-layer metric in
``bench/metrics/<metric>.py`` (or the file of the name without its last
dotted part, which names the end-to-end metric it moves).

The run refuses, before any work, unless JAX's first device is a TPU and
the program's kernels resolve to the compiled Pallas ones. It opens the
configuration's index (built on the first run in a checkout), warms the
shapes the traffic uses, measures for ``--seconds`` and then checks a
sample of the answers served in the window, drawn from the seed, against a
plain reachability reference. Its last line on stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end metrics,
or with ``--trace 1`` the per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``check``: each number compared, with its limit,
which also end standard error.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for _p in (str(ROOT / "src"), str(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import gen, peaks  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.index import open_session  # noqa: E402
from harness.loader import load_module  # noqa: E402
from harness.reference import Reference  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WARM_SEED = 7_340_033           # the warm-up stream: the same in every run
TRACE_SPAN_CAPACITY = 1 << 21
TRACE_SECONDS = 10.0            # a traced run's window, at most


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ by name --

def reader_path(metric: str) -> Path:
    base = metric
    while True:
        path = HERE / "metrics" / f"{base}.py"
        if path.exists():
            return path
        if "." not in base:
            raise SystemExit(f"bench: no reader for per-layer metric "
                             f"{metric!r} under bench/metrics/")
        base = base.rsplit(".", 1)[0]


def resolve(root: Path, name: str) -> dict:
    """The cell's entry and every file it names, found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = json.loads((root / "bench" / "configs"
                      / f"{cell['config']}.json").read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    driver = HERE / "traffic" / f"{traffic['kind']}.py"
    if not driver.exists():
        raise SystemExit(f"bench: no driver for traffic kind "
                         f"{traffic['kind']!r} ({driver})")
    gen.generator_path(cfg["generator"]["name"])    # refused before the chips
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "driver": driver, "end_to_end": e2e, "per_layer": layer,
            "readers": {m["name"]: reader_path(m["name"]) for m in layer}}


# ------------------------------------------------------------- device --

def require_chip(chips: int):
    """The chips the cell asks for, or exit before any work: JAX's first
    device must be a TPU, and the program's kernels the compiled Pallas
    ones that ``kernel_impl="auto"`` resolves to there."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (first device: "
                         f"{devs[0].platform}); refusing to run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    from repro.kernels import ops
    if ops.resolve_kernel_impl("auto") != "pallas":
        raise SystemExit("bench: the kernels would not compile for the TPU")
    return devs[:chips]


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# --------------------------------------------------------------- a run --

class Run:
    """What a traffic driver gets: the session, the graph, the mix's
    parameters, the seed, and the measured window."""

    def __init__(self, seed: int, seconds: float, trace: bool, found: dict,
                 session, graph):
        self.seed = seed
        # a traced window is short: traces are large and slow the host
        self.seconds = min(seconds, TRACE_SECONDS) if trace else seconds
        self.trace = trace
        self.params = found["traffic"]
        self.session = session
        self.n, self.indptr, self.indices = graph
        self.compile_times = []
        self.gc_pauses = []
        self._gc_t0 = None
        self.window_start = self.window_end = None
        self.compiles_in_window = 0
        self.trace_dir = None

    def pairs(self, q: int, seed: int):
        """``q`` query pairs of the mix, drawn from ``seed``."""
        return gen.mixed_pairs(self.n, self.indptr, self.indices, q,
                               float(self.params.get("positive_share", 0.0)),
                               seed, int(self.params.get("max_walk", 32)))

    def warm_pairs(self, q: int):
        """``q`` positive walks of a fixed warm-up stream: they reach phase
        2 and its overflow retries, whose programs compile lazily, without
        the long host searches a uniform pair can start."""
        return gen.positive_queries(self.indptr, self.indices, q, WARM_SEED,
                                    int(self.params.get("max_walk", 32)))

    def span(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def on_compile(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.compile_times.append(time.perf_counter())

    def on_gc(self, phase, info):
        """Times of the interpreter's full collections: a pause of the
        whole process, which a tail can show."""
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_pauses.append(time.perf_counter() - self._gc_t0)
            self._gc_t0 = None

    @contextlib.contextmanager
    def window(self):
        """The measured window. In a traced run the profiler and the
        program's spans are on inside it, and only inside it."""
        if self.trace:
            import jax
            from repro import obs
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            obs.get_tracer().clear()
            obs.enable_tracing(capacity=TRACE_SPAN_CAPACITY)
        gc.callbacks.append(self.on_gc)
        try:
            with self.span(tr.WINDOW_SPAN):
                self.window_start = time.perf_counter()
                yield self
                self.window_end = time.perf_counter()
        finally:
            gc.callbacks.remove(self.on_gc)
            if self.trace:
                import jax
                from repro import obs
                obs.enable_tracing(False)
                jax.profiler.stop_trace()
        self.compiles_in_window = sum(
            self.window_start <= t <= self.window_end
            for t in self.compile_times)


# -------------------------------------------------------------- check --

def sample_served(served: dict, budget: int, seed: int):
    """Pairs and answers of a sample, drawn from ``seed``, of what the
    window served: whole requests where the unit is a request (so
    each ticket's answers are checked against its own pairs), else pairs."""
    groups = served["groups"]
    rng = np.random.default_rng(seed + 3)
    if not groups:
        return (np.zeros(0, np.int64),) * 2 + (np.zeros(0, bool),)
    if served["unit"] == "request":
        per = max(1, int(np.mean([g[0].size for g in groups])))
        pick = np.sort(rng.choice(len(groups), replace=False,
                                  size=min(len(groups), max(1, budget // per))))
        chosen = [groups[i] for i in pick]
        return tuple(np.concatenate([g[j] for g in chosen]) for j in range(3))
    sizes = np.array([g[0].size for g in groups])
    ends = np.cumsum(sizes)
    idx = np.sort(rng.choice(int(ends[-1]), replace=False,
                             size=min(int(ends[-1]), budget)))
    gi = np.searchsorted(ends, idx, side="right")
    off = idx - (ends[gi] - sizes[gi])
    out = [np.empty(idx.size, np.int64), np.empty(idx.size, np.int64),
           np.empty(idx.size, bool)]
    for g in np.unique(gi):
        sel = np.flatnonzero(gi == g)
        for j in range(3):
            out[j][sel] = groups[g][j][off[sel]]
    return tuple(out)


def check_answers(served: dict, graph_edges, n: int, budget: int,
                  seed: int) -> dict:
    s, t, got = sample_served(served, budget, seed)
    t0 = time.perf_counter()
    want = Reference(n, *graph_edges).reachable(s, t)
    log(f"check: {s.size} pairs ({int(want.sum())} reachable) against the "
        f"reference in {time.perf_counter() - t0:.3f} s")
    return {"wrong_answers": {"value": int((got != want).sum()),
                              "limit": 0},
            "unanswered": {"value": int(served.get("unanswered", 0)),
                           "limit": 0}}


# ------------------------------------------------------------- layers --

def layer_metrics(found: dict, info: dict) -> dict:
    out = {}
    for m in found["per_layer"]:
        value = load_module(found["readers"][m["name"]]).read(m["name"], info)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def reduce_trace(trace_dir: str) -> dict:
    device, host = tr.read_xplane(tr.find_xplane(trace_dir),
                                  host_names=tr.HOST_SPANS)
    return tr.reduce_trace(device, host)


# --------------------------------------------------------------- main --

def prepare(workload: str, *, root: Path = ROOT, devices=require_chip,
            compile_cache: bool = True) -> dict:
    """Everything a run needs before its traffic: the cell's files, the
    chips, the graph and a session on the configuration's index."""
    found = resolve(Path(root), workload)
    devs = devices(int(found["cell"]["chips"]))
    env = {"found": found, "devs": devs,
           "peaks": peaks.peaks_for(devs[0].device_kind)}
    import jax
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        # every program, however quick to compile, comes from the cache in
        # a later run, so that set-up does the same work in each
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device: {devs[0].device_kind} x{len(devs)}")
    cfg = found["config"]
    t0 = time.perf_counter()
    n, src, dst = gen.make_graph(cfg)
    indptr, indices = gen.csr(n, src, dst)
    log(f"graph {cfg['name']}: {n} nodes, {src.size} edges in "
        f"{time.perf_counter() - t0:.3f} s")
    env["graph"] = (n, src, dst, indptr, indices)
    env["session"], env["built"] = open_session(
        Path(root) / "bench" / ".cache", cfg, n, indptr, indices, log)
    # the rest of set-up is the traffic and the warm-up
    log(f"index open {time.perf_counter() - T_PROCESS:.3f} s after the "
        f"process started")
    return env


def execute(env: dict, seed: int, seconds: float, trace: bool,
            t_process: float):
    """One measured window and its check: (the result line, what the
    traffic driver returned)."""
    import jax
    found = env["found"]
    n, src, dst, indptr, indices = env["graph"]
    run = Run(seed, seconds, trace, found, env["session"], (n, indptr, indices))
    jax.monitoring.register_event_duration_secs_listener(run.on_compile)
    try:
        served = load_module(found["driver"]).run(run)
    finally:
        jax.monitoring.unregister_event_duration_listener(run.on_compile)
    setup_s = run.window_start - t_process
    log(f"set-up {setup_s:.3f} s; window "
        f"{run.window_end - run.window_start:.3f} s; "
        f"{run.compiles_in_window} compiles inside the window; "
        f"{len(run.gc_pauses)} full garbage collections inside it, "
        f"longest {max(run.gc_pauses, default=0.0) * 1e3:.3f} ms, "
        f"{sum(run.gc_pauses) * 1e3:.3f} ms in all")
    devs = env["devs"]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}
    result = {"correct": None, "attempted": int(served["attempted"]),
              "failed": int(served["failed"])}
    if trace:
        from repro import obs
        info = {"counters": served.get("counters", {}),
                "window_s": run.window_end - run.window_start,
                "peaks": env["peaks"],
                "spans": obs.get_tracer().events()}
        t0 = time.perf_counter()
        info["trace"] = reduce_trace(run.trace_dir)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t0:.3f} s")
        for k in info["trace"]["kernels"]:
            log(f"kernel {k['module']}/{k['op']}: {k['calls']} calls, "
                f"{k['seconds']} s; bytes a call (HBM, VMEM read, VMEM "
                f"write): {k['traffic_per_call']}")
        result["metrics"] = layer_metrics(found, info)
        device["busy_s"] = info["trace"]["busy_s"]
        device["window_s"] = info["trace"]["window_s"]
        result["device"] = device
        result["breakdown"] = {"device_ops": info["trace"]["device_ops"],
                               "idle_gaps": info["trace"]["idle_gaps"]}
    else:
        values = dict(served["end_to_end"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                         "unit": m["unit"]}
                             for m in found["end_to_end"]}
        result["device"] = device
    check = check_answers(served, (src, dst), n,
                          int(found["config"]["check_pairs"]), seed)
    result["correct"] = all(c["value"] <= c["limit"] for c in check.values())
    result["check"] = check
    return result, served


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: Path = ROOT, devices=require_chip,
         compile_cache: bool = True) -> int:
    args = parse(argv)
    env = prepare(args.workload, root=root, devices=devices,
                  compile_cache=compile_cache)
    result, _ = execute(env, args.seed, args.seconds, bool(args.trace),
                        T_PROCESS)
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
