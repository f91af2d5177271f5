"""A checkout-shaped directory with tiny cells of each traffic kind, for
running the harness on the CPU (JAX's first device is faked as a TPU)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(REPO / "src"), str(BENCH), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "breakdown", "check"}

TINY = {
    "config": {"name": "tiny", "graph_seed": 1,
               "generator": {"name": "layered_dag",
                             "params": {"n": 2000, "n_layers": 20,
                                        "avg_deg": 3.0}},
               "index_spec": {"k": 2, "variant": "G", "builder": "host"},
               "check_pairs": 1 << 20},
    "closed": {"kind": "closed", "batch": 256, "pool_batches": 4,
               "warm_batches": 1, "positive_share": 0.5, "max_walk": 32},
    "open": {"kind": "open", "rate": 40, "pairs_per_request": 8,
             "tenants": 4, "positive_share": 0.5, "max_walk": 32,
             "warm_requests": 8},
}


def make_root(tmp: Path) -> Path:
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY["config"]))
    for kind in ("closed", "open"):
        (tmp / "bench" / "traffic" / f"tiny_{kind}.json").write_text(
            json.dumps(TINY[kind]))
    bench = {
        "workloads": [{"name": f"tiny.{k}", "config": "tiny",
                       "traffic": f"tiny_{k}", "chips": 1, "why": "test"}
                      for k in ("closed", "open")],
        "end_to_end": [
            {"name": "qps", "unit": "queries/s", "better": "higher",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny.closed"]},
            {"name": "p50_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny.open"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "session.pad_share.qps", "unit": "%",
             "better": "lower", "source": "program_counter",
             "layer": "reach.session", "moves": "qps"},
            {"name": "frontend.queries_per_slab.p50", "unit": "queries",
             "better": "higher", "source": "program_counter",
             "layer": "reach.frontend", "moves": "p50_ms"}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


class FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def fake_chips(chips: int):
    return [FakeTPU()] * chips
