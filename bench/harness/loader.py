"""Loading a file of the benchmark as a module of its own, by its path: how
the harness finds the traffic drivers, graph generators and per-layer
metric readers that the cells name."""
from __future__ import annotations

import importlib.util
from pathlib import Path


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
