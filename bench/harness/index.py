"""The configuration's index: built once per checkout, then loaded.

The first run of a configuration builds the index with the program's own
builder and saves it under ``bench/.cache/<config>/`` through
``reach.save_index``. Every later run opens it with ``QuerySession.load``,
the path a serving deployment takes on restart. The artifact records the
graph it was built from, and one built from other data is rebuilt.
"""
from __future__ import annotations

import shutil
import time
from pathlib import Path


def graph_identity(cfg: dict) -> dict:
    return {"generator": cfg["generator"], "graph_seed": cfg["graph_seed"],
            "index_spec": cfg["index_spec"]}


def open_session(cache_dir: Path, cfg: dict, n: int, indptr, indices, log):
    """(QuerySession, built_here) for the configuration's index."""
    from repro.graphs.csr import CSR
    from repro.reach import (IndexSpec, QuerySession, build, load_manifest,
                             save_index)

    spec = IndexSpec(**cfg["index_spec"])
    path = Path(cache_dir) / cfg["name"]
    ident = graph_identity(cfg)
    built = False
    try:
        saved = load_manifest(path)["extra"].get("user_meta", {})
    except FileNotFoundError:
        saved = None
    if saved is None or saved.get("graph") != ident:
        shutil.rmtree(path, ignore_errors=True)
        t0 = time.perf_counter()
        ix = build(CSR(n=n, indptr=indptr, indices=indices), spec)
        log(f"index built in {time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        save_index(path, ix, spec, meta={"graph": ident})
        log(f"index saved to {path} in {time.perf_counter() - t0:.3f} s")
        del ix
        built = True
    t0 = time.perf_counter()
    sess = QuerySession.load(path, spec)
    log(f"index loaded in {time.perf_counter() - t0:.3f} s")
    return sess, built
