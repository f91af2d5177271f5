"""Pairs per device slab the frontend dispatched (``FrontendStats``): how
well coalescing fills the slabs. Layer: reach.frontend."""


def read(name, info):
    fs = info["counters"].get("frontend")
    if not fs or not fs["n_batches"]:
        return None
    return fs["batch_queries"] / fs["n_batches"]
