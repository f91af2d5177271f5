"""Median duration of the ``queue_wait`` span, in ms: from a request's
admission in ``Frontend.submit`` to the cut of the slab that carries it,
the coalescing delay apart from service. Layer: reach.frontend."""
from harness.spans import median_ms


def read(name, info):
    return median_ms(info.get("spans", ()), "queue_wait")
