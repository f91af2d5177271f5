"""Median duration of the ``finish.deliver`` span, in ms: the frontend's
host work on a slab after the session returns its answers (scatter into
each request, answer-cache insert, latency accounting, slow log).
Layer: reach.frontend."""
from harness.spans import median_ms


def read(name, info):
    return median_ms(info.get("spans", ()), "finish.deliver")
