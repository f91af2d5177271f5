"""Per-layer numbers read from the program's own spans: ``info["spans"]``,
the ``repro.obs`` events a traced window recorded, each with its ``name``
and its ``dur`` in seconds."""
from __future__ import annotations

import statistics


def median_ms(spans, name: str):
    """Median duration, in ms, of the spans called ``name``; None where
    the window holds none."""
    durs = [e["dur"] for e in spans if e["name"] == name]
    return statistics.median(durs) * 1e3 if durs else None
