"""Median duration of the ``dispatch`` span, in ms: the phase-1 dispatch
of one batch in ``DeviceQueryEngine.start_answer`` (the ids' copy to the
device and one enqueue of ``jit_phase1_classify``, which looks up the
component ids itself), on ``query()``'s path and the frontend's staged one
alike. Layer: core.query_jax."""
from harness.spans import median_ms


def read(name, info):
    return median_ms(info.get("spans", ()), "dispatch")
