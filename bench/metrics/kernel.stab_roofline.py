"""Share of its roofline that the phase-1 ``interval_stab`` kernel reaches,
in percent: the least time its calls could take (``kernels.least_seconds``:
the bytes each call's shapes move, from HBM or on-chip VMEM as their
layouts say, at the chip's peaks) over the device time of those calls. It
compares integers on the vector unit and uses no matrix unit, so bandwidth
is its bound. Layer: kernels."""
from harness.kernels import least_seconds


def read(name, info):
    tr = info.get("trace")
    calls = [k for k in (tr or {}).get("kernels", [])
             if k["op"].startswith("interval_stab") and k["traffic_per_call"]]
    secs = sum(k["seconds"] for k in calls)
    if not secs:
        return None
    least = sum(least_seconds(k["traffic_per_call"], info["peaks"])
                * k["calls"] for k in calls)
    return 100.0 * least / secs
