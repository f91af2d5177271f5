"""Share of the traced window in which no operation ran on the device, in
percent: 1 minus the union of the device's op intervals over the window.
Layer: device."""


def read(name, info):
    tr = info.get("trace")
    if not tr or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
