"""Share of the slots dispatched that were padding (``SessionStats``:
``n_padded`` over real plus padded queries), in percent. Layer:
reach.session's power-of-two buckets."""


def read(name, info):
    st = info["counters"]["session"]
    slots = st["n_queries"] + st["n_padded"]
    return None if not slots else 100.0 * st["n_padded"] / slots
