"""Device-plane responses booked by the core per step of the window
(``hvd.metrics()["device_ops"]``): how many fused programs the gradient
tensors of one step became."""


def read(ctx):
    before, after = ctx.counters
    if not after or "device_ops" not in after:
        return None
    count = sum(v.get("responses", 0) for v in after["device_ops"].values()) \
        - sum(v.get("responses", 0) for v in before["device_ops"].values())
    return count / ctx.steps_in_window if count else None
