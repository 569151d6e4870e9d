"""Device operations the busiest device ran inside the traced window per
tick it stepped."""


def read(ctx):
    if ctx.get("busiest") is None or not ctx["device_ticks"]:
        return None
    return ctx["busiest_ops"] / ctx["device_ticks"]
