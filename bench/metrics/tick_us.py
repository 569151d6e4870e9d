"""Device busy time of the busiest device per tick stepped, in us."""


def read(ctx):
    if ctx.get("busiest") is None or not ctx["device_ticks"]:
        return None
    return 1e6 * ctx["busiest_busy_s"] / ctx["device_ticks"]
