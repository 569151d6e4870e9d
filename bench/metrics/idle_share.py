"""Share of the traced window in which the busiest device ran no
operation: 1 - (union of its op intervals) / window, in percent."""


def read(ctx):
    if ctx.get("busiest") is None:
        return None
    return 100.0 * (1.0 - ctx["busiest_busy_s"] / ctx["window_s"])
