"""Share of the traced window in which a collective (all-to-all,
all-reduce, all-gather, ...) runs on a device with no other operation
beside it, averaged over the devices, in percent; nothing where the
trace holds no collective."""

from bench.lib import trace


def read(ctx):
    if ctx["window"] is None:
        return None
    s = trace.exposed_collective_share(ctx["trace"], *ctx["window"])
    return None if s is None else 100.0 * s
