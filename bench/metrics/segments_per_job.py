"""Segment programs the chunk loop called per entry call: the program's
counters ``chunk.segments`` over ``slots.calls`` (``repro.core.obs``),
over every call the process made (set-up's warm-up job and the traced
window's); nothing where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:
        return None
    c = obs.counters()
    if not c.get("slots.calls"):
        return None
    return c.get("chunk.segments", 0) / c["slots.calls"]
