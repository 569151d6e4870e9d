"""Share of the sharded ticks that took the full-gather fallback instead
of the halo exchange, in percent: the program's counters
``halo.fallback_ticks`` over ``slots.ticks`` (``repro.core.obs``), over
every call the process made (set-up's warm-up job and the traced
window's); nothing where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:
        return None
    c = obs.counters()
    if "halo.fallback_ticks" not in c or not c.get("slots.ticks"):
        return None
    return 100.0 * c["halo.fallback_ticks"] / c["slots.ticks"]
