"""Share of the whole-trace slot engine's program lookups that found a
compiled program, in percent: 1 - ``slots.program_misses`` /
``slots.program_lookups`` of the program's counters (``repro.core.obs``),
over every call the process made (set-up's warm-up job and the traced
window's); nothing where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:
        return None
    c = obs.counters()
    if not c.get("slots.program_lookups"):
        return None
    return 100.0 * (1.0 - c.get("slots.program_misses", 0)
                    / c["slots.program_lookups"])
