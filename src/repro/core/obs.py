"""Host spans and counters of the slot engines' driver loops.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: a host span on the profiler's own clock, beside the
device trace, while a profile is recorded, and nothing otherwise. The
``args`` become the span's stats. Device-side phases are marked with
``jax.named_scope`` in the tick bodies instead (HLO metadata only).

``count(name, n)`` adds ``n`` to a process-wide total. ``n`` may be a
device scalar: it is kept as is and summed only when ``counters()`` is
read, so counting never waits on the device. ``counters()`` returns the
totals as Python ints, ``reset()`` clears them.

Spans and counters (PERF.md lists the metric each is for):

  repro.schedule.route   FabricRoutes.make_flows (``flows``)
  repro.schedule.build   network.make_schedule (``flows``)
  repro.slots.prepare    an entry call up to its first program call
  repro.slots.call       each call of a jitted program (``program``,
                         ``ticks``)
  repro.chunk.sync       the chunk loop's cursor fetch
  repro.chunk.window     the chunk loop's segment length and window
  repro.slots.finish     records, FCT merge and state fix-up after the loop

  slots.calls            entry calls (simulate_slots, simulate_slots_sharded)
  slots.ticks            ticks stepped
  slots.program_lookups  lookups of the whole-trace slot program cache
  slots.program_misses   of them, those that built a new program
  chunk.segments         segment programs called by a chunk loop
  halo.fallback_ticks    sharded ticks that took the full-gather fallback
"""
from __future__ import annotations

import threading

import jax

_lock = threading.Lock()
_ints: dict = {}
_lazy: dict = {}          # name -> device scalars not yet summed
_FOLD = 64                # pending device scalars folded on the device


def span(name: str, **args):
    return jax.profiler.TraceAnnotation("repro." + name, **args)


def count(name: str, n=1) -> None:
    with _lock:
        if isinstance(n, int):
            _ints[name] = _ints.get(name, 0) + n
            return
        pend = _lazy.setdefault(name, [])
        pend.append(n)
        if len(pend) >= _FOLD:              # an async add, no host sync
            _lazy[name] = [sum(pend[1:], pend[0])]


def counters() -> dict:
    """Every total as a Python int (device scalars are fetched here)."""
    with _lock:
        out = dict(_ints)
        for name, pend in _lazy.items():
            out[name] = out.get(name, 0) + sum(int(x) for x in pend)
    return out


def reset() -> None:
    with _lock:
        _ints.clear()
        _lazy.clear()
