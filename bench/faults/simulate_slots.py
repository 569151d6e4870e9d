"""Faults planted under the ``simulate_slots`` entry: each makes the
timed path wrong in one way that a later change could."""
import repro.core as core
import repro.core.fluid as fluid

from bench.faults import common


def state_unchanged(mp):
    """The tick returns its state unchanged."""
    mp.setattr(fluid, "slot_step",
               lambda sim, st, bw_fn=None, alloc_fn=None: (st, None))


def half_left_out(mp):
    """Every other flow of the schedule is left out."""
    common.wrap(mp, core, "simulate_slots", pre=common.every_other)


def answer_altered(mp):
    """One FCT is doubled where it is produced."""
    common.wrap(mp, core, "simulate_slots", post=common.double_first)


FAULTS = [state_unchanged, half_left_out, answer_altered]
