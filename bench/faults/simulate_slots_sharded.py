"""Faults planted under the ``simulate_slots_sharded`` entry."""
import jax

import repro.core as core
import repro.core.shardslots as shardslots

from bench.faults import common


def state_unchanged(mp):
    """The sharded tick returns its carry unchanged."""
    mp.setattr(shardslots, "_shard_tick",
               lambda simw, mi, off, blk0, c, bw_fn, rec: (c, None))


def half_left_out(mp):
    """Every other flow of the schedule is left out."""
    common.wrap(mp, core, "simulate_slots_sharded", pre=common.every_other)


def exchange_left_out(mp):
    """The halo ``all_to_all`` between chips is left out."""
    mp.setattr(jax.lax, "all_to_all", lambda x, *a, **k: x)


def answer_altered(mp):
    """One FCT is doubled where it is produced."""
    common.wrap(mp, core, "simulate_slots_sharded", post=common.double_first)


FAULTS = [state_unchanged, half_left_out, exchange_left_out, answer_altered]
