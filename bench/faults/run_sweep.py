"""Faults planted under the ``run_sweep`` entry: each makes the batched
timed path (``simulate_slots_batch`` under ``run_sweep``) wrong in one
way that a later change could, in every lane, so that whichever point
the check draws carries it."""
import jax
import jax.numpy as jnp
import numpy as np

import repro.core.fluid as fluid
import repro.core.sweep as sweep

from bench.faults import common


def state_unchanged(mp):
    """The vmapped tick returns its state unchanged."""
    mp.setattr(fluid, "slot_step",
               lambda sim, st, bw_fn=None, alloc_fn=None: (st, None))


def _every_other(real, topo, sb, law, slots, lcfg, *a, **k):
    """Every other flow of each lane's schedule is left out."""
    n = int(sb.start.shape[1])
    keep = np.arange(n) % 2 == 0

    def cut(x):
        x = np.asarray(x)
        return jnp.asarray(x[:, keep]) if x.ndim > 1 and x.shape[1] == n \
            else x

    st, rec = real(topo, jax.tree_util.tree_map(cut, sb), law, slots,
                   jax.tree_util.tree_map(cut, lcfg), *a, **k)
    fct = np.full((sb.start.shape[0], n), np.nan, np.float32)
    fct[:, keep] = np.asarray(st.fct)
    return st._replace(fct=jnp.asarray(fct)), rec


def half_left_out(mp):
    """Every other flow of each scenario is left out."""
    common.wrap(mp, sweep, "simulate_slots_batch", pre=_every_other)


def answer_altered(mp):
    """One FCT of each lane is doubled where it is produced."""
    common.wrap(mp, sweep, "simulate_slots_batch",
                post=lambda fct: jnp.stack([common.double_first(f)
                                            for f in fct]))


def lanes_rotated(mp):
    """Each lane's FCTs come back in the next lane's row."""
    common.wrap(mp, sweep, "simulate_slots_batch",
                post=lambda fct: jnp.roll(fct, 1, axis=0))


FAULTS = [state_unchanged, half_left_out, answer_altered, lanes_rotated]
