"""Helpers the per-entry fault files share. ``bench/faults/<entry>.py``
lists in ``FAULTS`` the faults its entry's timed path can have, each a
function that plants one through pytest's ``monkeypatch``;
``bench/tests/test_faults.py`` runs every cell of ``BENCHMARK.json``
under each fault of its entry."""
import jax
import jax.numpy as jnp
import numpy as np


def wrap(mp, module, name, post=None, pre=None):
    """Replace ``module.name(topo, sched, ...)``: ``pre`` runs in its
    place, or ``post`` rewrites the FCTs it returns."""
    real = getattr(module, name)

    def wrapped(topo, sched, *a, **k):
        if pre is not None:
            return pre(real, topo, sched, *a, **k)
        st, rec = real(topo, sched, *a, **k)
        return st._replace(fct=post(st.fct)), rec

    mp.setattr(module, name, wrapped)


def double_first(fct):
    fct = np.asarray(fct).copy()
    i = np.flatnonzero(np.isfinite(fct))[0]
    fct[i] *= 2.0
    return jnp.asarray(fct)


def every_other(real, topo, sched, *a, **k):
    """The program simulates every other flow and leaves the rest out."""
    n = int(sched.start.shape[0])
    keep = np.arange(n) % 2 == 0

    def cut(x):
        x = np.asarray(x)
        return jnp.asarray(x[keep]) if x.ndim and x.shape[0] == n else x

    law, slots, lcfg = a[:3]
    half = jax.tree_util.tree_map(cut, sched)
    st, rec = real(topo, half, law, slots, jax.tree_util.tree_map(cut, lcfg),
                   *a[3:], **k)
    fct = np.full(len(keep), np.nan, np.float32)
    fct[keep] = np.asarray(st.fct)
    return st._replace(fct=jnp.asarray(fct)), rec
