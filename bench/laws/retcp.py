"""reTCP (Mukerjee et al., NSDI 2020) on a reconfigurable fabric, in the
fluid form of ``powertcp.py``: NewReno sets a base window, and a circuit
switch tells the sender when its circuit is up.

  NewReno     on loss, at most once per measured RTT, the base window
              halves; else it grows by one MTU an update
  circuit     while the flow's scheduled hop is in its day, or will be
              within ``retcp_prebuffer`` seconds, the window is the
              base window times that hop's day over night capacity
              (``bw / bw_lo``); else the base window

The circuit's state comes from the run's ``Links`` (``constants["links"]``),
each hop at its own phase, evaluated at ``t`` and ``t + retcp_prebuffer``
by ``reference.circuit_up``. The multiplier is applied every tick, not
only on update ticks: the switch's notice arrives out of band, not with
the ACKs.

Departures from the paper, each a choice of the fluid model:

- updates run on the model's timer (``update_period``), not per ACK;
- loss is a hop's reported queue at 95% of its port buffer or more
  (the fluid model drops nothing; a queue that full is where a switch
  would);
- the base window starts at NIC rate x base RTT, and the last cut at 0.
"""
import jax.numpy as jnp

from bench.lib.reference import MTU, circuit_up

MD = 0.5                    # NewReno's cut on loss
FULL = 0.95                 # of the port buffer: a loss


def init(fl, c, ft):
    return (jnp.zeros(fl.tau.shape, jnp.float32),
            (fl.nic * fl.tau).astype(ft))


def _at_hops(x, path, pad):
    """``x`` [Q] at each hop of each flow, ``pad`` past the path's end."""
    return jnp.concatenate([x, jnp.full((1,), pad, x.dtype)])[path]


def update(st, o, w, cap, upd, fl, c, t):
    last_cut, wb = st
    L = c["links"]
    full = (o["q"] >= FULL * _at_hops(L.buf, fl.path, jnp.inf)).any(axis=1)
    cut = upd & full & (t - last_cut > o["theta32"])
    wb = jnp.maximum(jnp.where(cut, wb * MD, jnp.where(upd, wb + MTU, wb)),
                     MTU)
    on = circuit_up(t, L) | circuit_up(t + c["retcp_prebuffer"], L)
    ratio = jnp.where(_at_hops(on, fl.path, False),
                      _at_hops(L.bw / L.bw_lo, fl.path, 1.0), 1.0).max(axis=1)
    return (jnp.where(cut, t, last_cut), wb), wb * ratio.astype(wb.dtype), cap
