"""TIMELY (Mittal et al., SIGCOMM 2015), Algorithm 1, in the fluid form
of ``powertcp.py``: the sender sees the measured RTT ``theta`` and sets
its rate.

  gradient   (theta - previous theta) / minRTT, minRTT the base RTT
  theta < T_low         rate <- rate + delta
  theta > T_high        rate <- rate (1 - beta (1 - T_high / theta))
  gradient <= 0         rate <- rate + N delta  (N = hai_n after hai_n
                                                 updates in a row with a
                                                 gradient <= 0, else 1)
  else                  rate <- rate (1 - beta gradient)

The rate caps the flow's sending rate, and the window follows
rate x theta, so that the model's w / theta gives the same rate.

Departures from the paper, each a choice of the fluid model:

- updates run on the model's timer (``update_period``), not per
  completion event; the fluid RTT carries no noise, so the RTT
  difference is not smoothed (the paper's EWMA with alpha = 1);
- T_low = 1.5 and T_high = 3 base RTTs, delta = NIC rate / 100 (the
  paper's 50 us, 500 us and 10 Mbit/s were set for its 10 Gbit/s
  testbed);
- the hyper-additive step N delta (HAI) also applies below T_low;
- one update cuts the rate by at most half, and the rate stays within
  0.001 and 1 times the NIC rate;
- the rate starts at the NIC rate and the previous RTT at the base RTT.
"""
import jax.numpy as jnp

from bench.lib.reference import MTU

T_LOW, T_HIGH = 1.5, 3.0        # base RTTs
DELTA = 0.01                    # of the NIC rate


def init(fl, c, ft):
    return (fl.nic.astype(ft), fl.tau.astype(ft),
            jnp.zeros(fl.tau.shape, jnp.int32))


def update(st, o, w, cap, upd, fl, c, t):
    rate, prev, neg = st
    ft = w.dtype
    tau, nic, theta = fl.tau.astype(ft), fl.nic.astype(ft), o["theta"]
    t_high = T_HIGH * tau
    grad = (theta - prev) / tau
    neg_new = jnp.where(grad <= 0, neg + 1, 0)
    step = jnp.where(neg_new >= c["timely_hai_n"], c["timely_hai_n"], 1) \
        * (DELTA * nic)
    beta = c["timely_beta"]
    r = jnp.where(
        theta < T_LOW * tau, rate + step,
        jnp.where(theta > t_high, rate * (1.0 - beta * (1.0 - t_high / theta)),
                  jnp.where(grad <= 0, rate + step,
                            rate * jnp.maximum(1.0 - beta * grad, 0.5))))
    r = jnp.clip(r, 0.001 * nic, nic).astype(ft)
    rate = jnp.where(upd, r, rate)
    w = jnp.where(upd, jnp.maximum(rate * theta, MTU), w)
    return ((rate, jnp.where(upd, theta, prev),
             jnp.where(upd, neg_new, neg)), w, rate)
