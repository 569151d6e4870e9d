"""HPCC (Li et al., SIGCOMM 2019), Algorithm 1, in the fluid form of
``powertcp.py``: INT gives each hop's queue ``q``, egress rate ``mu``
and capacity ``b`` as the sender learns them.

  MeasureInflight  u_j = q_j / (b_j T) + mu_j / b_j over the path's hops,
                   U <- (1 - d/T) U + (d/T) max_j u_j, d the time since
                   the last update clipped to T (the base RTT)
  ComputeWind      U >= eta or incStage >= maxStage:
                       W = Wc / (U / eta) + W_AI
                   else
                       W = Wc + W_AI
  NewAck           once per RTT the reference window takes W (Wc <- W),
                   and incStage resets to 0 after the first branch and
                   counts up after the second
  rate             W over the RTT (the model's w / theta)

Departures from the paper, each a choice of the fluid model:

- updates run on the model's timer (``update_period``), not per ACK; the
  once-per-RTT reference update (the paper's ``lastUpdateSeq``) is the
  first update at least one measured RTT after the last one, timed from
  0;
- ``q_j`` is the hop's queue as the telemetry reports it, not the
  smaller of two ACKs' readings, and ``mu_j`` its egress rate, not a
  difference of byte counters;
- every hop's reading is smoothed with the same ``d``, the paper's uses
  the INT interval of the most loaded hop;
- W_AI is ``beta`` = NIC rate x base RTT / expected flows (the paper:
  W_init (1 - eta) / N); U starts at 1, Wc at the line-rate window
  NIC rate x base RTT, incStage at 0; U / eta is floored at 1e-6.
"""
import jax.numpy as jnp

from bench.lib.reference import MTU, smooth


def init(fl, c, ft):
    n = fl.tau.shape
    return (jnp.ones(n, ft), (fl.nic * fl.tau).astype(ft),
            jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.float32))


def update(st, o, w, cap, upd, fl, c, t):
    u, wc, stage, last_ref = st
    tau = fl.tau.astype(w.dtype)
    u_hop = jnp.where(o["valid"],
                      o["q"] / jnp.maximum(o["b"] * tau[:, None], 1.0)
                      + o["mu"] / jnp.maximum(o["b"], 1.0), 0.0)
    u = jnp.where(upd, smooth(u, u_hop.max(axis=1), o["dt_obs"], tau), u)
    over = (u >= c["hpcc_eta"]) | (stage >= c["hpcc_max_stage"])
    w_new = jnp.where(over, wc / jnp.maximum(u / c["hpcc_eta"], 1e-6),
                      wc) + c["beta"]
    w = jnp.where(upd, jnp.maximum(w_new, MTU), w)
    ref = upd & (t - last_ref >= o["theta32"])
    stage = jnp.where(ref, jnp.where(over, 0, stage + 1), stage)
    return ((u, jnp.where(ref, w, wc), stage, jnp.where(ref, t, last_ref)),
            w, cap)
