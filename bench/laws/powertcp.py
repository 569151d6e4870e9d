"""PowerTCP (Addanki et al., NSDI 2022), Algorithm 1 with INT: the
normalized power at the most loaded hop, smoothed over one base RTT,
sets the window multiplicatively."""
import jax.numpy as jnp

from bench.lib.reference import MTU, smooth


def init(fl, c, ft):
    return (jnp.ones(fl.tau.shape, ft),)


def update(st, o, w, cap, upd, fl, c, t):
    (gs,) = st
    tau = fl.tau[:, None].astype(w.dtype)
    bdp = o["b"] * tau
    power = (o["qdot"] + o["mu"]) * (o["q"] + bdp)
    norm = jnp.where(o["valid"], power / jnp.maximum(bdp * o["b"], 1.0), 0.0)
    gs = jnp.where(upd, smooth(gs, norm.max(axis=1), o["dt_obs"],
                               fl.tau.astype(w.dtype)), gs)
    target = o["w_old"] / jnp.maximum(gs, 1e-9) + c["beta"]
    w_new = c["gamma"] * target + (1.0 - c["gamma"]) * w
    return (gs,), jnp.where(upd, jnp.maximum(w_new, MTU), w), cap
