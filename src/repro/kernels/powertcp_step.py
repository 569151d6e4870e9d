"""Fused PowerTCP control-law kernel (Algorithm 1, vectorized over flows).

This is the paper's per-ACK hot path — NORMPOWER (per-hop power, max over
the path), EWMA smoothing, and UPDATEWINDOW — fused into one VMEM-resident
pass over a tile of flows. Deployed at fleet scale the law runs once per
ACK per flow (millions/s/host); in our simulator it runs F x steps times.
One kernel invocation = one simulator tick for a [BF] tile of flows with
all H path hops resident.

Hardware adaptation (DESIGN.md section 2): the paper's implementation
targets a NIC / P4 switch pipeline; on TPU the natural mapping is a wide VPU
tile over flows. Flows are laid out as 2-D ``[rows, 128]`` tiles (row
counts a multiple of 8, so every block is whole (8, 128) f32 vregs and
matches the layout XLA gives the operand) and per-hop metadata as
``[H, rows, 128]``, so the max-reduce over hops is a short unrolled loop
of elementwise ops on aligned registers. 1-D flow blocks are not used:
XLA tiles a 1-D f32 operand in chunks of up to 1024, and a block that is
not a multiple of that tile is refused by the TPU compiler.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

LANES = 128          # f32 vreg lanes
SUBLANES = 8         # f32 vreg sublanes
MAX_BLOCK_ROWS = 256  # rows per grid step (32,768 flows; ~3 MiB of VMEM
                      # for the Algorithm-1 kernel's 12 streamed operands
                      # at H=4, double-buffered)


def flow_tiling(F: int):
    """(block_rows, padded_rows) of the ``[rows, 128]`` flow layout: rows
    a multiple of 8 and of the block, so every grid step is whole
    (8, 128) tiles."""
    rows = -(-F // LANES)
    rows = -(-rows // SUBLANES) * SUBLANES
    br = min(rows, MAX_BLOCK_ROWS)
    return br, -(-rows // br) * br


def to_tiles(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """[..., F] -> [..., rows, 128] f32, zero-padded."""
    x = x.astype(jnp.float32)
    pad = rows * LANES - x.shape[-1]
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (rows, LANES))


def from_tiles(x: jnp.ndarray, F: int) -> jnp.ndarray:
    return x.reshape(-1)[:F]


def _kernel(q_ref, qdot_ref, mu_ref, b_ref, valid_ref, tau_ref, w_ref,
            wold_ref, gs_ref, dt_ref, upd_ref, beta_ref, wout_ref,
            gsout_ref, *, gamma, w_min, hops):
    tau = tau_ref[...]
    # max over path hops; invalid hops contribute 0, negative power (fast
    # queue drain) is preserved — identical to laws.norm_power_int.
    gmax = jnp.full_like(tau, -3.4e38)
    for h in range(hops):                      # H is tiny (<= 4): unrolled
        cur = qdot_ref[h] + mu_ref[h]
        volt = q_ref[h] + b_ref[h] * tau
        base = jnp.maximum(b_ref[h] * b_ref[h] * tau, 1.0)
        g = jnp.where(valid_ref[h] != 0, cur * volt / base, 0.0)
        gmax = jnp.maximum(gmax, g)
    d = jnp.clip(dt_ref[...], 0.0, tau)
    gs = (gs_ref[...] * (tau - d) + gmax * d) / jnp.maximum(tau, 1e-12)
    upd = upd_ref[...] != 0
    gs_out = jnp.where(upd, gs, gs_ref[...])
    target = wold_ref[...] / jnp.maximum(gs_out, 1e-9) + beta_ref[...]
    w_new = gamma * target + (1.0 - gamma) * w_ref[...]
    wout_ref[...] = jnp.where(upd, jnp.maximum(w_new, w_min), w_ref[...])
    gsout_ref[...] = gs_out


@functools.partial(jax.jit, static_argnames=("gamma", "w_min",
                                             "interpret"))
def powertcp_step(q, qdot, mu, b, valid, tau, w, w_old, gs_prev, dt_obs,
                  upd, beta, *, gamma=0.9, w_min=1000.0, interpret=None):
    """Per-hop arrays [F, H]; per-flow vectors [F]. Returns (w, gs)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    F, H = q.shape
    br, rows = flow_tiling(F)
    hop = lambda x: to_tiles(x.T, rows)                   # [H, rows, 128]
    flow = lambda x: to_tiles(x, rows)                    # [rows, 128]
    hop_spec = pl.BlockSpec((H, br, LANES), lambda i: (0, i, 0))
    flow_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((rows, LANES), jnp.float32)
    wout, gsout = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma, w_min=w_min, hops=H),
        grid=(rows // br,),
        in_specs=[hop_spec] * 5 + [flow_spec] * 7,
        out_specs=(flow_spec, flow_spec),
        out_shape=(shape, shape),
        interpret=interpret,
    )(hop(q), hop(qdot), hop(mu), hop(b), hop(valid), flow(tau), flow(w),
      flow(w_old), flow(gs_prev), flow(dt_obs), flow(upd), flow(beta))
    return from_tiles(wout, F), from_tiles(gsout, F)


def _theta_kernel(theta_ref, prev_ref, tau_ref, w_ref, wold_ref, gs_ref,
                  dt_ref, upd_ref, beta_ref, wout_ref, gsout_ref,
                  prevout_ref, *, gamma, w_min):
    tau = tau_ref[...]
    theta = theta_ref[...]
    prev = prev_ref[...]
    # Algorithm 2 NORMPOWER: Gamma_norm = (thetadot + 1) * theta / tau
    thetadot = (theta - prev) / jnp.maximum(dt_ref[...], 1e-12)
    gnorm = (thetadot + 1.0) * theta / jnp.maximum(tau, 1e-12)
    d = jnp.clip(dt_ref[...], 0.0, tau)
    gs = (gs_ref[...] * (tau - d) + gnorm * d) / jnp.maximum(tau, 1e-12)
    upd = upd_ref[...] != 0
    gs_out = jnp.where(upd, gs, gs_ref[...])
    target = wold_ref[...] / jnp.maximum(gs_out, 1e-9) + beta_ref[...]
    w_new = gamma * target + (1.0 - gamma) * w_ref[...]
    wout_ref[...] = jnp.where(upd, jnp.maximum(w_new, w_min), w_ref[...])
    gsout_ref[...] = gs_out
    prevout_ref[...] = jnp.where(upd, theta, prev)


@functools.partial(jax.jit, static_argnames=("gamma", "w_min",
                                             "interpret"))
def theta_powertcp_step(theta, prev_theta, tau, w, w_old, gs_prev, dt_obs,
                        upd, beta, *, gamma=0.9, w_min=1000.0,
                        interpret=None):
    """Fused theta-PowerTCP control step (Algorithm 2): RTT + RTT-gradient
    only, no per-hop INT. All inputs are per-flow vectors [F]; returns
    (w, gs, prev_theta) — purely elementwise, one VPU pass per flow tile."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    (F,) = theta.shape
    br, rows = flow_tiling(F)
    flow = lambda x: to_tiles(x, rows)
    flow_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((rows, LANES), jnp.float32)
    wout, gsout, prevout = pl.pallas_call(
        functools.partial(_theta_kernel, gamma=gamma, w_min=w_min),
        grid=(rows // br,),
        in_specs=[flow_spec] * 9,
        out_specs=(flow_spec, flow_spec, flow_spec),
        out_shape=(shape, shape, shape),
        interpret=interpret,
    )(flow(theta), flow(prev_theta), flow(tau), flow(w), flow(w_old),
      flow(gs_prev), flow(dt_obs), flow(upd), flow(beta))
    return from_tiles(wout, F), from_tiles(gsout, F), from_tiles(prevout, F)
