"""Fused (Pallas) law backends.

Importing this module registers the ``"fused"`` backend for the laws that
have a fused kernel (``kernels/powertcp_step.py``). Kept separate from
``laws.py`` so the reference implementations stay kernel-free and the
registry (``laws.LAW_BACKENDS``) is the single source of dispatch truth.

Backend contract (DESIGN.md section 10): a fused ``update`` consumes the
same ``PathObs``/state pytree as its reference twin and must be numerically
equivalent (the tier-1 suite asserts full-trajectory agreement). The fused
backend is close, not exact: FCTs within rtol 1e-4 and atol 2e-6 s of the
reference (``tests/test_backends.py``; ``chip_smoke.py`` gates the same
closeness on the chip), because the incidence matmul reassociates each
queue's arrival sum. The only
extra constraint is that EWMA ``gamma`` must be a concrete Python float —
the kernels take it as a static compile-time argument, so a fused law
cannot sit under a vmapped gamma sweep (use the reference backend there).
"""
from __future__ import annotations

from ..kernels.powertcp_step import powertcp_step, theta_powertcp_step
from .laws import (PowerTCPState, ThetaPowerTCPState, register_backend)
from .types import MTU


def _static_gamma(cfg):
    try:
        return float(cfg.gamma)
    except TypeError as e:          # traced gamma (vmapped hyperparam sweep)
        raise ValueError(
            "fused law backends need a concrete (non-traced) gamma; "
            "use backend='reference' for gamma sweeps") from e


def powertcp_update_fused(state, obs, w, rate_cap, upd_mask, cfg, t):
    """Algorithm 1 via the fused Pallas kernel (NORMPOWER+EWMA+UPDATEWINDOW)."""
    w_new, gs = powertcp_step(
        obs.q, obs.qdot, obs.mu, obs.b, obs.valid, cfg.tau, w, obs.w_old,
        state.gamma_smooth, obs.dt_obs, upd_mask, cfg.beta,
        gamma=_static_gamma(cfg), w_min=MTU)
    return PowerTCPState(gs), w_new, rate_cap


def theta_powertcp_update_fused(state, obs, w, rate_cap, upd_mask, cfg, t):
    """Algorithm 2 via the fused Pallas kernel (timestamps only)."""
    w_new, gs, prev = theta_powertcp_step(
        obs.theta, state.prev_theta, cfg.tau, w, obs.w_old,
        state.gamma_smooth, obs.dt_obs, upd_mask, cfg.beta,
        gamma=_static_gamma(cfg), w_min=MTU)
    return ThetaPowerTCPState(gs, prev), w_new, rate_cap


register_backend("powertcp", "fused", powertcp_update_fused)
register_backend("theta_powertcp", "fused", theta_powertcp_update_fused)
