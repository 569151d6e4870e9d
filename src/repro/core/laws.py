"""Congestion-control laws.

Every law is a pure-JAX pair ``init(nflows, cfg) -> state`` and
``update(state, obs, w, rate_cap, upd_mask, cfg, t) -> (state, w, rate_cap)``
operating on per-flow vectors. The fluid simulator (``fluid.py``) calls
``update`` every step; laws apply their control action only where
``upd_mask`` is set (the per-flow update timer fired — per-RTT by default,
matching the paper's once-per-RTT variant and theta-PowerTCP).

Implemented laws
  powertcp        Algorithm 1 (INT feedback; per-hop max normalized power)
  theta_powertcp  Algorithm 2 (RTT + RTT-gradient only)
  hpcc            HPCC (Li et al., SIGCOMM'19) inflight-MIMD w/ per-RTT wc ref
  swift           delay-based MIMD (paper Eq. 26 — Swift/FAST class)
  timely          TIMELY (Mittal et al.) gradient-based rate control w/ HAI
  gradient_mimd   paper Eq. 27 (pure RTT-gradient MIMD; used for phase plots)
  dcqcn           DCQCN fluid approximation (ECN + alpha, RP increase stages)
  reno            NewReno-style AI/MD on loss (basis for reTCP in rdcn.py)
  retcp           reno + circuit-state window scaling (registered by rdcn.py)

The electrical analogy (Table 1 of the paper):
  current  lambda = qdot + mu          [bytes/s]
  voltage  v      = q + b*tau          [bytes]
  power    Gamma  = lambda * v         [bytes^2/s],  e = b^2 * tau
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .types import PathObs, MTU


def _pin(x: jnp.ndarray) -> jnp.ndarray:
    """Pin an intermediate against XLA algebraic rewriting.

    The normalized-power ratio sits exactly on a float32 knife edge at the
    control law's fixed point (current == b, voltage == b*tau, so the true
    ratio is 1.0): XLA's simplifier may rewrite ``(a*b)/c`` into
    ``a*(b/c)`` in one compiled program and not another (vmap widths, slot
    vs padded engine, shard_map), flipping the result by 1 ulp right where
    the EWMA is most sensitive. An optimization barrier on the numerator
    forces every program to round the same mul-then-div order, which is
    what makes cross-engine trajectory equality bit-for-bit
    (tests/test_slot_engine.py) instead of merely close.
    """
    return jax.lax.optimization_barrier(x)


def _nofma(x: jnp.ndarray) -> jnp.ndarray:
    """Block FMA/FNMA contraction of a product feeding an add/sub.

    ``_pin`` stops XLA's algebraic rewrites but is stripped before
    codegen, so LLVM may still contract ``a*b + c`` (or ``c - a*b``)
    into a fused multiply-add — and compiled program variants (padded vs
    slot vs megakernel, different batch widths) make that choice
    independently, flipping f32 knife edges right where cross-engine
    bit-equality is asserted (first seen on 5-hop fat-tree paths,
    DESIGN.md section 14). Routing the product through a ``maximum``
    with a huge negative constant is numerically inert for every finite
    simulator quantity (and NaN-propagating), survives XLA's simplifier,
    and leaves LLVM no mul-feeds-add pattern to contract — every program
    rounds the product explicitly.
    """
    return jnp.maximum(x, jnp.float32(-3e38))


class LawConfig(NamedTuple):
    """Law hyperparameters. Every field is either a scalar, a per-flow [F]
    vector, or a pytree of scalars — so a whole config batches under
    ``fluid.stack_law_configs`` (leaves gain a leading [B] axis) and sweeps
    as one vmapped program (DESIGN.md section 10)."""
    # shared
    gamma: float = 0.9              # EWMA parameter (paper recommendation)
    beta: jnp.ndarray = None        # [F] additive increase (bytes) = HostBw*tau/N
    tau: jnp.ndarray = None         # [F] base RTT (seconds)
    host_bw: jnp.ndarray = None     # [F] NIC rate (bytes/s)
    # hpcc
    hpcc_eta: float = 0.95
    hpcc_max_stage: int = 5
    # timely
    t_low: jnp.ndarray = None       # [F] seconds (default 1.5*tau)
    t_high: jnp.ndarray = None      # [F] seconds (default 3*tau)
    timely_add: jnp.ndarray = None  # [F] additive step bytes/s
    timely_beta: float = 0.8
    timely_hai_n: int = 5
    # dcqcn
    dcqcn_kmin: float = 400e3       # bytes (NS3 100G-scaled defaults)
    dcqcn_kmax: float = 1.6e6
    dcqcn_pmax: float = 0.2
    dcqcn_g: float = 1.0 / 256.0
    dcqcn_rai: float = 50e6         # bytes/s additive increase (~400Mbps)
    dcqcn_timer: float = 55e-6      # rate-increase timer (seconds, scaled down)
    dcqcn_cnp_timer: float = 50e-6  # min interval between rate cuts (CNP gen)
    dcqcn_f: int = 5                # fast-recovery stages
    # reno
    reno_md: float = 0.5
    # retcp (rdcn.py): circuit schedule + prebuffer as batchable config data
    sched: tuple = None             # ScheduleParams pytree (scalar leaves)
    retcp_prebuffer: float = 0.0    # seconds of early window scale-up
    # feedback-channel laws (core/feedback.py, DESIGN.md section 16)
    fncc_eta: float = 0.95          # fncc target utilization
    pulser_n: float = 8.0           # incast count that triggers a pulse cut
    bp_xoff: float = 2e6            # bytes; queue level that raises pause
    bp_xon: float = 1e6             # bytes; queue level that clears pause
    bp_md: float = 0.5              # backpressure multiplicative decrease
    pcc_eps: float = 0.05           # pcc probe step (rate multiplier spread)
    pcc_b: float = 512.0            # pcc latency-penalty coefficient


# --------------------------------------------------------------------------
# Power computation (Algorithm 1, NORMPOWER) — shared helper
# --------------------------------------------------------------------------

def norm_power_int(obs: PathObs, cfg: LawConfig) -> jnp.ndarray:
    """Per-flow max over path hops of normalized power (INT variant).

    Gamma'      = (qdot + mu) * (q + b*tau)     (current * voltage)
    e           = b^2 * tau
    Gamma'_norm = Gamma' / e
    """
    tau = cfg.tau[:, None]
    current = obs.qdot + obs.mu                      # [F,H] bytes/s
    bdp = _nofma(obs.b * tau)                        # [F,H] bytes (b*tau)
    voltage = obs.q + bdp                            # [F,H] bytes
    # base is written as (b*tau)*b — the association SOME program
    # variants rewrite square(b)*tau into anyway (to reuse voltage's
    # b*tau subterm), flipping the result by 1 ulp between engines.
    # Building it from the materialized bdp and pinning the whole
    # product forces every program onto the same association AND keeps
    # later passes from re-deriving it (DESIGN.md section 14)
    base = _pin(bdp * obs.b)                         # [F,H] b^2 * tau
    power = _pin(current * voltage)
    # explicit reciprocal multiply: XLA CPU's vectorized codegen lowers
    # this f32 divide to recip-then-multiply in SOME programs (even with
    # both operands barriered) while others divide directly — writing
    # the reciprocal makes every program (and eager mode) round the same
    g = jnp.where(obs.valid, power * (1.0 / jnp.maximum(base, 1.0)), 0.0)
    return jnp.max(g, axis=1)                        # [F]


def norm_power_theta(theta: jnp.ndarray, theta_prev: jnp.ndarray,
                     dt_obs: jnp.ndarray, tau: jnp.ndarray) -> jnp.ndarray:
    """theta-PowerTCP (Algorithm 2): Gamma_norm = (thetadot + 1) * theta / tau."""
    thetadot = (theta - theta_prev) / jnp.maximum(dt_obs, 1e-12)
    return _pin((thetadot + 1.0) * theta) / jnp.maximum(tau, 1e-12)


def _smooth(prev: jnp.ndarray, new: jnp.ndarray, dt_obs: jnp.ndarray,
            tau: jnp.ndarray) -> jnp.ndarray:
    """Gamma_smooth update (Alg. 1 line 24), with dt clipped to tau."""
    d = jnp.clip(dt_obs, 0.0, tau)
    blend = _nofma(_pin(prev * (tau - d))) + _nofma(_pin(new * d))
    return blend / jnp.maximum(tau, 1e-12)


def _ewma(gamma, target, w):
    """``gamma * target + (1 - gamma) * w`` with both products pinned
    against XLA rewrites (_pin) and contraction-blocked against LLVM
    FMAs (_nofma), so no program variant fuses one of them into the
    add."""
    return _nofma(_pin(gamma * target)) + _nofma(_pin((1.0 - gamma) * w))


def _mimd_update(w, w_old, norm_power, cfg: LawConfig, upd_mask):
    """UPDATEWINDOW (Alg. 1 line 27): EWMA of (w_old / Gamma_norm + beta)."""
    target = w_old / jnp.maximum(norm_power, 1e-9) + cfg.beta
    w_new = _ewma(cfg.gamma, target, w)
    return jnp.where(upd_mask, jnp.maximum(w_new, MTU), w)


# --------------------------------------------------------------------------
# PowerTCP (INT)
# --------------------------------------------------------------------------

class PowerTCPState(NamedTuple):
    gamma_smooth: jnp.ndarray       # [F]


def powertcp_init(n, cfg):
    return PowerTCPState(gamma_smooth=jnp.ones((n,), jnp.float32))


def powertcp_update(state, obs, w, rate_cap, upd_mask, cfg, t):
    gnorm = norm_power_int(obs, cfg)
    gs = jnp.where(upd_mask,
                   _smooth(state.gamma_smooth, gnorm, obs.dt_obs, cfg.tau),
                   state.gamma_smooth)
    w = _mimd_update(w, obs.w_old, gs, cfg, upd_mask)
    return PowerTCPState(gs), w, rate_cap


# --------------------------------------------------------------------------
# theta-PowerTCP (timestamps only)
# --------------------------------------------------------------------------

class ThetaPowerTCPState(NamedTuple):
    gamma_smooth: jnp.ndarray
    prev_theta: jnp.ndarray


def theta_powertcp_init(n, cfg):
    return ThetaPowerTCPState(jnp.ones((n,), jnp.float32),
                              jnp.asarray(cfg.tau, jnp.float32) * jnp.ones((n,)))


def theta_powertcp_update(state, obs, w, rate_cap, upd_mask, cfg, t):
    gnorm = norm_power_theta(obs.theta, state.prev_theta, obs.dt_obs, cfg.tau)
    gs = jnp.where(upd_mask,
                   _smooth(state.gamma_smooth, gnorm, obs.dt_obs, cfg.tau),
                   state.gamma_smooth)
    w = _mimd_update(w, obs.w_old, gs, cfg, upd_mask)
    prev = jnp.where(upd_mask, obs.theta, state.prev_theta)
    return ThetaPowerTCPState(gs, prev), w, rate_cap


# --------------------------------------------------------------------------
# HPCC
# --------------------------------------------------------------------------

class HPCCState(NamedTuple):
    u: jnp.ndarray                  # EWMA max-link utilization proxy
    wc: jnp.ndarray                 # per-RTT reference window
    inc_stage: jnp.ndarray          # int32
    last_ref: jnp.ndarray           # time of last wc reference update


def hpcc_init(n, cfg):
    return HPCCState(jnp.ones((n,), jnp.float32),
                     jnp.asarray(cfg.host_bw * cfg.tau, jnp.float32) * jnp.ones((n,)),
                     jnp.zeros((n,), jnp.int32),
                     jnp.zeros((n,), jnp.float32))


def hpcc_update(state, obs, w, rate_cap, upd_mask, cfg, t):
    """HPCC: per-ack window update against a once-per-RTT reference wc
    (Li et al. SIGCOMM'19, Alg. 1). upd_mask may fire per-ack or per-RTT;
    the wc reference advances at most once per measured RTT either way."""
    tau = cfg.tau[:, None]
    u_link = jnp.where(obs.valid,
                       obs.q / jnp.maximum(obs.b * tau, 1.0) +
                       obs.mu / jnp.maximum(obs.b, 1.0), 0.0)
    u_max = jnp.max(u_link, axis=1)
    u = jnp.where(upd_mask, _smooth(state.u, u_max, obs.dt_obs, cfg.tau), state.u)
    over = (u >= cfg.hpcc_eta) | (state.inc_stage >= cfg.hpcc_max_stage)
    w_mimd = state.wc / jnp.maximum(u / cfg.hpcc_eta, 1e-6) + cfg.beta
    w_ai = state.wc + cfg.beta
    w_new = jnp.where(over, w_mimd, w_ai)
    w_out = jnp.where(upd_mask, jnp.maximum(w_new, MTU), w)
    ref = upd_mask & (t - state.last_ref >= obs.theta)
    wc = jnp.where(ref, w_out, state.wc)
    inc = jnp.where(ref, jnp.where(over, 0, state.inc_stage + 1),
                    state.inc_stage)
    last_ref = jnp.where(ref, t, state.last_ref)
    return HPCCState(u, wc, inc, last_ref), w_out, rate_cap


# --------------------------------------------------------------------------
# Swift / FAST class: delay-based MIMD (paper Eq. 26)
# --------------------------------------------------------------------------

class SwiftState(NamedTuple):
    dummy: jnp.ndarray


def swift_init(n, cfg):
    return SwiftState(jnp.zeros((n,), jnp.float32))


def swift_update(state, obs, w, rate_cap, upd_mask, cfg, t):
    f = jnp.maximum(obs.theta, 1e-12)
    target = _pin(obs.w_old * cfg.tau) / f + cfg.beta
    w_new = _ewma(cfg.gamma, target, w)
    w = jnp.where(upd_mask, jnp.maximum(w_new, MTU), w)
    return state, w, rate_cap


# --------------------------------------------------------------------------
# Pure RTT-gradient MIMD (paper Eq. 27) — current-based CC for phase plots
# --------------------------------------------------------------------------

class GradState(NamedTuple):
    prev_theta: jnp.ndarray


def gradient_init(n, cfg):
    return GradState(jnp.asarray(cfg.tau, jnp.float32) * jnp.ones((n,)))


def gradient_update(state, obs, w, rate_cap, upd_mask, cfg, t):
    thetadot = (obs.theta - state.prev_theta) / jnp.maximum(obs.dt_obs, 1e-12)
    f = jnp.maximum(thetadot + 1.0, 1e-2)
    target = obs.w_old / f + cfg.beta
    w_new = _ewma(cfg.gamma, target, w)
    w = jnp.where(upd_mask, jnp.maximum(w_new, MTU), w)
    prev = jnp.where(upd_mask, obs.theta, state.prev_theta)
    return GradState(prev), w, rate_cap


# --------------------------------------------------------------------------
# TIMELY (rate-based, gradient + HAI)
# --------------------------------------------------------------------------

class TimelyState(NamedTuple):
    rate: jnp.ndarray
    prev_theta: jnp.ndarray
    neg_count: jnp.ndarray          # consecutive negative-gradient counter


def timely_init(n, cfg):
    return TimelyState(jnp.asarray(cfg.host_bw, jnp.float32) * jnp.ones((n,)),
                       jnp.asarray(cfg.tau, jnp.float32) * jnp.ones((n,)),
                       jnp.zeros((n,), jnp.int32))


def timely_update(state, obs, w, rate_cap, upd_mask, cfg, t):
    t_low = cfg.t_low if cfg.t_low is not None else 1.5 * cfg.tau
    t_high = cfg.t_high if cfg.t_high is not None else 3.0 * cfg.tau
    # explicit reciprocal multiply: program variants disagree on whether
    # x / 100.0 lowers to a division or a reciprocal multiply (they
    # round differently); writing the multiply makes every engine agree
    add = cfg.timely_add if cfg.timely_add is not None \
        else cfg.host_bw * (1.0 / 100.0)
    grad = (obs.theta - state.prev_theta) / jnp.maximum(cfg.tau, 1e-12)  # normalized
    neg = jnp.where(grad <= 0, state.neg_count + 1, 0)
    hai = neg >= cfg.timely_hai_n
    r = state.rate
    # the additive increment is _nofma'd: some variants contract
    # r + hai_n*add into an FMA through the select, some round the
    # product first
    r_low = r + _nofma(jnp.where(hai, cfg.timely_hai_n * add, add))
    r_high = r * (1.0 - _nofma(_pin(cfg.timely_beta *
                               (1.0 - t_high / jnp.maximum(obs.theta,
                                                           1e-12)))))
    r_grad_neg = r + _nofma(jnp.where(hai, cfg.timely_hai_n * add, add))
    r_grad_pos = r * jnp.maximum(1.0 - _nofma(_pin(cfg.timely_beta * grad)),
                                 0.5)
    r_mid = jnp.where(grad <= 0, r_grad_neg, r_grad_pos)
    r_new = jnp.where(obs.theta < t_low, r_low,
                      jnp.where(obs.theta > t_high, r_high, r_mid))
    r_new = jnp.clip(r_new, 0.001 * cfg.host_bw, cfg.host_bw)
    rate = jnp.where(upd_mask, r_new, state.rate)
    # window bookkeeping: keep w tracking rate*theta so FCT logic stays uniform
    w = jnp.where(upd_mask, jnp.maximum(rate * obs.theta, MTU), w)
    prev = jnp.where(upd_mask, obs.theta, state.prev_theta)
    return TimelyState(rate, prev, jnp.where(upd_mask, neg, state.neg_count)), w, rate


# --------------------------------------------------------------------------
# DCQCN (fluid approximation)
# --------------------------------------------------------------------------

class DCQCNState(NamedTuple):
    rc: jnp.ndarray                 # current rate
    rt: jnp.ndarray                 # target rate
    alpha: jnp.ndarray
    t_last_cut: jnp.ndarray
    t_last_inc: jnp.ndarray
    inc_stage: jnp.ndarray


def dcqcn_init(n, cfg):
    hb = jnp.asarray(cfg.host_bw, jnp.float32) * jnp.ones((n,))
    z = jnp.zeros((n,), jnp.float32)
    return DCQCNState(hb, hb, jnp.ones((n,), jnp.float32), z, z,
                      jnp.zeros((n,), jnp.int32))


def dcqcn_update(state, obs, w, rate_cap, upd_mask, cfg, t):
    """ECN-marking-driven rate control. ``upd_mask`` fires per RTT; timers
    gate the actual cut/increase cadence."""
    p = obs.ecn_frac                                  # marking prob at bottleneck
    # probability >=1 marked packet among packets sent since last update
    pkts = jnp.maximum(_pin(state.rc * obs.dt_obs) / MTU, 1.0)
    pe = 1.0 - jnp.power(jnp.clip(1.0 - p, 0.0, 1.0), pkts)
    cut = upd_mask & (pe > 0.01) & (t - state.t_last_cut >= cfg.dcqcn_cnp_timer)
    alpha = jnp.where(cut, _ewma(cfg.dcqcn_g, pe, state.alpha), state.alpha)
    rt = jnp.where(cut, state.rc, state.rt)
    # expected-value (fluid) cut: scale the alpha/2 cut by the mark fraction
    rc = jnp.where(cut,
                   state.rc * (1.0 - _nofma(_pin(0.5 * alpha *
                                                 jnp.minimum(pe, 1.0)))),
                   state.rc)
    t_cut = jnp.where(cut, t, state.t_last_cut)
    # increase path: timer since last increase and no recent cut
    can_inc = upd_mask & (~cut) & (t - state.t_last_inc >= cfg.dcqcn_timer)
    stage = jnp.where(cut, 0, state.inc_stage)
    fast = stage < cfg.dcqcn_f
    hyper = stage >= 2 * cfg.dcqcn_f
    rai = jnp.where(hyper, 5.0 * cfg.dcqcn_rai, cfg.dcqcn_rai)
    rt_inc = jnp.where(fast, rt, rt + rai)
    rc_inc = 0.5 * (rc + rt_inc)
    rc = jnp.where(can_inc, rc_inc, rc)
    rt = jnp.where(can_inc, rt_inc, rt)
    stage = jnp.where(can_inc, stage + 1, stage)
    t_inc = jnp.where(can_inc, t, state.t_last_inc)
    # alpha decay toward 0 when no congestion (per DCQCN alpha-update timer)
    alpha = jnp.where(can_inc, (1.0 - cfg.dcqcn_g) * alpha, alpha)
    rc = jnp.clip(rc, 0.001 * cfg.host_bw, cfg.host_bw)
    w = jnp.where(upd_mask, jnp.maximum(rc * jnp.maximum(obs.theta, cfg.tau), MTU), w)
    return DCQCNState(rc, rt, alpha, t_cut, t_inc, stage), w, rc


# --------------------------------------------------------------------------
# NewReno-ish AI/MD (loss == bottleneck queue at capacity). Used by reTCP.
# --------------------------------------------------------------------------

class RenoState(NamedTuple):
    last_cut: jnp.ndarray


def reno_init(n, cfg):
    return RenoState(jnp.zeros((n,), jnp.float32))


def reno_update(state, obs, w, rate_cap, upd_mask, cfg, t):
    # loss proxy: observed bottleneck queue within one MTU of the buffer cap is
    # signalled by the simulator via ecn_frac >= 1 (hard mark).
    loss = obs.ecn_frac >= 1.0
    can_cut = upd_mask & loss & (t - state.last_cut > obs.theta)
    # MD on loss (at most once per RTT), else AI of one MTU per update tick.
    w_new = jnp.where(can_cut, w * cfg.reno_md,
                      jnp.where(upd_mask, w + MTU, w))
    w_new = jnp.maximum(w_new, MTU)
    last = jnp.where(can_cut, t, state.last_cut)
    return RenoState(last), w_new, rate_cap


class Law(NamedTuple):
    """A congestion-control law bound to one concrete backend.

    ``init(nflows, cfg) -> state`` and
    ``update(state, obs, w, rate_cap, upd_mask, cfg, t) -> (state, w, rate_cap)``
    form the uniform state/obs contract every backend must honour: same state
    pytree, same ``PathObs`` fields, same masking semantics. ``backend`` names
    the implementation currently bound to ``update`` (``"reference"`` pure-jnp,
    ``"fused"`` Pallas, or ``"megakernel"``, the whole-tick fused slot engine;
    see ``register_backend``/``get_law``).

    ``uses_qdot``/``uses_mu``/``uses_ecn`` declare which optional ``PathObs``
    telemetry the law actually reads. The reference engines always deliver
    everything; the megakernel backend uses the flags to skip building
    telemetry a law ignores (the skipped fields arrive as zeros, so a law
    that honours its declaration computes identically — and bit-equality
    with the reference backend is asserted registry-wide in
    tests/test_megakernel.py). Keep a flag True when in doubt.

    ``masked_updates`` declares that the law honours the ``upd_mask``
    contract strictly — outside the mask its state, window and rate cap
    pass through unchanged (every law above; per-tick clips that are
    identities on in-range values, like DCQCN's rate clamp, qualify). The
    megakernel's quiescent-pool fast tick relies on this; a law with a
    documented every-step deviation (reTCP's circuit-state multiplier)
    must set it False.

    ``feedback`` selects the delay model of the feedback path (DESIGN.md
    section 16): ``"receiver"`` is the classic receiver-echo loop (INT
    metadata rides to the receiver and returns with the ack — hop h's
    telemetry is ``rtt - tf_h`` old), ``"hop"`` is congestion-point
    feedback (the congested switch notifies the sender directly over the
    reverse path — hop h's telemetry is only ``tf_h`` old, a strictly
    shorter control loop on symmetric fabrics). ``uses_pause`` asks the
    engines to run per-queue XON/XOFF pause hysteresis and deliver the
    delayed per-hop pause state as ``PathObs.pause``; ``uses_incast``
    asks for per-queue live-sender counts as ``PathObs.incast``. All
    channel flags are validated at registration time against
    ``ENGINE_CHANNELS`` — a flag naming a channel no engine provides
    raises instead of being silently ignored.
    """
    name: str
    init: Callable
    update: Callable
    rate_based: bool = False
    backend: str = "reference"
    uses_qdot: bool = True          # reads PathObs.qdot (queue gradient)
    uses_mu: bool = True            # reads PathObs.mu (egress txRate)
    uses_ecn: bool = True           # reads PathObs.ecn_frac (marking)
    masked_updates: bool = True     # strict upd_mask passthrough contract
    feedback: str = "receiver"      # feedback-path delay model (see above)
    uses_pause: bool = False        # reads PathObs.pause (XON/XOFF state)
    uses_incast: bool = False       # reads PathObs.incast (sender counts)


LAWS = {
    "powertcp": Law("powertcp", powertcp_init, powertcp_update,
                    uses_ecn=False),
    "theta_powertcp": Law("theta_powertcp", theta_powertcp_init,
                          theta_powertcp_update, uses_qdot=False,
                          uses_mu=False, uses_ecn=False),
    "hpcc": Law("hpcc", hpcc_init, hpcc_update, uses_qdot=False,
                uses_ecn=False),
    "swift": Law("swift", swift_init, swift_update, uses_qdot=False,
                 uses_mu=False, uses_ecn=False),
    "gradient_mimd": Law("gradient_mimd", gradient_init, gradient_update,
                         uses_qdot=False, uses_mu=False, uses_ecn=False),
    "timely": Law("timely", timely_init, timely_update, rate_based=True,
                  uses_qdot=False, uses_mu=False, uses_ecn=False),
    "dcqcn": Law("dcqcn", dcqcn_init, dcqcn_update, rate_based=True,
                 uses_qdot=False, uses_mu=False),
    "reno": Law("reno", reno_init, reno_update, uses_qdot=False,
                uses_mu=False),
}


# --------------------------------------------------------------------------
# Law + backend registry (DESIGN.md section 10)
#
# ``LAWS`` maps law name -> the canonical ``Law`` (its "reference" pure-jnp
# implementation). ``LAW_BACKENDS`` maps law name -> {backend name -> update
# callable}; alternative backends (e.g. the fused Pallas kernels registered
# on import of ``core.backends`` — kept separate so laws.py stays
# kernel-free) are pure drop-in replacements for ``Law.update``.
#
# Every law also carries a ``"megakernel"`` backend entry: its
# KERNEL-COMPOSABLE per-tick update, the function the whole-tick fused slot
# engine (core/megakernel.py, DESIGN.md section 13) inlines into its K-tick
# block. By default this is the reference update itself — reference updates
# are pure per-flow jnp and therefore compose into the megernel's traced
# block unchanged, which is how every registered law (including ones
# registered tomorrow) runs on the fused path with zero extra code. A law
# may override its composable form via ``register_backend(name,
# "megakernel", fn)``; such an override must stay free of nested
# ``pallas_call``s (it runs INSIDE the megakernel's traced block, so e.g.
# the "fused" Pallas law kernels are not composable).
#
# The contract, which every registered implementation must honour:
#
#   * ``init(nflows, cfg: LawConfig) -> state`` returns the law's state
#     pytree with [F]-leading leaves; the SAME pytree structure for every
#     backend of a law (state produced by one backend must be consumable by
#     another — backends are interchangeable mid-contract, not mid-scan).
#   * ``update(state, obs: PathObs, w, rate_cap, upd_mask, cfg: LawConfig,
#     t) -> (state, w, rate_cap)`` is pure, per-flow vectorized, and applies
#     its control action only where ``upd_mask`` is set — flows outside the
#     mask must pass ``state``/``w``/``rate_cap`` through unchanged. A law
#     modelling an out-of-band signal may deviate for that signal only if
#     its docstring says so (sole case: retcp's circuit-state multiplier,
#     rdcn.py).
#   * Window-based laws return ``rate_cap`` untouched; rate-based laws
#     (``Law.rate_based``) also return their rate as ``rate_cap`` and keep
#     ``w ≈ rate * theta`` so FCT accounting stays uniform.
#   * Backend choice may change *where* the law runs, never *what* it
#     computes: full-trajectory equivalence with the reference backend is
#     asserted in tests/test_backends.py.
#
# ``get_law(name, backend)`` is the single dispatch point the simulator
# uses; nothing else should reach into ``LAW_BACKENDS`` directly.
# --------------------------------------------------------------------------

LAW_BACKENDS: dict = {name: {"reference": law.update,
                             "megakernel": law.update}
                      for name, law in LAWS.items()}

# Telemetry channels the engines can actually provide, i.e. the legal
# ``uses_<channel>`` declarations on a Law, and the legal feedback-path
# delay models. Validated at registration time (``register_law``) so a
# typo'd flag (``uses_quot``) raises immediately instead of being
# silently ignored by every engine.
ENGINE_CHANNELS = ("qdot", "mu", "ecn", "pause", "incast")
FEEDBACK_MODELS = ("receiver", "hop")


def _validate_law(law) -> None:
    """Raise ``ValueError`` if a law declares a channel no engine provides
    or an unknown feedback-path model. Scans the law's own fields so Law
    extensions (extra ``uses_*`` fields on a subclassed NamedTuple) are
    caught too."""
    name = getattr(law, "name", "<unnamed>")
    for field in getattr(law, "_fields", ()):
        if field.startswith("uses_") and field[5:] not in ENGINE_CHANNELS:
            raise ValueError(
                f"law '{name}' declares '{field}' but no engine provides a "
                f"'{field[5:]}' channel; available channels: "
                f"{ENGINE_CHANNELS}")
    fb = getattr(law, "feedback", "receiver")
    if fb not in FEEDBACK_MODELS:
        raise ValueError(
            f"law '{name}' declares feedback={fb!r}; engines implement "
            f"{FEEDBACK_MODELS}")


def register_law(law: Law) -> None:
    """Add a new law to the registry (its ``update`` becomes both the
    ``"reference"`` backend and the kernel-composable ``"megakernel"``
    entry). The law must obey the contract above; its name becomes
    resolvable through ``get_law`` and listable backends.
    Re-registering a name replaces the law AND resets its backends table —
    alternative backends of the old law would otherwise stay resolvable
    and silently pair the new law with the old implementation.
    Channel declarations are validated eagerly (``_validate_law``)."""
    _validate_law(law)
    LAWS[law.name] = law
    LAW_BACKENDS[law.name] = {"reference": law.update,
                              "megakernel": law.update}


def register_backend(law_name: str, backend: str, update: Callable) -> None:
    """Register an alternative ``update`` implementation for a law.

    The implementation must obey the Law contract exactly (same state pytree,
    same ``PathObs`` consumption, identical masking semantics) — backend choice
    may change *where* the law runs, never *what* it computes.
    """
    if law_name not in LAWS:
        raise KeyError(f"unknown law '{law_name}'; have {sorted(LAWS)}")
    LAW_BACKENDS.setdefault(law_name, {})[backend] = update


def law_backends(name: str) -> list:
    """Names of the backends available for ``name``."""
    return sorted(LAW_BACKENDS.get(name, {}))


def get_law(name: str, backend: str = "reference") -> Law:
    """Single dispatch point: resolve a law bound to a concrete backend.

    Promises: the returned ``Law`` has ``update`` swapped for the chosen
    backend's implementation and ``backend`` recording the choice; raises
    ``KeyError`` (never silently falls back) for unknown laws or backends.
    """
    if name not in LAWS:
        raise KeyError(f"unknown law '{name}'; have {sorted(LAWS)}")
    impls = LAW_BACKENDS[name]
    if backend not in impls:
        raise KeyError(f"law '{name}' has no backend '{backend}'; "
                       f"have {sorted(impls)}")
    return LAWS[name]._replace(update=impls[backend], backend=backend)


# The builtin table above predates registration-time validation; check it
# once at import so the module can never load with an invalid builtin.
for _law in LAWS.values():
    _validate_law(_law)
del _law
