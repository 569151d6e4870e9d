"""Vectorized fluid-model network simulator.

Implements the paper's analytical model (Eqs. 4/9/10 and Appendix A) as a
jittable ``lax.scan`` over time steps:

  queue dynamics    qdot_j = sum_i[i traverses j] lam_i(t - tf_i) - mu_j
  flow rates        lam_i  = min(w_i / theta_i, rate_cap_i, nic_i)
  measured RTT      theta_i = tau_i + sum_j on path q_j / b_j
  feedback delay    senders observe bottleneck state theta_i seconds late

Control laws (laws.py) fire on per-flow timers (default once per measured
RTT). Telemetry is taken from ring-buffer histories, exactly the INT metadata
of Algorithm 1 (qlen, its gradient, txRate, bandwidth) plus the RTT sample
used by the theta variant.

Backends (DESIGN.md section 10): every simulation runs either on the
``"reference"`` backend (pure jnp: scatter-add queue update, jnp laws) or
the ``"fused"`` backend, which routes the two hot spots through the Pallas
kernels — the per-tick control update through ``kernels/powertcp_step.py``
(laws with a registered fused backend) and the queue-arrival scatter through
``kernels/queue_arrivals.py`` (incidence matmul). Both backends are
numerically equivalent; tests/test_backends.py asserts full-trajectory
agreement.

Batched sweeps: ``simulate_batch`` vmaps a whole axis of scenarios (shared
topology, stacked ``Flows``/``LawConfig`` leaves, per-scenario ``bw_params``
for time-varying bandwidth schedules) through one ``lax.scan``, so an
entire benchmark sweep (seeds, loads, law hyperparameters, circuit
schedules) compiles once and runs as a single program instead of once per
point. With ``devices > 1`` the batch axis is sharded across the active
device mesh via ``shard_map`` — each device scans its slice of scenarios —
falling back bit-exactly to the single-device vmap when one device is
present. Batch-axis layout, padding semantics and the sharding contract
are specified in DESIGN.md section 11; the declarative grid front end is
``core/sweep.py``.

Flow-slot streaming engine (DESIGN.md section 12): the padded engine above
carries EVERY flow of a scenario through every tick, so per-tick cost grows
with the total flow count even though only a few hundred flows are ever
concurrently active. ``simulate_slots`` instead streams a time-sorted
``FlowSchedule`` through a fixed pool of S active slots — a jittable
admit/retire pass inside the scan body pulls due arrivals into free slots
and retires completed flows once their in-flight traffic has drained — so
per-tick cost is O(S * hops), independent of the total flow count. With
``S >= total_flows`` the slot engine reproduces the padded engine's
queue and FCT trajectories bit-for-bit (asserted in
tests/test_slot_engine.py; per-flow windows agree to <= 1 ulp — the
exactness boundary and the arithmetic pinning behind it are documented
in DESIGN.md section 12). Undersized pools stay correct but
admission-delay flows that arrive while the pool is full.
``simulate_slots_batch`` is the batched/sharded twin with the same
padding and device-sharding contract as ``simulate_batch``.

Deviations from a packet simulator are documented in DESIGN.md section 9:
no per-packet loss/retransmit (losses appear as capped queues), store-and-
forward shaping across hops is not modelled, and ECN feedback uses the
expected marking fraction.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Callable, List, NamedTuple, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map

from ..kernels.queue_arrivals import (apply_loss, ordered_scatter_add,
                                      queue_arrivals, suggest_maxdeg,
                                      update_incidence)
from ..launch.mesh import make_mesh
from ..sharding.axes import active_mesh, active_rules, axes_to_pspec
from . import obs
from .faults import FaultSpec, InjectedCrash, UnsupportedFeature
from .impair import ImpairmentParams, impair_vectors, link_bw_at
from .laws import Law, LawConfig, get_law, _nofma, _pin
from .types import (MTU, CheckpointSpec, Flows, FlowSchedule, PathObs,
                    Record, SimConfig, SimState, SlotState, Topology,
                    pad_hops)

_INT32_MAX = np.iinfo(np.int32).max


def default_law_config(flows: Flows, gamma: float = 0.9,
                       expected_flows: float = 1.0, **kw) -> LawConfig:
    """Paper parameterization: beta = HostBw * tau / N."""
    beta = flows.nic_rate * flows.tau / expected_flows
    return LawConfig(gamma=gamma, beta=beta, tau=flows.tau,
                     host_bw=flows.nic_rate, **kw)


def _hop_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sequential sum over the (last) hop axis with a fixed association.

    ``jnp.sum``'s reduction order is implementation-defined — compiled
    program variants (padded vs slot vs megakernel) may associate a
    5-hop sum differently and flip the per-flow RTT by 1 ulp, which
    breaks cross-engine bit-equality for laws that consume theta
    directly (first seen with TIMELY on fat-tree paths; DESIGN.md
    section 14). An unrolled left-to-right chain costs the same H-1
    adds and leaves no association choice to make.
    """
    acc = x[..., 0]
    for h in range(1, x.shape[-1]):
        acc = acc + x[..., h]
    return acc


def _hop_keep(keep: jnp.ndarray, path: jnp.ndarray,
              valid: jnp.ndarray) -> jnp.ndarray:
    """Per-flow survival fraction: the product of ``keep`` over the
    flow's valid hops, as an unrolled left-to-right chain (``_hop_sum``'s
    multiplicative twin — same fixed-association rationale; a pure
    multiply chain has no add for LLVM to contract). Invalid hops
    contribute the exact identity 1.0, and an all-ones ``keep`` returns
    exactly 1.0, which keeps the zero-impairment goodput bitwise equal
    to the unimpaired engine (core/impair.py)."""
    k_hop = _pin(keep[path])                           # [.., H]
    acc = jnp.where(valid[..., 0], k_hop[..., 0], 1.0)
    for h in range(1, path.shape[-1]):
        acc = acc * jnp.where(valid[..., h], k_hop[..., h], 1.0)
    return _pin(acc)


def _marking(q: jnp.ndarray, buf: jnp.ndarray, cfg: LawConfig) -> jnp.ndarray:
    """ECN marking probability + hard mark when a hop's buffer is ~full."""
    p = jnp.clip((q - cfg.dcqcn_kmin) /
                 jnp.maximum(cfg.dcqcn_kmax - cfg.dcqcn_kmin, 1.0),
                 0.0, 1.0) * cfg.dcqcn_pmax
    hard = q >= 0.95 * buf
    return jnp.where(hard, 1.0, p)


def _pause_step(q_new: jnp.ndarray, pause: jnp.ndarray,
                cfg: LawConfig) -> jnp.ndarray:
    """Per-queue XON/XOFF pause hysteresis (laws with ``uses_pause``).

    Raises pause at ``bp_xoff``, clears it at ``bp_xon``, holds in
    between. Pure comparisons on the already-integrated queue level —
    no arithmetic, so the channel is trivially bit-identical across
    engines. A drained queue (q <= bp_xon) ALWAYS clears its pause, which
    is the no-deadlock guarantee the property suite asserts: pausing
    senders drains the queue, the drain clears the pause, additive
    increase resumes. The sentinel queue stays 0 (bp_xon >= 0)."""
    return jnp.where(q_new >= cfg.bp_xoff, 1.0,
                     jnp.where(q_new <= cfg.bp_xon, 0.0, pause))


def _incast_count(q: jnp.ndarray, path: jnp.ndarray, valid: jnp.ndarray,
                  lam_del: jnp.ndarray) -> jnp.ndarray:
    """Per-queue count of flows currently contributing traffic (laws with
    ``uses_incast``). Counts are integer-valued f32 sums of 1.0 — exactly
    representable and associativity-free, so scatter order differences
    between engines cannot flip a bit."""
    sending = jnp.where(valid & (lam_del > 0.0), 1.0, 0.0)
    return ordered_scatter_add(jnp.zeros_like(q), path, sending)


class FluidSim(NamedTuple):
    """One scenario bound to a backend.

    ``backend`` selects the implementation of the two hot spots in ``step``
    (law update + queue-arrival update); ``incidence`` is the precomputed
    [H, F, Q+1] one-hot path incidence used by the fused queue kernel
    (``build_incidence``; None on the reference backend).
    """
    topo: Topology
    flows: Flows
    law: Law
    law_cfg: LawConfig
    cfg: SimConfig
    backend: str = "reference"
    incidence: Optional[jnp.ndarray] = None
    # per-link impairment regime (core/impair.py); None keeps the compiled
    # program byte-identical to the unimpaired build (trace-time gating)
    impair: Optional[ImpairmentParams] = None


def build_incidence(flows: Flows, num_queues: int) -> jnp.ndarray:
    """[H, F, Q+1] one-hot path incidence for the fused queue update.

    Invalid (padded) hops become all-zero rows, so the incidence matmul
    reproduces exactly the masked scatter-add of the reference backend.
    """
    valid = flows.path < num_queues
    oh = jax.nn.one_hot(flows.path, num_queues + 1, dtype=jnp.float32)
    oh = oh * valid[..., None].astype(jnp.float32)
    return jnp.swapaxes(oh, 0, 1)


def init_state(sim: FluidSim) -> SimState:
    topo, flows, cfg = sim.topo, sim.flows, sim.cfg
    F = flows.tau.shape[0]
    Q = topo.num_queues
    D = cfg.hist
    w0 = flows.nic_rate * flows.tau          # cwnd_init = HostBw * tau
    law_state = sim.law.init(F, sim.law_cfg)
    return SimState(
        t=jnp.asarray(0, jnp.int32),
        w=w0.astype(jnp.float32),
        rate_cap=jnp.full((F,), jnp.inf, jnp.float32),
        q=jnp.zeros((Q + 1,), jnp.float32),
        out_rate=jnp.zeros((Q + 1,), jnp.float32),
        hist_lam=jnp.zeros((D, F), jnp.float32),
        hist_q=jnp.zeros((D, Q + 1), jnp.float32),
        hist_out=jnp.zeros((D, Q + 1), jnp.float32),
        hist_w=jnp.broadcast_to(w0, (D, F)).astype(jnp.float32),
        remaining=flows.size.astype(jnp.float32),
        fct=jnp.full((F,), jnp.nan, jnp.float32),
        next_update=(flows.start + flows.tau).astype(jnp.float32),
        last_update=flows.start.astype(jnp.float32),
        law=law_state,
        # feedback channels only materialize when the law declares them —
        # None leaves keep the carry (and the compiled program) identical
        # for every pre-existing law
        pause=(jnp.zeros((Q + 1,), jnp.float32)
               if sim.law.uses_pause else None),
        hist_pause=(jnp.zeros((D, Q + 1), jnp.float32)
                    if sim.law.uses_pause else None),
        hist_inc=(jnp.zeros((D, Q + 1), jnp.float32)
                  if sim.law.uses_incast else None),
    )


def _bandwidth(topo: Topology, bw_fn, t_sec, impair=None):
    """[Q+1] per-queue service rates at ``t_sec`` (sentinel appended).

    Three mutually-exclusive drivers, in precedence order: an impairment
    regime (``core.impair.link_bw_at`` — per-link processes), a bw_fn
    (the legacy whole-vector schedule hook), or the static topology
    capacities. The public drivers reject ``bw_fn`` + ``impair`` together
    (two owners of the same vector)."""
    if impair is not None:
        bw = link_bw_at(t_sec, impair)
    else:
        bw = topo.bandwidth if bw_fn is None else bw_fn(t_sec)
    return jnp.concatenate([bw, jnp.asarray([1e15], jnp.float32)])


_SWITCH_TABLE_MAX_DEG = 64
_switch_table_cache: dict = {}


def _switch_queue_table(sw: np.ndarray, num_switches: int) -> np.ndarray:
    """Static ``[num_switches, max_deg]`` table of each switch's queue ids in
    ascending order, padded with ``len(sw)`` (points at an appended 0.0).

    Replays ``segment_sum``'s per-switch accumulation exactly: XLA:CPU lowers
    the scatter-add to a loop over updates in ascending queue order, so each
    switch's sum is the left fold over its queues sorted ascending — which is
    precisely a column-wise fold over this table (pads add +0.0, an exact
    identity for the non-negative queue depths). Memoized per topology.
    """
    key = (sw.tobytes(), num_switches)
    tab = _switch_table_cache.get(key)
    if tab is None:
        counts = np.bincount(sw, minlength=num_switches)
        deg = int(counts.max()) if counts.size else 0
        tab = np.full((num_switches, deg), len(sw), dtype=np.int32)
        order = np.argsort(sw, kind="stable")   # per switch: queues ascending
        col = np.concatenate([np.arange(c) for c in counts]) \
            if counts.size else np.zeros((0,), np.int64)
        tab[sw[order], col] = order.astype(np.int32)
        _switch_table_cache[key] = tab
    return tab


def _buffer_caps(topo: Topology, q: jnp.ndarray) -> jnp.ndarray:
    """Per-queue caps; Dynamic Thresholds [17] when dt_alpha > 0."""
    buf = jnp.concatenate([topo.buffer, jnp.asarray([1e30], jnp.float32)])
    if topo.dt_alpha <= 0:
        return buf
    try:                              # concrete at trace time (closed-over)
        sw_np = np.asarray(topo.switch_of_queue)
    except Exception:                 # traced topology: keep the scatter
        sw_np = None
    if sw_np is not None and sw_np.size:
        tab = _switch_queue_table(sw_np, int(topo.num_switches))
    else:
        tab = None
    if tab is not None and 0 < tab.shape[1] <= _SWITCH_TABLE_MAX_DEG:
        # Exact gather/fold replay of the scatter-add (see table docstring):
        # ~deg fused vector adds instead of a serial per-element scatter.
        qp = jnp.concatenate([q[:-1], jnp.zeros((1,), q.dtype)])
        used = jnp.zeros((int(topo.num_switches),), q.dtype)
        for j in range(tab.shape[1]):
            used = used + qp[tab[:, j]]
    else:
        used = jax.ops.segment_sum(q[:-1], topo.switch_of_queue,
                                   num_segments=topo.num_switches)
    free = jnp.maximum(topo.switch_buffer - used, 0.0)
    thr = topo.dt_alpha * free[topo.switch_of_queue]
    thr = jnp.concatenate([jnp.minimum(thr, topo.buffer),
                           jnp.asarray([1e30], jnp.float32)])
    return thr


def _queue_update(topo: Topology, dt: float, backend: str, incidence,
                  path, q, lam_del, valid, bw, keep=None):
    """Queue-arrival accumulation + integration: (arrivals, out, q_new).

    Reference backend: masked scatter-add over ``path``. Fused backend:
    incidence matmul through ``kernels/queue_arrivals`` (passing
    ``out_rate=bw`` to the kernel is exact — when q == 0 and arr < bw the
    clip at 0 reproduces ``out = min(arr, bw)``; the recorded ``out`` is
    still computed from the returned arrivals). Shared by the padded
    (``step``) and slot (``slot_step``) engines — ``path``/``incidence``
    are the static per-flow hop table for the former, the pool's current
    occupancy for the latter.
    """
    caps = _buffer_caps(topo, q)
    if backend == "fused" and incidence is not None:
        arr, q_new = queue_arrivals(jnp.swapaxes(lam_del, 0, 1),
                                    incidence, q, bw, caps, dt=dt)
    else:
        contrib = jnp.where(valid, lam_del, 0.0)
        # bit-identical to zeros.at[path].add(contrib); small row counts
        # unroll to straight-line code instead of the per-row while loop
        # XLA CPU emits for a float scatter (which dominated the whole
        # tick on small scenarios, e.g. the fig8 VOQ — see the kernel's
        # docstring)
        arr = ordered_scatter_add(jnp.zeros_like(q), path, contrib)
        if keep is not None:
            # per-link loss folds into the ACCUMULATED arrivals (the one
            # placement every engine shares bit-for-bit; see the kernel)
            arr = apply_loss(arr, keep)
        # pinned against XLA rewrites and contraction-blocked against
        # LLVM FMAs so no program variant fuses the integration into the
        # add, which would break cross-engine bit-equality (laws._pin /
        # laws._nofma; mirrored by kernels.integrate_arrivals)
        q_new = jnp.clip(q + _nofma(_pin((arr - bw) * dt)), 0.0, caps)
    out = jnp.where(q > 0.0, bw, jnp.minimum(arr, bw))
    q_new = q_new.at[-1].set(0.0)
    return arr, out, q_new


def _pin_flow_cfg(cfg: LawConfig) -> LawConfig:
    """Pin per-flow LawConfig vectors in the PADDED engine.

    There they are compile-time constants (the scenario is closed over),
    so XLA folds divisions by them into reciprocal multiplies — arithmetic
    the slot engine, where the same values are dynamic (gathered on
    admission), never performs. Pinning makes both engines round the same
    true divisions, a prerequisite of the bit-for-bit exactness anchor
    (DESIGN.md section 12). Scalars stay constant — they are constants in
    both engines.
    """
    def g(leaf):
        x = jnp.asarray(leaf)
        if x.ndim >= 1 and jnp.issubdtype(x.dtype, jnp.floating):
            return _pin(x)
        return leaf
    return jax.tree_util.tree_map(g, cfg)


def step(sim: FluidSim, state: SimState, bw_fn=None, alloc_fn=None):
    topo, flows, cfg = sim.topo, sim.flows, sim.cfg
    law_cfg = _pin_flow_cfg(sim.law_cfg)
    D = cfg.hist
    dt = cfg.dt
    F = flows.tau.shape[0]
    # the t*dt product feeds timer subtractions/adds downstream; blocked
    # against FMA contraction so every engine rounds it identically
    t_sec = _nofma(state.t.astype(jnp.float32) * dt)
    ptr = jnp.mod(state.t, D)
    bw = _bandwidth(topo, bw_fn, t_sec, sim.impair)           # [Q+1]
    # keep/jit only materialize under an impairment regime — None leaves
    # the compiled program byte-identical (mirrored by slot_step and the
    # megakernel tick; DESIGN.md section 17)
    keep, jit = (impair_vectors(t_sec, sim.impair)
                 if sim.impair is not None else (None, None))

    started = t_sec >= flows.start
    active = (started & (state.remaining > 0.0) & (t_sec < flows.stop))
    # -- instantaneous RTT and send rates ---------------------------------
    q_hop = state.q[flows.path]                               # [F,H]
    # pinned: a constant path would let XLA fold the gather and turn the
    # divisions below into reciprocal multiplies the slot engine (dynamic
    # path) never performs
    b_hop = _pin(bw[flows.path])
    valid = flows.path < topo.num_queues
    qb_now = q_hop / b_hop
    if jit is not None:
        # jitter is observed only once a flow has STARTED: the slot
        # engine admits a flow the tick its start is due, so a pre-start
        # flow is not resident there and sees the sentinel (0.0) jitter.
        qb_now = qb_now + jnp.where(started[:, None], jit[flows.path], 0.0)
    theta_now = flows.tau + _hop_sum(
        jnp.where(valid, qb_now, 0.0))
    lam = jnp.where(active,
                    jnp.minimum(jnp.minimum(_pin(state.w / theta_now),
                                            state.rate_cap),
                                flows.nic_rate), 0.0)

    # -- histories at current time ----------------------------------------
    hist_lam = state.hist_lam.at[ptr].set(lam)
    hist_w = state.hist_w.at[ptr].set(state.w)

    # -- queue update ------------------------------------------------------
    hop_delay_idx = jnp.mod(ptr - flows.tf_steps, D)          # [F,H]
    lam_del = hist_lam[hop_delay_idx, jnp.arange(F)[:, None]]  # [F,H]
    arr, out, q_new = _queue_update(topo, dt, sim.backend, sim.incidence,
                                    flows.path, state.q, lam_del, valid, bw,
                                    keep=keep)
    hist_q = state.hist_q.at[ptr].set(q_new)
    hist_out = state.hist_out.at[ptr].set(out)

    # -- feedback channels (only traced when the law declares them) --------
    if sim.law.uses_pause:
        pause_new = _pause_step(q_new, state.pause, law_cfg)
        hist_pause = state.hist_pause.at[ptr].set(pause_new)
    else:
        pause_new, hist_pause = None, None
    if sim.law.uses_incast:
        inc = _incast_count(state.q, flows.path, valid, lam_del)
        hist_inc = state.hist_inc.at[ptr].set(inc)
    else:
        hist_inc = None

    # -- delayed observation ------------------------------------------------
    # INT metadata of hop h is stamped when a segment *dequeues* there and
    # reaches the sender after the backward propagation delay
    # tb_h = rtt_prop - tf_h (paper section 3.3: "all values correspond to
    # the time when the packet is scheduled for transmission"). The RTT the
    # sender measures is reconstructed from the same snapshot:
    # theta = tau + sum_h q_obs_h / b_h. w_old (GETCWND of the acked seq) is
    # the window one measured-RTT ago. Laws with congestion-point feedback
    # (``Law.feedback == "hop"``) skip the receiver echo: the congested
    # switch notifies the sender directly over the reverse path, so hop h's
    # telemetry is only tf_h old — strictly younger than the receiver echo
    # on every real hop (DESIGN.md section 16).
    if sim.law.feedback == "hop":
        tb_steps = jnp.clip(flows.tf_steps, 1, D - 2)
    else:
        tb_steps = jnp.clip(flows.rtt_steps[:, None] - flows.tf_steps,
                            1, D - 2)
    ohidx = jnp.mod(ptr - tb_steps, D)                        # [F,H]
    ohprev = jnp.mod(ohidx - 1, D)
    fidx = jnp.arange(F)
    q_obs = hist_q[ohidx, flows.path]
    q_obs_prev = hist_q[ohprev, flows.path]
    # explicit reciprocal multiply: program variants disagree on whether
    # the divide-by-constant lowers to a division or a reciprocal
    # multiply; the multiply makes every engine round identically
    # (mirrored by megakernel.integrate_queues at write time). The
    # product is also contraction-blocked: it feeds the law's
    # current = qdot + mu add, which LLVM otherwise FMA-contracts in
    # some programs (fp-contract is on even without fast-math)
    qdot_obs = _nofma((q_obs - q_obs_prev) * (1.0 / dt))
    mu_obs = hist_out[ohidx, flows.path]
    qb_obs = q_obs / b_hop
    if jit is not None:
        # same started-gating as qb_now above
        qb_obs = qb_obs + jnp.where(started[:, None], jit[flows.path], 0.0)
    theta_obs = flows.tau + _hop_sum(
        jnp.where(valid, qb_obs, 0.0))
    wold_delay = jnp.clip(jnp.round(theta_obs / dt).astype(jnp.int32),
                          1, D - 2)
    w_old = hist_w[jnp.mod(ptr - wold_delay, D), fidx]
    buf_hop = jnp.concatenate(
        [topo.buffer, jnp.asarray([1e30], jnp.float32)])[flows.path]
    ecn = jnp.max(jnp.where(valid, _marking(q_obs, buf_hop, law_cfg), 0.0),
                  axis=1)

    upd = active & (t_sec >= state.next_update)
    dt_obs = jnp.maximum(t_sec - state.last_update, dt)
    obs = PathObs(q=q_obs, qdot=qdot_obs, mu=mu_obs, b=b_hop,
                  valid=valid, theta=theta_obs, w_old=w_old, dt_obs=dt_obs,
                  ecn_frac=ecn,
                  pause=(hist_pause[ohidx, flows.path]
                         if sim.law.uses_pause else None),
                  incast=(hist_inc[ohidx, flows.path]
                          if sim.law.uses_incast else None))

    # -- control-law update (dispatches through the law's bound backend) ---
    law_state, w, rate_cap = sim.law.update(
        state.law, obs, state.w, state.rate_cap, upd, law_cfg, t_sec)
    w = jnp.clip(w, MTU, _nofma(_pin(8.0 * flows.nic_rate * flows.tau)) +
                 _nofma(_pin(8.0 * flows.nic_rate * theta_now)))
    # a flow that has not started has no window to drive: hold the init
    # carry so the slot engine's admission re-init (w = nic*tau in
    # ``_admit_retire``) lands on the same bits.  Masked laws leave
    # pre-start w at init anyway; this pins the masked_updates=False
    # case (retcp's circuit multiplier would otherwise pre-scale the
    # window before admission, visible the tick the flow starts).
    w = jnp.where(started, w, state.w)
    period = jnp.where(cfg.update_period > 0.0, cfg.update_period, theta_now)
    next_update = jnp.where(upd, t_sec + period, state.next_update)
    last_update = jnp.where(upd, t_sec, state.last_update)

    if alloc_fn is not None:
        rate_cap = alloc_fn(state.remaining, active, t_sec, flows, rate_cap)

    # -- flow progress ------------------------------------------------------
    # under loss only the surviving fraction of a flow's rate is goodput
    # (the path survival product; exact 1.0 when keep is all-ones)
    lam_good = lam if keep is None else lam * _hop_keep(keep, flows.path,
                                                        valid)
    remaining = jnp.where(active,
                          state.remaining - _nofma(_pin(lam_good * dt)),
                          state.remaining)
    done = active & (remaining <= 0.0)
    # tau/start are compile-time constants here; pinned so XLA cannot
    # fold (tau/2 - start) into one constant — the slot engine (dynamic
    # values) rounds the sequential (t_sec + tau/2) - start, and the
    # bit-for-bit anchor needs both engines on the same association
    fct = jnp.where(done & jnp.isnan(state.fct),
                    t_sec + _nofma(_pin(flows.tau / 2.0)) -
                    _pin(flows.start),
                    state.fct)

    new_state = SimState(
        t=state.t + 1, w=w, rate_cap=rate_cap, q=q_new, out_rate=out,
        hist_lam=hist_lam, hist_q=hist_q, hist_out=hist_out, hist_w=hist_w,
        remaining=remaining, fct=fct,
        next_update=next_update, last_update=last_update, law=law_state,
        pause=pause_new, hist_pause=hist_pause, hist_inc=hist_inc)
    rec = Record(t=t_sec, q=q_new, w_sum=jnp.sum(jnp.where(active, w, 0.0)),
                 thru=out, lam=jnp.sum(lam), lam_f=lam,
                 n_active=jnp.sum(active.astype(jnp.int32)))
    return new_state, rec


def _make_sim(topo: Topology, flows: Flows, law: Law, law_cfg: LawConfig,
              cfg: SimConfig, backend: str, impair=None) -> FluidSim:
    incidence = (build_incidence(flows, topo.num_queues)
                 if backend == "fused" else None)
    return FluidSim(topo, flows, law, law_cfg, cfg, backend, incidence,
                    impair)


def _check_impair(impair, bw_fn, backend: str):
    """Shared driver validation for the impairment seam: the fused (dense
    Pallas) backend rejects impairments outright (its incidence matmul
    reassociates the arrival sums, so the bit-for-bit loss fold has no
    home there), and ``bw_fn`` + ``impair`` would be two owners of the
    same bandwidth vector."""
    if impair is None:
        return
    if backend == "fused":
        raise UnsupportedFeature(
            "impairments are not supported on the fused backend (its "
            "incidence matmul reassociates the arrival sums, so the "
            "bit-for-bit loss fold has no home there)",
            hint="use the reference or megakernel backend")
    if bw_fn is not None:
        raise ValueError("bw_fn and impair are mutually exclusive "
                         "bandwidth drivers (wrap the schedule as a "
                         "KIND_SCHEDULE impairment process instead)")


def _scan_scenario(sim, state, bw_fn, alloc_fn, record: bool, step_fn=None):
    """lax.scan over cfg.steps; honours cfg.record_every by scanning chunks
    (one record per chunk, the chunk's last step) so the recording memory
    shrinks by the subsample factor. steps must divide by record_every.
    ``step_fn`` selects the engine (padded ``step`` by default,
    ``slot_step`` for the flow-slot streaming engine)."""
    cfg = sim.cfg
    step_fn = step_fn or step
    k = max(int(cfg.record_every), 1) if record else 1

    def body(st, _):
        st, rec = step_fn(sim, st, bw_fn=bw_fn, alloc_fn=alloc_fn)
        return st, (rec if record else None)

    if k <= 1:
        return jax.lax.scan(body, state, None, length=cfg.steps)

    if cfg.steps % k:
        raise ValueError(f"steps ({cfg.steps}) must be divisible by "
                         f"record_every ({k})")

    def chunk(st, _):
        st = jax.lax.fori_loop(
            0, k - 1, lambda _, s: step_fn(sim, s, bw_fn=bw_fn,
                                           alloc_fn=alloc_fn)[0], st)
        return body(st, None)

    return jax.lax.scan(chunk, state, None, length=cfg.steps // k)


def _resolve_law(law: Union[str, Law], backend: str) -> Law:
    """Accept a law name (resolved through the registry) or a prebuilt
    ``Law`` (already bound to an implementation, e.g. a custom wrapper)."""
    return law if isinstance(law, Law) else get_law(law, backend)


def audit_carry_dtypes(state) -> None:
    """Assert every scan-carry leaf is float32/int32 (trace-time check).

    A stray float64/int64 leaf would silently double the carried state in
    HBM (and double-buffer through the whole scan); catching it at init
    keeps long traces at their audited footprint. Boolean leaves are fine
    (1 byte)."""
    ok = (jnp.float32, jnp.int32, jnp.bool_)
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if leaf is None:
            continue
        # read the dtype without materializing (works on tracers) and
        # without jnp.asarray (which would silently downcast the very
        # float64 leaves the audit exists to catch)
        dtype = getattr(leaf, "dtype", None) or jnp.asarray(leaf).dtype
        if dtype not in ok:
            raise TypeError(
                f"scan carry leaf {jax.tree_util.keystr(path)} has dtype "
                f"{dtype}; expected float32/int32 "
                f"(HBM double-buffering audit)")


def simulate(topo: Topology, flows: Flows, law_name: Union[str, Law],
             law_cfg: Optional[LawConfig] = None,
             cfg: Optional[SimConfig] = None,
             bw_fn: Optional[Callable] = None,
             alloc_fn: Optional[Callable] = None,
             record: bool = True,
             backend: str = "reference",
             impair: Optional[ImpairmentParams] = None):
    """Run a scenario to completion. Returns (final_state, Record pytree).

    The whole scenario (topology, flows, law) is closed over and jitted as a
    unit; hist buffers live in the carried state so the scan is O(1) memory.
    ``backend="fused"`` dispatches the law update and the queue-arrival
    scatter through the Pallas kernels (see module docstring);
    ``backend="megakernel"`` resolves (every law carries a
    kernel-composable entry) but the whole-tick fused engine is a SLOT
    path — on this padded engine it degrades to the reference ops, same
    program, same bits (DESIGN.md section 13). ``law_name`` may also be
    a prebuilt ``Law``.
    """
    cfg = cfg or SimConfig()
    _check_impair(impair, bw_fn, backend)
    law = _resolve_law(law_name, backend)
    law_cfg = law_cfg or default_law_config(flows)
    sim = _make_sim(topo, flows, law, law_cfg, cfg, backend, impair=impair)
    state = init_state(sim)

    @jax.jit
    def run(st):
        return _scan_scenario(sim, st, bw_fn, alloc_fn, record)

    final, recs = run(state)
    return final, recs


# --------------------------------------------------------------------------
# Flow-slot streaming engine (DESIGN.md section 12)
# --------------------------------------------------------------------------

class SlotSim(NamedTuple):
    """One schedule bound to a slot pool and a backend.

    ``slots`` (S) is the static pool size: per-tick cost is O(S * hops)
    regardless of how many flows the schedule holds in total. ``backend``
    selects the queue-update implementation exactly as in ``FluidSim``;
    the fused incidence is [H, S, Q+1]-sized and lives in the scan state
    (rebuilt by masked dynamic-update on admission, see
    ``kernels.queue_arrivals.update_incidence``).

    Chunk-streamed runs (``simulate_slots(..., chunk=)``, DESIGN.md
    section 15) bind ``sched`` to a C-sized WINDOW of the full schedule
    instead of the whole trace: ``win_off`` is then the window's global
    base index (an int32 scalar, traced) and ``n_flows`` the full
    schedule's flow count N — sentinels (``slot_flow == N``), the [N]
    FCT output and the [N]-leaf LawConfig gathers all keep their global
    meaning while the O(N * H) hop table streams through in windows.
    Both stay None on whole-trace runs.
    """
    topo: Topology
    sched: FlowSchedule
    law: Law
    law_cfg: LawConfig
    cfg: SimConfig
    slots: int
    backend: str = "reference"
    n_flows: Optional[int] = None
    win_off: Optional[jnp.ndarray] = None
    # per-link impairment regime (core/impair.py); rides unchanged through
    # the chunk driver's window _replace
    impair: Optional[ImpairmentParams] = None


def _slot_n(sim: SlotSim) -> int:
    """Global flow count N: the full schedule's, even when ``sim.sched``
    is a chunk window."""
    if sim.n_flows is not None:
        return int(sim.n_flows)
    return int(sim.sched.start.shape[0])


def _gather_law_cfg(law_cfg: LawConfig, gf: jnp.ndarray, n_flows: int):
    """Per-slot view of a LawConfig: leaves with an [N] flow axis are
    gathered at ``gf`` (the pool's current schedule indices, clamped);
    scalars and non-flow pytrees (e.g. ``sched``) pass through."""
    def g(leaf):
        x = jnp.asarray(leaf)
        if x.ndim >= 1 and x.shape[0] == n_flows:
            return x[gf]
        return leaf
    return jax.tree_util.tree_map(g, law_cfg)


def init_slot_state(sim: SlotSim) -> SlotState:
    """All slots free; pool metadata holds the same inert values as
    ``pad_flows`` so empty slots never send and never NaN."""
    topo, sched, cfg = sim.topo, sim.sched, sim.cfg
    S = int(sim.slots)
    N = _slot_n(sim)
    H = int(sched.path.shape[1])
    Q = topo.num_queues
    D = cfg.hist
    tau0 = jnp.full((S,), 20e-6, jnp.float32)
    nic0 = jnp.full((S,), 1e9, jnp.float32)
    w0 = nic0 * tau0
    cfg0 = _gather_law_cfg(sim.law_cfg, jnp.zeros((S,), jnp.int32), N)
    incidence = (jnp.zeros((H, S, Q + 1), jnp.float32)
                 if sim.backend == "fused" else None)
    return SlotState(
        t=jnp.asarray(0, jnp.int32),
        cursor=jnp.asarray(0, jnp.int32),
        hw=jnp.asarray(0, jnp.int32),
        slot_flow=jnp.full((S,), N, jnp.int32),
        admit_t=jnp.zeros((S,), jnp.int32),
        free_at=jnp.zeros((S,), jnp.int32),
        path=jnp.full((S, H), Q, jnp.int32),
        tf_steps=jnp.ones((S, H), jnp.int32),
        rtt_steps=jnp.ones((S,), jnp.int32),
        tau=tau0, nic_rate=nic0,
        start=jnp.full((S,), jnp.inf, jnp.float32),
        stop=jnp.full((S,), jnp.inf, jnp.float32),
        w=w0,
        rate_cap=jnp.full((S,), jnp.inf, jnp.float32),
        q=jnp.zeros((Q + 1,), jnp.float32),
        out_rate=jnp.zeros((Q + 1,), jnp.float32),
        hist_lam=jnp.zeros((D, S), jnp.float32),
        hist_q=jnp.zeros((D, Q + 1), jnp.float32),
        hist_out=jnp.zeros((D, Q + 1), jnp.float32),
        hist_w=jnp.broadcast_to(w0, (D, S)).astype(jnp.float32),
        remaining=jnp.full((S,), jnp.inf, jnp.float32),
        next_update=jnp.full((S,), jnp.inf, jnp.float32),
        last_update=jnp.zeros((S,), jnp.float32),
        law=sim.law.init(S, cfg0),
        fct=jnp.full((N,), jnp.nan, jnp.float32),
        incidence=incidence,
        # feedback channels (mirror of init_state: None unless declared)
        pause=(jnp.zeros((Q + 1,), jnp.float32)
               if sim.law.uses_pause else None),
        hist_pause=(jnp.zeros((D, Q + 1), jnp.float32)
                    if sim.law.uses_pause else None),
        hist_inc=(jnp.zeros((D, Q + 1), jnp.float32)
                  if sim.law.uses_incast else None),
    )


def _admit_retire(sim: SlotSim, state: SlotState, t_sec, due=None):
    """The per-tick admit/retire pass (pure, jittable, O(S + log N)).

    Retire: slots whose occupant completed (or passed ``stop``) AND whose
    in-flight traffic has drained (``t >= free_at``) return to the pool.
    Admit: due arrivals (``start <= t``, a binary search against the
    sorted schedule — or the precomputed ``due`` count when the caller
    already holds the whole-trace table, see ``megakernel._due_table``)
    fill free slots, fresh-never-used slots first
    (ascending), recycled slots only when fresh ones run out. While
    ``S >= total_flows`` this maps schedule entry i to slot i, which is
    what makes the padded-engine equivalence bit-for-bit — the queue
    scatter-add then accumulates contributions in the identical order.
    Admitted slots gather the flow's metadata, reset window/config state
    exactly as ``init_state`` would, and re-init the law's state pytree
    entries (``law.init`` against the slot-gathered config).

    Chunk windows (``sim.win_off`` set): the binary search runs against
    the C-sized window and is rebased by the window's global offset —
    bit-identical to the full-schedule search whenever no entry beyond
    the window is due, which the chunk driver guarantees by segment
    construction (DESIGN.md section 15). Metadata gathers use the
    window-local index; the LawConfig gather keeps the global index
    (those [N] leaves stay resident, see ``SlotSim``).
    """
    sched = sim.sched
    S = int(state.w.shape[0])
    N = _slot_n(sim)
    sidx = jnp.arange(S, dtype=jnp.int32)

    occupied = state.slot_flow < N
    freeable = occupied & (state.t >= state.free_at)
    slot_flow = jnp.where(freeable, N, state.slot_flow)
    occupied = slot_flow < N

    if due is None:
        due = jnp.searchsorted(sched.start, t_sec,
                               side="right").astype(jnp.int32)
        if sim.win_off is not None:
            due = sim.win_off + due
    n_free = S - jnp.sum(occupied.astype(jnp.int32))
    n_admit = jnp.minimum(due - state.cursor, n_free)
    free = ~occupied
    fresh = free & (sidx >= state.hw)
    n_fresh = jnp.minimum(n_admit, jnp.sum(fresh.astype(jnp.int32)))
    take_fresh = fresh & (jnp.cumsum(fresh.astype(jnp.int32)) - 1 < n_fresh)
    recycled = free & (sidx < state.hw)
    take_rec = recycled & (jnp.cumsum(recycled.astype(jnp.int32)) - 1 <
                           n_admit - n_fresh)
    admit = take_fresh | take_rec
    rank = jnp.cumsum(admit.astype(jnp.int32)) - 1
    slot_flow = jnp.where(admit, state.cursor + rank, slot_flow)

    gf = jnp.clip(slot_flow, 0, N - 1)
    if sim.win_off is None:
        gw = gf
    else:
        # window-local gather index; rows not admitted this tick may
        # gather arbitrary window entries, all masked out by ``sel``
        gw = jnp.clip(slot_flow - sim.win_off, 0,
                      int(sched.start.shape[0]) - 1)

    def sel(new, old):
        m = admit.reshape(admit.shape + (1,) * (old.ndim - 1))
        return jnp.where(m, new, old)

    tau = sel(sched.tau[gw], state.tau)
    nic = sel(sched.nic_rate[gw], state.nic_rate)
    start = sel(sched.start[gw], state.start)
    cfg_slot = _gather_law_cfg(sim.law_cfg, gf, N)
    fresh_law = sim.law.init(S, cfg_slot)
    law_state = jax.tree_util.tree_map(
        lambda f, o: jnp.where(
            admit.reshape(admit.shape + (1,) * (o.ndim - 1)), f, o),
        fresh_law, state.law)
    state = state._replace(
        slot_flow=slot_flow,
        cursor=state.cursor + n_admit,
        hw=state.hw + n_fresh,
        admit_t=jnp.where(admit, state.t, state.admit_t),
        free_at=jnp.where(admit, _INT32_MAX, state.free_at),
        path=sel(sched.path[gw], state.path),
        tf_steps=sel(sched.tf_steps[gw], state.tf_steps),
        rtt_steps=sel(sched.rtt_steps[gw], state.rtt_steps),
        tau=tau, nic_rate=nic, start=start,
        stop=sel(sched.stop[gw], state.stop),
        w=sel(nic * tau, state.w),
        rate_cap=sel(jnp.full((S,), jnp.inf, jnp.float32), state.rate_cap),
        remaining=sel(sched.size[gw].astype(jnp.float32), state.remaining),
        next_update=sel((start + tau).astype(jnp.float32),
                        state.next_update),
        last_update=sel(start.astype(jnp.float32), state.last_update),
        law=law_state,
    )
    if sim.backend == "fused" and state.incidence is not None:
        state = state._replace(incidence=update_incidence(
            state.incidence, state.path, admit, sim.topo.num_queues))
    return state, occupied | admit


def slot_step(sim: SlotSim, state: SlotState, bw_fn=None, alloc_fn=None):
    """One tick of the flow-slot streaming engine.

    Identical arithmetic to ``step`` on the S-sized pool, plus the
    admit/retire pass and two occupancy guards on the delayed ring-buffer
    reads: a slot's history older than its occupant's admission reads as
    the ring-init values (0 for rates, the initial window for ``w_old``)
    — exactly what the padded engine's pre-start history holds — so the
    previous occupant's traffic is never observed and no O(D*S) history
    reset is needed on admission. Retirement is deferred until the
    occupant's in-flight traffic has drained (``free_at``; its delayed
    rates are zero from then on), so queues see the same tail the padded
    engine delivers. ``alloc_fn`` is not supported on the slot path
    (receiver-grant bookkeeping is tied to a static flow set).
    """
    if alloc_fn is not None:
        raise ValueError("alloc_fn is not supported on the slot path")
    topo, cfg = sim.topo, sim.cfg
    S = int(state.w.shape[0])
    N = _slot_n(sim)
    D = cfg.hist
    dt = cfg.dt
    with jax.named_scope("rates"):
        t_sec = _nofma(state.t.astype(jnp.float32) * dt)   # mirror of step()
        ptr = jnp.mod(state.t, D)
        bw = _bandwidth(topo, bw_fn, t_sec, sim.impair)           # [Q+1]
        keep, jit = (impair_vectors(t_sec, sim.impair)
                     if sim.impair is not None else (None, None))
        sidx = jnp.arange(S)

    # -- admit / retire ----------------------------------------------------
    with jax.named_scope("admit"):
        state, occupied = _admit_retire(sim, state, t_sec)
        (path, tf_steps, tau, nic) = (state.path, state.tf_steps, state.tau,
                                      state.nic_rate)
        gf = jnp.clip(state.slot_flow, 0, N - 1)
        cfg_slot = _gather_law_cfg(sim.law_cfg, gf, N)

    with jax.named_scope("rates"):
        active = (occupied & (t_sec >= state.start) & (state.remaining > 0.0) &
                  (t_sec < state.stop))
        # -- instantaneous RTT and send rates -----------------------------
        q_hop = state.q[path]                                     # [S,H]
        b_hop = _pin(bw[path])            # mirror of the padded engine's pin
        valid = path < topo.num_queues
        qb_now = q_hop / b_hop
        if jit is not None:
            qb_now = qb_now + jit[path]
        theta_now = tau + _hop_sum(
            jnp.where(valid, qb_now, 0.0))
        lam = jnp.where(active,
                        jnp.minimum(jnp.minimum(_pin(state.w / theta_now),
                                                state.rate_cap),
                                    nic), 0.0)

        # -- histories at current time ------------------------------------
        hist_lam = state.hist_lam.at[ptr].set(lam)
        hist_w = state.hist_w.at[ptr].set(state.w)

    # -- queue update (reads older than admission are the prior occupant's
    #    — they are exactly 0 by the free_at drain guarantee, and the mask
    #    also reproduces the padded engine's all-zero pre-start history) --
    with jax.named_scope("queue"):
        hop_delay_idx = jnp.mod(ptr - tf_steps, D)                # [S,H]
        lam_del = hist_lam[hop_delay_idx, sidx[:, None]]          # [S,H]
        lam_del = jnp.where(state.t - tf_steps >= state.admit_t[:, None],
                            lam_del, 0.0)
        arr, out, q_new = _queue_update(topo, dt, sim.backend, state.incidence,
                                        path, state.q, lam_del, valid, bw,
                                        keep=keep)
        hist_q = state.hist_q.at[ptr].set(q_new)
        hist_out = state.hist_out.at[ptr].set(out)

        # -- feedback channels (mirror of step: gated at trace time) ------
        if sim.law.uses_pause:
            pause_new = _pause_step(q_new, state.pause, cfg_slot)
            hist_pause = state.hist_pause.at[ptr].set(pause_new)
        else:
            pause_new, hist_pause = None, None
        if sim.law.uses_incast:
            inc = _incast_count(state.q, path, valid, lam_del)
            hist_inc = state.hist_inc.at[ptr].set(inc)
        else:
            hist_inc = None

    # -- delayed observation (see step; w_old before admission is the
    #    occupant's initial window, the padded engine's ring-init) --------
    with jax.named_scope("observe"):
        if sim.law.feedback == "hop":
            tb_steps = jnp.clip(tf_steps, 1, D - 2)
        else:
            tb_steps = jnp.clip(state.rtt_steps[:, None] - tf_steps, 1, D - 2)
        ohidx = jnp.mod(ptr - tb_steps, D)                        # [S,H]
        ohprev = jnp.mod(ohidx - 1, D)
        q_obs = hist_q[ohidx, path]
        q_obs_prev = hist_q[ohprev, path]
        qdot_obs = _nofma((q_obs - q_obs_prev) * (1.0 / dt))  # mirror of step
        mu_obs = hist_out[ohidx, path]
        qb_obs = q_obs / b_hop
        if jit is not None:
            qb_obs = qb_obs + jit[path]
        theta_obs = tau + _hop_sum(
            jnp.where(valid, qb_obs, 0.0))
        wold_delay = jnp.clip(jnp.round(theta_obs / dt).astype(jnp.int32),
                              1, D - 2)
        w_old = hist_w[jnp.mod(ptr - wold_delay, D), sidx]
        w_old = jnp.where(state.t - wold_delay >= state.admit_t, w_old,
                          nic * tau)
        buf_hop = jnp.concatenate(
            [topo.buffer, jnp.asarray([1e30], jnp.float32)])[path]
        ecn = jnp.max(jnp.where(valid, _marking(q_obs, buf_hop, cfg_slot),
                                0.0), axis=1)

        upd = active & (t_sec >= state.next_update)
        dt_obs = jnp.maximum(t_sec - state.last_update, dt)
        obs = PathObs(q=q_obs, qdot=qdot_obs, mu=mu_obs, b=b_hop,
                      valid=valid, theta=theta_obs, w_old=w_old, dt_obs=dt_obs,
                      ecn_frac=ecn,
                      pause=(hist_pause[ohidx, path]
                             if sim.law.uses_pause else None),
                      incast=(hist_inc[ohidx, path]
                              if sim.law.uses_incast else None))

    # -- control-law update (slot-gathered config) ------------------------
    with jax.named_scope("law"):
        law_state, w, rate_cap = sim.law.update(
            state.law, obs, state.w, state.rate_cap, upd, cfg_slot, t_sec)
        w = jnp.clip(w, MTU, _nofma(_pin(8.0 * nic * tau)) +
                     _nofma(_pin(8.0 * nic * theta_now)))
        period = jnp.where(cfg.update_period > 0.0, cfg.update_period,
                           theta_now)
        next_update = jnp.where(upd, t_sec + period, state.next_update)
        last_update = jnp.where(upd, t_sec, state.last_update)

    # -- flow progress; FCT scatters to the schedule-ordered [N] output ---
    with jax.named_scope("progress"):
        lam_good = lam if keep is None else lam * _hop_keep(keep, path, valid)
        remaining = jnp.where(active,
                              state.remaining - _nofma(_pin(lam_good * dt)),
                              state.remaining)
        done = active & (remaining <= 0.0)
        fct = state.fct.at[jnp.where(done, state.slot_flow, N)].set(
            jnp.where(done, t_sec + _nofma(tau / 2.0) - state.start, jnp.nan),
            mode="drop")
        # hold the slot until the flow's tail has drained into the queues
        hold = jnp.max(jnp.where(valid, tf_steps, 0), axis=1)
        expire = (occupied & (t_sec >= state.stop) &
                  (state.free_at == _INT32_MAX) & ~done)
        free_at = jnp.where(done | expire, state.t + hold + 1, state.free_at)

        new_state = state._replace(
            t=state.t + 1, w=w, rate_cap=rate_cap, q=q_new, out_rate=out,
            hist_lam=hist_lam, hist_q=hist_q, hist_out=hist_out, hist_w=hist_w,
            remaining=remaining, fct=fct, free_at=free_at,
            next_update=next_update, last_update=last_update, law=law_state,
            pause=pause_new, hist_pause=hist_pause, hist_inc=hist_inc)
        rec = Record(t=t_sec, q=q_new,
                     w_sum=jnp.sum(jnp.where(active, w, 0.0)),
                     thru=out, lam=jnp.sum(lam), lam_f=lam,
                     n_active=jnp.sum(active.astype(jnp.int32)))
    return new_state, rec


# --------------------------------------------------------------------------
# Chunk-streamed schedules (DESIGN.md section 15)
# --------------------------------------------------------------------------

def _host_window(sched_np: FlowSchedule, w0: int, chunk: int,
                 pad_queue: int) -> FlowSchedule:
    """C-sized window ``sched[w0:w0+C]`` (host-side slice), padded with
    inert ``pad_schedule`` entries past the schedule's end so every
    segment program shares one shape."""
    n = int(sched_np.start.shape[0])
    end = min(w0 + chunk, n)
    win = jax.tree_util.tree_map(lambda x: x[w0:end], sched_np)
    if end - w0 < chunk:
        win = pad_schedule(win, chunk, pad_queue)
    return win


def _safe_ticks(start_np: np.ndarray, w0: int, chunk: int, t0: int,
                t_end: int, dt: float) -> int:
    """Ticks from ``t0`` during which no schedule entry beyond the window
    ``[w0, w0+C)`` becomes due — within them the window-rebased admission
    search is bit-identical to the full-schedule search. 0 means entry
    ``w0+C`` is already due at ``t0``; the driver then runs a single tick
    (exact because C >= S caps the per-tick admission count at the free
    pool, see ``simulate_slots``)."""
    n = int(start_np.shape[0])
    if w0 + chunk >= n:
        return t_end - t0
    lim = np.float32(start_np[w0 + chunk])
    if not np.isfinite(lim):
        return t_end - t0
    # t_sec(t) = f32(t) * f32(dt): the exact product the engines compute
    # (monotone nondecreasing in t); find the first due tick by bisection
    dtf = np.float32(dt)

    def f(t):
        return np.float32(t) * dtf

    if f(t0) >= lim:
        return 0
    if f(t_end - 1) < lim:
        return t_end - t0
    lo, hi = t0, t_end - 1            # f(lo) < lim <= f(hi)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if f(mid) >= lim:
            hi = mid
        else:
            lo = mid
    return hi - t0


_CHUNK_SEG_MAX = 4096                 # longest single segment (ticks)


def _simulate_slots_chunked(sim: SlotSim, chunk: int, bw_fn, record: bool,
                            checkpoint: Optional[CheckpointSpec] = None,
                            faults: Optional[FaultSpec] = None,
                            guard: bool = False,
                            resume: bool = False,
                            resume_tick: Optional[int] = None):
    """Host-driven segment loop: the jitted inner program advances L ticks
    against a C-sized schedule window; between segments the cursor is
    fetched and the window re-anchored at it. Segment lengths are chosen
    so the window-rebased admission is provably bit-identical to the
    single-shot run (``_safe_ticks``), and are rounded down to powers of
    two so the whole trace compiles at most log2(seg_max) inner programs.
    Carried state (pool, queues, telemetry rings, megakernel carry)
    crosses segment boundaries unchanged — only the O(N * H) schedule is
    windowed; the [N] FCT output and [N]-leaf LawConfig stay resident
    (the knife-edge constraint of ``megakernel.MegaCarry`` forbids
    routing the float config gather through carried state).

    Segment boundaries are also the fault-tolerance seam (DESIGN.md
    section 18): ``checkpoint`` snapshots the full carry (and the
    recorded trace so far) at boundaries — cadence multiples of
    ``checkpoint.every`` are hit EXACTLY because the pow2-floored
    segment decomposition converges onto any bound it is clamped to;
    ``guard`` runs the divergence finite-check at each boundary (where
    the host already pays the cursor sync); ``faults`` injects a
    deterministic ``InjectedCrash`` after the boundary's checkpoint is
    written. ``resume=True`` restores the newest (or ``resume_tick``)
    snapshot into the init-built carry template and continues — bit-
    for-bit identical to the uninterrupted run, because resuming only
    changes the segmentation of the remaining ticks and the trajectory
    is invariant to segmentation (the chunk-stream exactness property).
    """
    cfg = sim.cfg
    if record and int(cfg.record_every) > 1:
        raise ValueError("chunk-streamed runs record every tick; "
                         "record_every > 1 is not supported with chunk=")
    if sim.backend == "fused":
        raise ValueError("chunk= is not supported on the fused backend")
    mega = sim.backend == "megakernel"
    with obs.span("slots.prepare"):
        sched_np = jax.tree_util.tree_map(np.asarray, sim.sched)
        N = int(sched_np.start.shape[0])
        S = int(sim.slots)
        Q = int(sim.topo.num_queues)
        T = int(cfg.steps)
        # C >= S makes the 1-tick fallback exact: one tick admits at most
        # n_free <= S entries, which the C-clamped due count never truncates
        C = min(max(int(chunk), S), max(N, 1))
        start_np = np.asarray(sched_np.start, np.float32)
        first = _host_window(sched_np, 0, C, Q)
        if mega:
            from .megakernel import make_tick, _unpack_state
            maxdeg = suggest_maxdeg(sched_np.path, Q, S)

    def make_simw(win, w0):
        return sim._replace(sched=win, n_flows=N, win_off=w0)

    @jax.jit
    def init(win):
        simw = make_simw(win, jnp.asarray(0, jnp.int32))
        state = init_slot_state(simw)
        audit_carry_dtypes(state)
        if mega:
            return make_tick(simw, bw_fn, gate=True,
                             maxdeg=maxdeg).init_carry(state)
        return state

    seg_cache = {}

    def get_seg(L):
        if L in seg_cache:
            return seg_cache[L]

        @jax.jit
        def seg(carry, win, w0):
            simw = make_simw(win, w0)
            if mega:
                tick = make_tick(simw, bw_fn, gate=True, maxdeg=maxdeg)
                # global tick indices: bit-identical to _due_table's
                # f32(t) * dt grid, rebased by the window offset
                t_grid = ((carry.state.t +
                           jnp.arange(L, dtype=jnp.int32))
                          .astype(jnp.float32) * cfg.dt)
                due = w0 + jnp.searchsorted(
                    win.start, t_grid, side="right").astype(jnp.int32)

                def body(c, d):
                    c, rec = tick(c, d)
                    return c, (rec if record else None)

                return jax.lax.scan(body, carry, due)

            def body(st, _):
                st, rec = slot_step(simw, st, bw_fn=bw_fn)
                return st, (rec if record else None)

            return jax.lax.scan(body, carry, None, length=L)

        seg_cache[L] = seg
        return seg

    with obs.span("slots.call", program="init", ticks=0):
        carry = init(first)
    recs = []
    t0 = 0
    seg_idx = 0
    scenario_meta = dict(law=sim.law.name, steps=T, slots=S, flows=N,
                         mega=mega)
    if resume:
        from . import ckpt as _ckpt
        if checkpoint is None:
            raise ValueError("resume requires a CheckpointSpec")
        tick_r = (int(resume_tick) if resume_tick is not None
                  else _ckpt.latest_checkpoint(checkpoint.path))
        if tick_r is None:
            raise FileNotFoundError(
                f"no ckpt-*.npz snapshot in {checkpoint.path!r}")
        rec_template = (Record(*([0] * len(Record._fields)))
                        if record else None)
        meta, carry, recs0 = _ckpt.load_checkpoint(
            checkpoint.path, tick_r, carry, rec_template=rec_template)
        saved = {k: meta.get(k) for k in scenario_meta}
        if saved != scenario_meta:
            raise ValueError(
                f"checkpoint scenario mismatch: snapshot was written by "
                f"{saved}, resume was asked for {scenario_meta} — "
                f"resume_slots must be called with the original run's "
                f"scenario arguments")
        if record:
            recs.append(recs0)
        t0 = int(meta["tick"])

    crash_tick = faults.crash_tick if faults is not None else None
    crash_seg = faults.crash_segment if faults is not None else None
    every = int(checkpoint.every) if checkpoint is not None else 0

    def maybe_checkpoint(t_now):
        if checkpoint is None:
            return
        if every > 0 and t_now % every != 0 and t_now < T:
            return
        from . import ckpt as _ckpt
        rcat = (jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
            *recs) if record else None)
        _ckpt.save_checkpoint(checkpoint, t_now, carry, recs=rcat,
                              meta=dict(scenario_meta, record=record))

    while t0 < T:
        cursor = (carry.state.cursor if mega else carry.cursor)
        with obs.span("chunk.sync"):
            w0 = int(jax.device_get(cursor))
        with obs.span("chunk.window"):
            safe = _safe_ticks(start_np, w0, C, t0, T, cfg.dt)
            allowed = max(1, min(max(safe, 1), T - t0, _CHUNK_SEG_MAX))
            # clamping the segment to the next cadence multiple / crash
            # tick keeps boundaries landing EXACTLY on them: the pow2
            # floor below only shortens segments, and repeated shortening
            # converges onto the clamp (e.g. 1000 = 512+256+128+64+32+8)
            if every > 0:
                allowed = min(allowed, ((t0 // every) + 1) * every - t0)
            if crash_tick is not None and t0 < crash_tick:
                allowed = min(allowed, crash_tick - t0)
            L = 1 << (allowed.bit_length() - 1)       # pow2 floor, >= 1
            win = _host_window(sched_np, w0, C, Q)
        with obs.span("slots.call", program="segment", ticks=L):
            carry, rec = get_seg(L)(carry, win, jnp.asarray(w0, jnp.int32))
        obs.count("chunk.segments")
        obs.count("slots.ticks", L)
        if record:
            recs.append(rec)
        t0 += L
        seg_idx += 1
        if guard:
            from .guard import check_divergence
            check_divergence(carry.state if mega else carry,
                             sim.law.name, t0)
        maybe_checkpoint(t0)
        # the crash fires AFTER the boundary's checkpoint write: the
        # injected failure models the process dying after its last
        # durable snapshot, the worst recoverable case
        if crash_tick is not None and t0 >= crash_tick:
            raise InjectedCrash(t0, seg_idx)
        if crash_seg is not None and seg_idx >= crash_seg:
            raise InjectedCrash(t0, seg_idx)

    with obs.span("slots.finish"):
        if record:
            recs = jax.tree_util.tree_map(
                lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
                *recs)
        else:
            recs = None
        if mega:
            return _unpack_state(carry, N, Q + 1), recs
        return carry, recs


def simulate_slots(topo: Topology, sched: FlowSchedule,
                   law_name: Union[str, Law], slots: int,
                   law_cfg: Optional[LawConfig] = None,
                   cfg: Optional[SimConfig] = None,
                   bw_fn: Optional[Callable] = None,
                   record: bool = True,
                   backend: str = "reference",
                   chunk: Optional[int] = None,
                   impair: Optional[ImpairmentParams] = None,
                   checkpoint: Optional[CheckpointSpec] = None,
                   faults: Optional[FaultSpec] = None,
                   guard: bool = False):
    """Run a schedule through a bounded pool of ``slots`` active slots.

    Returns (final ``SlotState``, ``Record`` pytree); ``final.fct`` is [N]
    in SCHEDULE order (join back to unsorted flows via ``sched.order``).
    With ``slots >= N`` this reproduces the queue and FCT trajectories of
    ``simulate`` on ``network.schedule_as_flows(sched)`` bit-for-bit
    (windows to <= 1 ulp; DESIGN.md section 12); smaller pools
    admission-delay flows that arrive while the pool is full (size with
    ``workload.suggest_slots``). ``law_cfg`` leaves with an [N] flow axis
    are gathered into slots on admission.

    ``backend="megakernel"`` (DESIGN.md section 13) advances the run in
    K-tick fused blocks (``core.megakernel``) — bit-identical
    trajectories, measured severalfold faster at paper scale; the other
    backends step tick-by-tick through ``_scan_scenario``. Either way
    the scan carry is born inside the jitted program (the strong form of
    buffer donation: no boundary-crossing buffer exists to double-buffer
    the rings in HBM — a law init may legally alias one zeros buffer
    across state leaves, which ``donate_argnums`` would reject) and its
    dtypes are audited (``audit_carry_dtypes``) so a stray wide leaf
    cannot silently double the carried footprint.

    The whole-trace run (reference and fused backends, no ``chunk``)
    compiles once per static signature: the schedule and the [N]-axis
    ``LawConfig`` leaves are arguments of a cached program, padded to
    one count per bit length of max(N, S) (``_slot_program``; DESIGN.md
    section 12), with bit-identical results.

    ``chunk=C`` streams the schedule through the scan in C-entry windows
    (reference and megakernel backends; DESIGN.md section 15): trace
    length then no longer bounds device memory — only O(C * H) schedule
    rows plus the fixed pool/ring state are resident per segment, so
    100k+-flow traces fit. The trajectory is bit-for-bit identical to
    the single-shot run for EVERY chunk size (C is clamped up to S
    internally; tests/test_chunk_stream.py holds the property). Not
    compatible with ``record_every > 1`` or the fused backend.

    ``checkpoint=CheckpointSpec(path)`` snapshots the full carry (and
    recorded trace) at chunk-segment boundaries via atomic temp+rename
    writes; ``resume_slots`` continues from the newest snapshot
    bit-for-bit (DESIGN.md section 18). ``guard=True`` runs the
    divergence finite-check at each boundary (``core/guard.py`` —
    raises ``DivergenceError`` naming law/tick/field instead of
    returning NaN output); ``faults`` injects a deterministic crash
    (``core/faults.py``). All three ride the chunk-streamed driver:
    without an explicit ``chunk`` they default to a full-schedule
    window (bit-identical to the single-shot run by the chunk
    contract); the fused backend rejects them.
    """
    obs.count("slots.calls")
    with obs.span("slots.prepare"):
        cfg = cfg or SimConfig()
        _check_impair(impair, bw_fn, backend)
        law = _resolve_law(law_name, backend)
        law_cfg = law_cfg or default_law_config(sched)
        sim = SlotSim(topo, sched, law, law_cfg, cfg, int(slots), backend,
                      impair=impair)
    if checkpoint is not None or faults is not None or guard:
        if backend == "fused":
            raise UnsupportedFeature(
                "checkpoint/fault/guard execution rides the "
                "chunk-streamed driver, which the fused backend does "
                "not support",
                hint="use the reference or megakernel backend")
        C = int(chunk) if chunk is not None else int(sched.start.shape[0])
        return _simulate_slots_chunked(sim, C, bw_fn, record,
                                       checkpoint=checkpoint,
                                       faults=faults, guard=guard)
    if chunk is not None:
        return _simulate_slots_chunked(sim, int(chunk), bw_fn, record)
    obs.count("slots.ticks", int(cfg.steps))
    if backend == "megakernel":
        from .megakernel import simulate_slots_mega
        with obs.span("slots.call", program="megakernel",
                      ticks=int(cfg.steps)):
            return simulate_slots_mega(sim, bw_fn=bw_fn, record=record)

    with obs.span("slots.prepare"):
        run, args = _slot_program(sim, bw_fn, record)
    N = int(sched.start.shape[0])
    with obs.span("slots.call", program="run", ticks=int(cfg.steps)):
        final, recs = run(*args)
    if int(final.fct.shape[0]) != N:
        with obs.span("slots.finish"):
            # on the host: an eager device slice compiles per flow count
            final = final._replace(
                fct=jnp.asarray(np.asarray(final.fct)[:N]))
    return final, recs


class _Ident:
    """A cache-key part compared by identity. It holds the object, so
    the object's ``id`` is never reused while the key lives."""
    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _Ident) and other.obj is self.obj


def _digest(tree) -> bytes:
    """Content digest of a pytree of concrete arrays and scalars: its
    structure and each leaf's type, weak type, dtype, shape and bytes."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    h = hashlib.blake2b(str(treedef).encode(), digest_size=16)
    for x in leaves:
        a = np.asarray(x)
        h.update(f"{type(x).__name__}|{getattr(x, 'weak_type', '')}|"
                 f"{a.dtype.str}|{a.shape}".encode())
        h.update(a.tobytes())
    return h.digest()


_SLOT_PROGRAMS_MAX = 8
_slot_programs: OrderedDict = OrderedDict()     # key -> jitted run, LRU
_slot_programs_lock = threading.Lock()


def _slot_program(sim: SlotSim, bw_fn, record: bool):
    """The whole-trace slot program for ``sim`` and its arguments
    (DESIGN.md section 12, program cache).

    The schedule and the [N]-axis ``LawConfig`` leaves are arguments,
    padded to ``Np = 2**k - 1``, the largest count with the bit length
    k of max(N, S): Poisson flow counts of one deployment then share one
    compiled program, and the admission's binary search over the starts
    (``jnp.searchsorted``: as many levels as the length has bits) keeps
    the depth it has at N; a power of two would add a level. The
    schedule pads with inert ``pad_schedule`` entries (never admitted);
    the config leaves repeat the last real flow's values, so an empty
    slot, which gathers entry Np - 1 where it gathered N - 1, reads the
    same numbers. Everything else that shapes the program is in the key:
    topology, impairments, ``SimConfig`` and the scalar config leaves by
    content (the topology stays concrete: ``_buffer_caps`` reads it on
    the host), the law by value, ``bw_fn`` and the tick function
    (``slot_step`` as the module holds it now) by identity, and the pool,
    backend, recording, ``Np`` and the argument shapes.
    """
    sched = sim.sched
    N = int(sched.start.shape[0])
    S = int(sim.slots)
    Np = (1 << max(N, S, 1).bit_length()) - 1
    sched_p = jax.tree_util.tree_map(
        np.asarray, pad_schedule(sched, Np, sim.topo.num_queues))
    leaves, treedef = jax.tree_util.tree_flatten(sim.law_cfg)
    is_flow = [np.ndim(x) >= 1 and np.shape(x)[0] == N for x in leaves]
    flow = []
    for x, f in zip(leaves, is_flow):
        if f:
            a = np.asarray(x)
            flow.append(np.concatenate([a, np.repeat(a[-1:], Np - N, 0)]))
    cfg_key = tuple("flow" if f else _digest(x)
                    for x, f in zip(leaves, is_flow))
    shapes = tuple((a.shape, a.dtype.str) for a in
                   jax.tree_util.tree_leaves(sched_p) + flow)
    key = (_digest(sim.topo), _digest(sim.impair), _digest(sim.cfg),
           sim.law, _Ident(bw_fn), _Ident(slot_step), S, sim.backend,
           bool(record), treedef, cfg_key, Np, shapes)
    obs.count("slots.program_lookups")
    with _slot_programs_lock:
        run = _slot_programs.get(key)
        if run is None:
            obs.count("slots.program_misses")
            run = _build_slot_program(sim, bw_fn, slot_step, record, treedef,
                                      is_flow, leaves, Np)
            _slot_programs[key] = run
            while len(_slot_programs) > _SLOT_PROGRAMS_MAX:
                _slot_programs.popitem(last=False)
        else:
            _slot_programs.move_to_end(key)
    return run, (sched_p, flow, np.int32(N))


def _build_slot_program(sim: SlotSim, bw_fn, step_fn, record: bool,
                        treedef, is_flow, leaves, Np: int):
    # close over the key's parts only: no schedule, no [N] leaves
    base = sim._replace(sched=None, law_cfg=None)
    static = [None if f else x for x, f in zip(leaves, is_flow)]

    @jax.jit
    def run(sched, flow, n):
        it = iter(flow)
        law_cfg = treedef.unflatten([next(it) if f else x
                                     for x, f in zip(static, is_flow)])
        simp = base._replace(sched=sched, law_cfg=law_cfg)
        state = init_slot_state(simp)
        audit_carry_dtypes(state)
        final, recs = _scan_scenario(simp, state, bw_fn, None, record,
                                     step_fn=step_fn)
        # free slots read the caller's N, as if nothing were padded
        return final._replace(slot_flow=jnp.where(
            final.slot_flow == Np, n, final.slot_flow)), recs

    return run


def resume_slots(topo: Topology, sched: FlowSchedule,
                 law_name: Union[str, Law], slots: int,
                 checkpoint: CheckpointSpec,
                 law_cfg: Optional[LawConfig] = None,
                 cfg: Optional[SimConfig] = None,
                 bw_fn: Optional[Callable] = None,
                 record: bool = True,
                 backend: str = "reference",
                 chunk: Optional[int] = None,
                 impair: Optional[ImpairmentParams] = None,
                 faults: Optional[FaultSpec] = None,
                 guard: bool = False,
                 tick: Optional[int] = None):
    """Continue a checkpointed ``simulate_slots`` run (DESIGN.md s18).

    Call with the ORIGINAL run's scenario arguments (topology, schedule,
    law, slot pool, configs — a snapshot holds only the carry and the
    recorded trace; law update functions and schedules are rebuilt, not
    deserialized) plus the same ``checkpoint`` spec. The newest snapshot
    (or an explicit ``tick``) is restored into a freshly-built carry
    template — the snapshot's law/steps/slots/flows/engine metadata must
    match or this raises — and the run continues to completion,
    checkpointing onward at the same cadence.

    Returns the standard ``(final SlotState, Record)`` contract with the
    Record covering the FULL trace from tick 0, bit-for-bit identical to
    the uninterrupted run: restoring a boundary snapshot only changes
    how the remaining ticks are cut into segments, and the chunk-
    streamed trajectory is invariant to segmentation
    (tests/test_resume.py holds inject -> crash -> resume -> bitmatch
    for every registered law).
    """
    cfg = cfg or SimConfig()
    _check_impair(impair, bw_fn, backend)
    if backend == "fused":
        raise UnsupportedFeature(
            "checkpoint/resume rides the chunk-streamed driver, which "
            "the fused backend does not support",
            hint="use the reference or megakernel backend")
    law = _resolve_law(law_name, backend)
    law_cfg = law_cfg or default_law_config(sched)
    sim = SlotSim(topo, sched, law, law_cfg, cfg, int(slots), backend,
                  impair=impair)
    C = int(chunk) if chunk is not None else int(sched.start.shape[0])
    return _simulate_slots_chunked(sim, C, bw_fn, record,
                                   checkpoint=checkpoint, faults=faults,
                                   guard=guard, resume=True,
                                   resume_tick=tick)


# --------------------------------------------------------------------------
# Batched scenario engine
# --------------------------------------------------------------------------

def pad_flows(flows: Flows, n: int, pad_queue: int) -> Flows:
    """Pad a Flows batch to ``n`` flows with inert entries.

    Padded flows never activate (``start = inf``), traverse only the sentinel
    queue ``pad_queue`` (== topo.num_queues), and carry ``size = inf`` so FCT
    accounting (which keys on finite sizes) ignores them.
    """
    F = int(flows.tau.shape[0])
    add = n - F
    if add < 0:
        raise ValueError(f"cannot pad {F} flows down to {n}")
    if add == 0:
        return flows

    def cat(x, fill, dtype):
        pad = jnp.full((add,) + tuple(x.shape[1:]), fill, dtype)
        return jnp.concatenate([jnp.asarray(x, dtype), pad])

    return Flows(
        path=cat(flows.path, pad_queue, jnp.int32),
        tf_steps=cat(flows.tf_steps, 1, jnp.int32),
        rtt_steps=cat(flows.rtt_steps, 1, jnp.int32),
        tau=cat(flows.tau, 20e-6, jnp.float32),
        nic_rate=cat(flows.nic_rate, 1e9, jnp.float32),
        size=cat(flows.size, jnp.inf, jnp.float32),
        start=cat(flows.start, jnp.inf, jnp.float32),
        stop=cat(flows.stop, jnp.inf, jnp.float32),
        weight=cat(flows.weight, 1.0, jnp.float32),
    )


def stack_flows(flows_list: List[Flows], pad_queue: int) -> Flows:
    """Stack scenarios along a new leading batch axis, padding each to the
    largest flow count with inert flows (``pad_flows``) and to the
    largest hop count with sentinel hops (``types.pad_hops`` — scenarios
    mixing path depths, e.g. incast bursts alongside a permutation
    matrix on one fat-tree, stack into one program)."""
    n = max(int(f.tau.shape[0]) for f in flows_list)
    h = max(int(f.path.shape[-1]) for f in flows_list)
    padded = [pad_flows(pad_hops(f, h, pad_queue), n, pad_queue)
              for f in flows_list]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


def stack_law_configs(cfgs: List[LawConfig]) -> LawConfig:
    """Stack per-scenario LawConfigs along a new leading axis (scalars become
    [B] vectors; None leaves must be None everywhere)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *cfgs)


def pad_schedule(sched: FlowSchedule, n: int, pad_queue: int) -> FlowSchedule:
    """Pad a schedule to ``n`` flows with inert tail entries.

    Same inert values as ``pad_flows`` plus ``start = inf`` — the sorted
    order is preserved (inf sorts last) and the admission cursor never
    reaches the padding, so padded scenarios in one batch share a flow
    count without ever admitting phantom flows. ``order`` pads with -1.
    The padding is built on the host (numpy), so a new flow count
    compiles no device program.
    """
    N = int(sched.start.shape[0])
    add = n - N
    if add < 0:
        raise ValueError(f"cannot pad {N} schedule entries down to {n}")
    if add == 0:
        return sched

    def cat(x, fill, dtype):
        pad = np.full((add,) + tuple(x.shape[1:]), fill, dtype)
        return np.concatenate([np.asarray(x, dtype), pad])

    return FlowSchedule(
        path=cat(sched.path, pad_queue, np.int32),
        tf_steps=cat(sched.tf_steps, 1, np.int32),
        rtt_steps=cat(sched.rtt_steps, 1, np.int32),
        tau=cat(sched.tau, 20e-6, np.float32),
        nic_rate=cat(sched.nic_rate, 1e9, np.float32),
        size=cat(sched.size, np.inf, np.float32),
        start=cat(sched.start, np.inf, np.float32),
        stop=cat(sched.stop, np.inf, np.float32),
        weight=cat(sched.weight, 1.0, np.float32),
        order=cat(sched.order, -1, np.int32),
    )


def stack_flow_schedules(scheds: List[FlowSchedule],
                         pad_queue: int) -> FlowSchedule:
    """Stack schedules along a new leading batch axis, padding each to the
    largest flow count with inert entries (``pad_schedule``) and to the
    largest hop count with sentinel hops (``types.pad_hops``)."""
    n = max(int(s.start.shape[0]) for s in scheds)
    h = max(int(s.path.shape[-1]) for s in scheds)
    padded = [pad_schedule(pad_hops(s, h, pad_queue), n, pad_queue)
              for s in scheds]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


def resolve_devices(devices) -> int:
    """Normalize the ``devices`` argument of ``simulate_batch``.

    ``None``/``0``/``1`` -> 1 (single-device vmap path); ``"auto"`` -> all
    local devices; an explicit count larger than the local device count
    raises — a run asked for N devices never quietly runs on fewer.
    """
    if devices is None:
        return 1
    avail = jax.local_device_count()
    if devices == "auto":
        return avail
    n = int(devices)
    if n > avail:
        raise ValueError(f"devices={n} requested but only {avail} local "
                         f"device(s) are present")
    return max(1, n)


def _batch_mesh(ndev: int):
    """(mesh, rules) carrying the scenario batch axis: the enclosing
    ``use_rules`` mesh + rules when one is active (the mesh's own batch-axis
    product then determines the shard count, not ``ndev``), else a fresh
    1-D ``(data=ndev,)`` mesh over local devices with the default rules."""
    mesh = active_mesh()
    if mesh is not None:
        return mesh, active_rules()
    return make_mesh((ndev,), ("data",)), None


def _pad_batch(tree, pad: int):
    """Repeat the last scenario ``pad`` times along the batch axis (filler
    points are real simulations whose outputs are sliced off)."""
    if pad == 0 or tree is None:
        return tree
    return jax.tree_util.tree_map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])]), tree)


def _dispatch_batch(run, args: tuple, batch: int, devices):
    """Run a vmapped scenario program on the single-device path or, with
    ``devices`` > 1, shard its batch axis across the device mesh
    (DESIGN.md section 11). Shared by ``simulate_batch`` and
    ``simulate_slots_batch`` — identical padding/sharding contract."""
    ndev = resolve_devices(devices)
    if ndev <= 1:
        return jax.jit(run)(*args)

    mesh, rules = _batch_mesh(ndev)
    spec = axes_to_pspec(("batch",), mesh, rules)
    ax0 = spec[0] if len(spec) else None
    ax0 = ax0 if isinstance(ax0, tuple) else ((ax0,) if ax0 else ())
    sizes = dict(mesh.shape)
    shards = 1
    for a in ax0:
        shards *= sizes[a]
    if shards <= 1:
        return jax.jit(run)(*args)

    pad = -batch % shards
    args = tuple(_pad_batch(a, pad) for a in args)
    sharded = shard_map(run, mesh=mesh, in_specs=(spec,) * len(args),
                        out_specs=spec, check_vma=False)
    out = jax.jit(sharded)(*args)
    if pad:
        out = jax.tree_util.tree_map(lambda x: x[:batch], out)
    return out


def simulate_batch(topo: Topology, flows: Flows, law_name: Union[str, Law],
                   law_cfg: Optional[LawConfig] = None,
                   cfg: Optional[SimConfig] = None,
                   bw_fn: Optional[Callable] = None,
                   bw_params=None,
                   alloc_fn: Optional[Callable] = None,
                   record: bool = True,
                   backend: str = "reference",
                   expected_flows: float = 1.0,
                   devices=None,
                   impair_params: Optional[ImpairmentParams] = None):
    """Run a whole sweep of scenarios as ONE jitted, vmapped program.

    ``flows`` carries a leading batch axis B on every leaf (build it with
    ``stack_flows``); ``law_cfg`` likewise (``stack_law_configs``), or None
    to derive the paper-default config per scenario with ``expected_flows``.
    Topology, SimConfig and the law are shared across the batch — the whole
    sweep compiles once and every scenario advances in lockstep through one
    ``lax.scan``, instead of one compile + one serial scan per point.

    Time-varying bandwidth: without ``bw_params``, ``bw_fn(t)`` is shared by
    every scenario; with ``bw_params`` (a pytree whose leaves carry the same
    leading batch axis, e.g. ``rdcn.stack_schedules``), scenario ``i`` sees
    ``bw_fn(t, bw_params_i)`` — a whole axis of circuit schedules runs
    inside the one compiled program.

    Device sharding (DESIGN.md section 11): ``devices`` > 1 (or ``"auto"``)
    splits the batch axis across a device mesh with ``shard_map`` — each
    device runs the identical vmapped scan on its B/ndev slice, with no
    cross-device communication. B is padded to a multiple of the shard
    count by repeating the last scenario (outputs sliced back to B). The
    mesh and rules come from the enclosing ``sharding.use_rules`` context
    when active — the batch axis then maps through that context's
    ``"batch"`` rule and the shard count is the product of those mesh
    axes, overriding ``devices`` — else a 1-D ``(data=ndev,)`` mesh with
    the default rules. ``devices=None`` is the bit-exact single-device
    vmap path (no shard_map in the program).

    Returns (final_states, records) with a leading batch axis.
    """
    cfg = cfg or SimConfig()
    _check_impair(impair_params, bw_fn, backend)
    law = _resolve_law(law_name, backend)

    def _one(flows_i, lcfg_i, bwp_i, imp_i):
        lcfg = (lcfg_i if lcfg_i is not None else
                default_law_config(flows_i, expected_flows=expected_flows))
        bfn = bw_fn if bwp_i is None else (lambda t: bw_fn(t, bwp_i))
        sim = _make_sim(topo, flows_i, law, lcfg, cfg, backend,
                        impair=imp_i)
        return _scan_scenario(sim, init_state(sim), bfn, alloc_fn, record)

    def axes(tree):
        return (None if tree is None else
                jax.tree_util.tree_map(lambda _: 0, tree))

    run = jax.vmap(_one, in_axes=(axes(flows), axes(law_cfg),
                                  axes(bw_params), axes(impair_params)))
    return _dispatch_batch(run, (flows, law_cfg, bw_params, impair_params),
                           int(flows.tau.shape[0]), devices)


def simulate_slots_batch(topo: Topology, scheds: FlowSchedule,
                         law_name: Union[str, Law], slots: int,
                         law_cfg: Optional[LawConfig] = None,
                         cfg: Optional[SimConfig] = None,
                         bw_fn: Optional[Callable] = None,
                         bw_params=None,
                         record: bool = True,
                         backend: str = "reference",
                         expected_flows: float = 1.0,
                         devices=None,
                         sequential: bool = False,
                         impair_params: Optional[ImpairmentParams] = None):
    """Batched/sharded twin of ``simulate_slots`` (the slot path of the
    sweep engine).

    ``scheds`` carries a leading batch axis B on every leaf (build with
    ``stack_flow_schedules``); ``law_cfg``/``bw_params`` batch exactly as
    in ``simulate_batch``, and ``devices`` shards the batch axis with the
    same padding contract (DESIGN.md section 11). The pool size ``slots``
    is shared across the batch — one compiled program whose per-tick cost
    is O(B * S * hops) regardless of the stacked schedules' total flow
    counts. Returns (final ``SlotState``s, records) with a leading batch
    axis; ``fct`` rows are in each scenario's schedule order.

    ``sequential=True`` runs the batch axis as a ``lax.scan`` over
    scenarios instead of a vmap: still ONE compiled program (one compile
    for the whole sweep), but scenarios execute one after another, so
    data-dependent ``lax.cond`` branches keep their runtime short-circuit
    — this is how the megakernel backend's idle-tick gate stays effective
    across a sweep (under vmap a cond lowers to executing both branches).
    Identical results, different schedule; ``devices`` is ignored.
    """
    cfg = cfg or SimConfig()
    _check_impair(impair_params, bw_fn, backend)
    law = _resolve_law(law_name, backend)
    S = int(slots)

    def _one(sched_i, lcfg_i, bwp_i, imp_i):
        lcfg = (lcfg_i if lcfg_i is not None else
                default_law_config(sched_i, expected_flows=expected_flows))
        bfn = bw_fn if bwp_i is None else (lambda t: bw_fn(t, bwp_i))
        sim = SlotSim(topo, sched_i, law, lcfg, cfg, S, backend,
                      impair=imp_i)
        if backend == "megakernel":
            from .megakernel import simulate_slots_mega
            # the idle-tick gate is a lax.cond; under vmap it would
            # lower to running both branches every tick — keep it only
            # on the sequential path (bit-identical either way, see
            # make_block_fn)
            return simulate_slots_mega(sim, bw_fn=bfn, record=record,
                                       gate=sequential)
        # state is born inside the jitted program (nothing to donate);
        # the audit still gates stray wide dtypes out of the carry
        state = init_slot_state(sim)
        audit_carry_dtypes(state)
        return _scan_scenario(sim, state, bfn, None, record,
                              step_fn=slot_step)

    def axes(tree):
        return (None if tree is None else
                jax.tree_util.tree_map(lambda _: 0, tree))

    if sequential:
        @jax.jit
        def run_seq():
            def body(_, xs):
                return None, _one(*xs)
            return jax.lax.scan(body, None,
                                (scheds, law_cfg, bw_params,
                                 impair_params))[1]
        return run_seq()

    run = jax.vmap(_one, in_axes=(axes(scheds), axes(law_cfg),
                                  axes(bw_params), axes(impair_params)))
    return _dispatch_batch(run, (scheds, law_cfg, bw_params, impair_params),
                           int(scheds.start.shape[0]), devices)
