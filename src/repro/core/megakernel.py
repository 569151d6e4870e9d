"""Time-blocked whole-tick megakernel for the flow-slot streaming engine.

The op-by-op slot engine (``fluid.slot_step``) runs every tick as ~200
separate XLA ops; at paper scale the tick is dominated not by arithmetic
but by scatters that XLA CPU lowers to per-row ``while`` loops (queue
arrivals, Dynamic-Thresholds buffer accounting, the FCT output write) and
by per-tick bookkeeping that runs even when no flow arrives or leaves.
The megakernel backend (``backend="megakernel"``, DESIGN.md section 13)
rebuilds the whole tick around one fused core:

  * the **admit/retire pass is gated** behind ``lax.cond`` on "an arrival
    is due or a slot is freeable" — with the due-arrival counts
    precomputed for the whole trace (one vectorized ``searchsorted``
    instead of one per tick) the idle-tick predicate costs three ops, and
    the ring buffers never cross the cond (the pass does not touch them);
  * **FCT writes are deferred**: completions park in a per-slot pending
    buffer and scatter into the O(N) output only on the (gated) tick that
    recycles the slot, plus one final flush — the per-tick [S]-row
    scatter disappears;
  * **Dynamic-Thresholds buffer accounting** uses a static per-switch CSR
    of queue ids with an unrolled in-order column sum instead of a
    segment-sum scatter (bit-identical: same per-switch accumulation
    chains);
  * the **queue-arrival incidence stays sparse** and is kept INVERTED
    (``kernels.queue_arrivals.build_csr_gather``): per tick the arrivals
    are one [Q+1, maxdeg] gather plus maxdeg in-order column adds —
    O(nnz), bit-identical accumulation — rebuilt only on (gated)
    admission ticks, with a scatter fallback when a queue's degree
    overflows the static CSR width;
  * **telemetry is packed**: queue length, egress rate and queue gradient
    share one ring row ([q | out | qdot]), with the gradient computed at
    write time over exactly the operands the reference engine subtracts
    at read time — the delayed observation is ONE gather instead of
    three, and laws declare which telemetry they consume
    (``Law.uses_qdot`` / ``uses_mu`` / ``uses_ecn``) so unused channels
    are never built.

Two lowerings run the same tick function:

  * **XLA scan** (the default on every platform, TPU included): the tick
    scans flat through ``fluid._scan_scenario`` exactly like the
    reference engine (same ``record_every`` chunking), so the only
    differences against the reference program are the restructurings
    above;
  * **Pallas whole-tick harness** (``kernels.fused_tick``,
    ``impl="pallas"``): one ``pallas_call`` evaluates a K-tick block.
    Mosaic cannot lower the tick's gathers, scatter and
    ``dynamic_slice``, so it runs only in interpret mode off-TPU (tests);
    on TPU ``impl="pallas"`` raises before compiling.

Exactness contract (the PR-3 anchor discipline, tests/test_megakernel.py,
CI-gated via ``fct_mega_exact_bitmatch``): on the single-bottleneck
anchor scenario the megakernel reproduces the reference backend's queue
trace, FCT vector, per-slot rates and ring contents BIT-FOR-BIT for
every registered law, on both lowerings; at paper scale the completion
set matches exactly and FCT tails agree to cross-program float noise
(compiled program variants may round isolated knife-edge ticks apart —
the same boundary PR 3 documents for the slot-vs-padded engines,
DESIGN.md section 12; one such flip, LLVM contracting ``t*dt`` into the
update-timer add, is why the tick computes ``t_sec`` inside its own code
region, see ``make_tick``).

Laws need no megakernel-specific code: the tick composes the law's
registered kernel-composable update (``laws.get_law(name,
"megakernel")``), so every registered law — powertcp, theta_powertcp,
hpcc, dcqcn, retcp, ... — runs on the fused path.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels.fused_tick import DEFAULT_BLOCK, fused_tick_block
from ..kernels.queue_arrivals import (apply_loss, build_csr_gather,
                                      csr_gather_arrivals,
                                      integrate_arrivals,
                                      ordered_scatter_add, suggest_maxdeg)
from .impair import impair_vectors
from .laws import _nofma, _pin
from .types import MTU, PathObs, Record, SlotState
from . import fluid  # safe: fluid imports this module only inside functions

_INT32_MAX = np.iinfo(np.int32).max


class PendingFCT(NamedTuple):
    """Completions awaiting their deferred write into the [N] FCT output.

    ``flow == N`` marks an empty lane. A slot parks its occupant's FCT
    here on the completion tick and the value scatters out when the slot
    is recycled (inside the gated admit/retire pass) or in the final
    flush — a slot holds at most one unflushed completion because a new
    occupant is only admitted after the previous one's lane is flushed.
    """
    flow: jnp.ndarray               # [S] int32 schedule index (N = empty)
    val: jnp.ndarray                # [S] float32 completion time


class MegaCarry(NamedTuple):
    """Scan carry of the megakernel.

    Besides the full ``SlotState`` (whose ``hist_q`` leaf holds the
    packed [q | out | qdot] telemetry ring and whose ``hist_out`` leaf
    rides as None — unpacked on exit) it carries values the reference
    engine recomputes every tick but that can only change on a gated
    admit/retire tick: the pending-FCT buffer, the per-slot drain hold,
    and (when the sparse-gather queue path is active) the inverted
    incidence with its overflow flag. All are integer or write-once
    float values, so carrying them is bit-safe; the float LawConfig
    gather is deliberately NOT carried — the values would be identical,
    but rerouting them through the loop carry shifts XLA's downstream
    instruction selection enough to flip f32 knife edges the
    ``laws._pin`` barriers do not cover.

    Checkpoint contract (core/ckpt.py, DESIGN.md section 18): every
    field here is plain carried data, so the whole MegaCarry round-trips
    through a chunk-boundary snapshot leaf-for-leaf. Restore goes
    through a template built by the same ``init_carry`` — the treedef
    (including whether ``inv``/``ovf`` exist, decided statically by the
    CSR-vs-scatter choice) is re-derived from scenario arguments, never
    deserialized, and the float LawConfig gather stays outside the
    carry on resume exactly as it does on a fresh run."""
    state: SlotState
    pend: PendingFCT
    hold: jnp.ndarray               # [S] int32 max valid hop delay
    inv: Optional[jnp.ndarray]      # [Q+1, maxdeg] int32 CSR (or None)
    ovf: Optional[jnp.ndarray]      # bool: some queue exceeds maxdeg


def build_switch_csr(topo) -> Optional[np.ndarray]:
    """Static per-switch queue lists for Dynamic-Thresholds accounting.

    Row s holds switch s's queue ids in ascending order, padded with the
    sentinel queue Q (whose length is structurally 0.0) — summing the
    rows column-by-column therefore reproduces the reference
    ``segment_sum`` per-switch accumulation chains bit-for-bit (ascending
    queue order; trailing +0.0 terms are additive identities on the
    non-negative queue lengths). Returns None when DT is disabled."""
    if topo.dt_alpha <= 0:
        return None
    sw = np.asarray(topo.switch_of_queue)
    nsw = int(topo.num_switches)
    deg = int(np.bincount(sw, minlength=nsw).max()) if sw.size else 0
    csr = np.full((nsw, max(deg, 1)), int(topo.num_queues), np.int32)
    for s in range(nsw):
        qs = np.nonzero(sw == s)[0]
        csr[s, :qs.size] = qs
    return csr


def _buffer_caps_csr(topo, q: jnp.ndarray, csr: Optional[np.ndarray]):
    """``fluid._buffer_caps`` with the DT segment-sum replaced by the
    static CSR column sum (bit-identical; see ``build_switch_csr``). The
    scatter XLA CPU emits for the segment-sum costs ~1us per QUEUE per
    tick in loop overhead alone — this is a handful of fused adds."""
    buf = jnp.concatenate([topo.buffer, jnp.asarray([1e30], jnp.float32)])
    if csr is None:
        return buf
    g = q[csr]                                        # [n_sw, deg]
    used = jnp.zeros((csr.shape[0],), jnp.float32)
    for j in range(csr.shape[1]):                     # in-order, unrolled
        used = used + g[:, j]
    free = jnp.maximum(topo.switch_buffer - used, 0.0)
    thr = topo.dt_alpha * free[topo.switch_of_queue]
    return jnp.concatenate([jnp.minimum(thr, topo.buffer),
                            jnp.asarray([1e30], jnp.float32)])


def _due_table(sched, steps: int, dt: float) -> jnp.ndarray:
    """[T] due-arrival counts, one vectorized binary search for the whole
    trace. ``due[t]`` is bit-identical to the per-tick
    ``searchsorted(start, t * dt)`` of ``fluid._admit_retire`` (same f32
    time values, same search)."""
    t_sec = jnp.arange(steps, dtype=jnp.int32).astype(jnp.float32) * dt
    return jnp.searchsorted(sched.start, t_sec,
                            side="right").astype(jnp.int32)


def _flush_pending(fct: jnp.ndarray, pend: PendingFCT, mask, N: int):
    """Scatter masked pending completions into the [N] FCT output (rows
    outside the mask drop on the sentinel index)."""
    fct = fct.at[jnp.where(mask, pend.flow, N)].set(
        jnp.where(mask, pend.val, jnp.nan), mode="drop")
    pend = PendingFCT(jnp.where(mask, N, pend.flow),
                      jnp.where(mask, jnp.nan, pend.val))
    return fct, pend


def make_tick(sim, bw_fn=None, gate: bool = True,
              quiet: bool = False,
              maxdeg: Optional[int] = None) -> Callable:
    """Build the megakernel tick: ``tick(carry, due_t) -> (carry', rec)``.

    The arithmetic mirrors ``fluid.slot_step`` op for op (pins included)
    with the restructurings listed in the module docstring; laws run
    through ``sim.law.update`` — the registered kernel-composable
    update — against the slot-gathered config, so any registry law
    composes unchanged. ``gate`` enables the idle-tick admit/retire cond
    (keep it off under vmap, where a cond lowers to running both
    branches). ``quiet`` additionally short-circuits fully-quiescent
    ticks (empty pool, nothing due) down to the queue drain and ring
    writes — value-preserving for laws with ``masked_updates``, but a
    net loss on current CPU measurements (the branch operands include
    the rings), so it is off by default; the TPU kernel, where
    predication is cheap, is its intended user. ``maxdeg`` overrides the
    CSR width (the chunk driver passes the FULL schedule's static degree
    — the window visible to this tick would understate it).

    Returns the tick plus ``tick.init_carry(state0) -> MegaCarry`` for
    the matching initial carry.
    """
    topo, cfg, law = sim.topo, sim.cfg, sim.law
    sched = sim.sched
    S = int(sim.slots)
    N = fluid._slot_n(sim)
    Q = int(topo.num_queues)
    Q1 = Q + 1
    D = int(cfg.hist)
    dt = cfg.dt
    csr = build_switch_csr(topo)
    sidx = jnp.arange(S)
    buf_cat = jnp.concatenate([topo.buffer,
                               jnp.asarray([1e30], jnp.float32)])
    H = int(sched.path.shape[1])
    # sparse-gather queue path: worth carrying the inverted incidence
    # once the hop list outgrows the unrolled accumulate, but only on
    # the gated (serial) path — ungated, the rebuild would run every
    # tick (and under vmap the overflow cond runs both branches). The
    # CSR width comes from the compiled path set (the schedule's static
    # per-queue degree bounds the runtime degree), so deep fat-tree /
    # incast hop tables get a wide-enough table instead of falling back
    # to the per-tick scatter every tick. Under the batched drivers the
    # schedule is a tracer (no concrete hop table at trace time) — keep
    # the historical fixed width there; the runtime overflow fallback
    # stays bit-identical either way.
    if maxdeg is None:
        maxdeg = (min(S, 32) if isinstance(sched.path, jax.core.Tracer)
                  else suggest_maxdeg(sched.path, Q, S))
    use_csr = gate and S * H > 128
    # Packed-ring layout (DESIGN.md section 16): feedback channels APPEND
    # to the [q | out | qdot] row — existing column offsets never move, so
    # ring growth cannot perturb the compiled program of a law that does
    # not declare the new channels.
    nchan = 3 + int(law.uses_pause) + int(law.uses_incast)
    off_pause = 3 * Q1
    off_inc = (3 + int(law.uses_pause)) * Q1

    def slot_hold(st):
        return jnp.max(jnp.where(st.path < Q, st.tf_steps, 0), axis=1)

    def incidence_extras(st):
        if not use_csr:
            return None, None
        return build_csr_gather(st.path, Q, maxdeg)

    def init_carry(state0: SlotState) -> MegaCarry:
        hold0, inv0, ovf0 = ((slot_hold(state0),) +
                             incidence_extras(state0))
        return MegaCarry(
            # [q | out | qdot | pause? | inc?] telemetry packs into ONE
            # ring (see integrate_queues); hist_out rides as its middle
            # third and is restored by the driver on exit, and the
            # feedback-channel rings (when the law declares them) ride as
            # appended columns instead of separate [D, Q+1] leaves
            state=state0._replace(hist_q=jnp.zeros((D, nchan * Q1),
                                                   jnp.float32),
                                  hist_out=None, hist_pause=None,
                                  hist_inc=None),
            pend=PendingFCT(jnp.full((S,), N, jnp.int32),
                            jnp.full((S,), jnp.nan, jnp.float32)),
            hold=hold0, inv=inv0, ovf=ovf0)

    def admit_retire(st, pend, carry_inv, carry_ovf, t_sec, due_t):
        """Retire drained slots (flushing their parked FCTs), admit due
        arrivals, refresh the carried admission-only values. Gated ticks
        only (the pass is the identity when nothing is due/freeable)."""
        freeable = ((st.slot_flow < N) & (st.t >= st.free_at) &
                    (pend.flow < N))
        fct, pend = _flush_pending(st.fct, pend, freeable, N)
        st2, occupied = fluid._admit_retire(
            sim, st._replace(fct=fct), t_sec, due=due_t)
        if use_csr:
            # the hop table only changes when a slot is ADMITTED
            # (retiring slots keep their stale rows, whose delayed rates
            # are structurally zero), so the O(nnz log nnz) inversion
            # reruns only on admission ticks
            inv, ovf = jax.lax.cond(
                st2.cursor > st.cursor,
                lambda s: build_csr_gather(s.path, Q, maxdeg),
                lambda s: (carry_inv, carry_ovf), st2)
        else:
            inv, ovf = None, None
        return st2, pend, occupied, slot_hold(st2), inv, ovf

    def integrate_queues(st, bw, arr, inc=None):
        """``kernels.queue_arrivals.integrate_arrivals`` (the pinned
        integration shared with the standalone sparse form) plus the
        packed telemetry row: the queue gradient is computed at WRITE
        time — ``(q_new - q)/dt`` over exactly the stored operands the
        reference engine subtracts at read time — so the delayed
        observation later costs one gather instead of three,
        bit-identically. Declared feedback channels append their columns
        (pause hysteresis evaluated here, on the integrated queue level,
        mirroring ``fluid._pause_step``; ``inc`` is the caller's sender
        count)."""
        caps = _buffer_caps_csr(topo, st.q, csr)
        out, q_new = integrate_arrivals(arr, st.q, bw, caps, dt=dt)
        parts = [q_new, out, _nofma((q_new - st.q) * (1.0 / dt))]
        pause_new = None
        if law.uses_pause:
            pause_new = fluid._pause_step(q_new, st.pause, sim.law_cfg)
            parts.append(pause_new)
        if law.uses_incast:
            parts.append(inc)
        row = jnp.concatenate(parts)
        return q_new, out, row, pause_new

    def quiet_tick(c, bw, jit, ptr):
        """Quiescent-pool fast tick: no slot occupied, nothing due.
        Everything except the queue drain, the telemetry-row writes and
        the every-tick window clamp is provably frozen (laws honour the
        upd_mask passthrough and retirement/admission cannot fire)."""
        st, pend, hold, inv, ovf = c
        # a quiescent pool contributes no traffic: the sender count is
        # structurally zero, and pause still evolves with the drain.
        # Loss needs no fold here — apply_loss on all-zero arrivals is
        # the exact identity (0 * keep == +0.0), so skipping it is
        # bit-identical to slot_step's scaled zero arrivals
        q_new, out, row, pause_new = integrate_queues(
            st, bw, jnp.zeros_like(st.q),
            inc=(jnp.zeros_like(st.q) if law.uses_incast else None))
        q_hop = st.q[st.path]
        b_hop = _pin(bw[st.path])
        valid = st.path < Q
        # retired slots keep stale valid paths, so the clamp's theta must
        # fold the jitter exactly like slot_step's (mirror of busy_tick)
        qb_now = q_hop / b_hop
        if jit is not None:
            qb_now = qb_now + jit[st.path]
        theta_now = st.tau + fluid._hop_sum(
            jnp.where(valid, qb_now, 0.0))
        w = jnp.clip(st.w, MTU, _nofma(_pin(8.0 * st.nic_rate * st.tau)) +
                     _nofma(_pin(8.0 * st.nic_rate * theta_now)))
        st = st._replace(
            t=st.t + 1, w=w, q=q_new, out_rate=out,
            hist_lam=st.hist_lam.at[ptr].set(jnp.zeros((S,), jnp.float32)),
            hist_w=st.hist_w.at[ptr].set(st.w),
            hist_q=st.hist_q.at[ptr].set(row))
        if law.uses_pause:
            st = st._replace(pause=pause_new)
        return st, pend, hold, inv, ovf, jnp.zeros((), jnp.float32), \
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)

    def busy_tick(c, bw, keep, jit, ptr, due_t):
        st, pend, hold, inv, ovf = c
        # t*dt is contraction-blocked (laws._nofma), mirroring the
        # reference engines: every program rounds the product before it
        # feeds the update timers, instead of relying on each program's
        # codegen contracting it the same way (an optimization_barrier
        # alone cannot pin it — LLVM contracts after XLA strips
        # barriers)
        t_sec = _nofma(st.t.astype(jnp.float32) * dt)

        if gate:
            # ticks with nothing due and nothing freeable skip the whole
            # admit/retire pass. The ring buffers never cross the cond —
            # the pass does not touch them, and keeping multi-MB buffers
            # out of the branch operands keeps the cond traffic trivial
            need = ((due_t > st.cursor) |
                    jnp.any((st.slot_flow < N) & (st.t >= st.free_at)))
            rings = (st.hist_lam, st.hist_q, st.hist_w)
            st_l = st._replace(hist_lam=None, hist_q=None, hist_w=None)
            st_l, pend, occupied, hold, inv, ovf = jax.lax.cond(
                need,
                lambda a: admit_retire(a[0], a[1], a[3], a[4], t_sec,
                                       due_t),
                lambda a: (a[0], a[1], a[0].slot_flow < N) + a[2:],
                (st_l, pend, hold, inv, ovf))
            st = st_l._replace(hist_lam=rings[0], hist_q=rings[1],
                               hist_w=rings[2])
        else:
            st, pend, occupied, hold, inv, ovf = admit_retire(
                st, pend, inv, ovf, t_sec, due_t)
        path, tf_steps, tau, nic = (st.path, st.tf_steps, st.tau,
                                    st.nic_rate)
        gf = jnp.clip(st.slot_flow, 0, N - 1)
        cfg_slot = fluid._gather_law_cfg(sim.law_cfg, gf, N)

        active = (occupied & (t_sec >= st.start) &
                  (st.remaining > 0.0) & (t_sec < st.stop))
        q_hop = st.q[path]                            # [S,H]
        b_hop = _pin(bw[path])       # mirror of the reference engine pin
        valid = path < Q
        qb_now = q_hop / b_hop
        if jit is not None:
            qb_now = qb_now + jit[path]
        theta_now = tau + fluid._hop_sum(
            jnp.where(valid, qb_now, 0.0))
        lam = jnp.where(active,
                        jnp.minimum(jnp.minimum(_pin(st.w / theta_now),
                                                st.rate_cap), nic), 0.0)

        hist_lam = st.hist_lam.at[ptr].set(lam)
        hist_w = st.hist_w.at[ptr].set(st.w)

        # -- queue update: sparse incidence, O(nnz) ---------------------
        hop_delay_idx = jnp.mod(ptr - tf_steps, D)
        lam_del = hist_lam[hop_delay_idx, sidx[:, None]]
        lam_del = jnp.where(st.t - tf_steps >= st.admit_t[:, None],
                            lam_del, 0.0)
        contrib = jnp.where(valid, lam_del, 0.0)
        if use_csr:
            # inverted-incidence gather + in-order column sums; scatter
            # fallback when a queue's degree exceeds the static CSR
            # width (bit-identical accumulation either way, see
            # kernels/queue_arrivals.py)
            arr = jax.lax.cond(
                ovf,
                lambda c_: ordered_scatter_add(jnp.zeros_like(st.q),
                                               path, c_),
                lambda c_: csr_gather_arrivals(c_, inv,
                                               jnp.zeros_like(st.q)),
                contrib)
        else:
            arr = ordered_scatter_add(jnp.zeros_like(st.q), path, contrib)
        if keep is not None:
            # loss folds into the ACCUMULATED arrivals, after either
            # accumulation path — the same post-scatter placement as
            # fluid._queue_update (kernels.apply_loss)
            arr = apply_loss(arr, keep)
        inc = (fluid._incast_count(st.q, path, valid, lam_del)
               if law.uses_incast else None)
        q_new, out, row, pause_new = integrate_queues(st, bw, arr, inc)
        hist_qoq = st.hist_q.at[ptr].set(row)

        # -- delayed observation: ONE packed gather covers queue length,
        #    egress rate, queue gradient and any declared feedback
        #    channels (appended columns, see make_tick) -------------------
        if law.feedback == "hop":
            tb_steps = jnp.clip(tf_steps, 1, D - 2)
        else:
            tb_steps = jnp.clip(st.rtt_steps[:, None] - tf_steps, 1, D - 2)
        ohidx = jnp.mod(ptr - tb_steps, D)
        cols = [path]
        if law.uses_mu:
            cols.append(path + Q1)
        if law.uses_qdot:
            cols.append(path + 2 * Q1)
        if law.uses_pause:
            cols.append(path + off_pause)
        if law.uses_incast:
            cols.append(path + off_inc)
        pause_obs = inc_obs = None
        if len(cols) > 1:
            g = hist_qoq[ohidx[..., None], jnp.stack(cols, axis=-1)]
            q_obs = g[..., 0]
            k = 1
            if law.uses_mu:
                mu_obs, k = g[..., k], k + 1
            else:
                mu_obs = jnp.zeros_like(q_obs)
            if law.uses_qdot:
                qdot_obs, k = g[..., k], k + 1
            else:
                qdot_obs = jnp.zeros_like(q_obs)
            if law.uses_pause:
                pause_obs, k = g[..., k], k + 1
            if law.uses_incast:
                inc_obs, k = g[..., k], k + 1
        else:
            q_obs = hist_qoq[ohidx, path]
            mu_obs = qdot_obs = jnp.zeros_like(q_obs)
        qb_obs = q_obs / b_hop
        if jit is not None:
            qb_obs = qb_obs + jit[path]
        theta_obs = tau + fluid._hop_sum(
            jnp.where(valid, qb_obs, 0.0))
        wold_delay = jnp.clip(jnp.round(theta_obs / dt).astype(jnp.int32),
                              1, D - 2)
        w_old = hist_w[jnp.mod(ptr - wold_delay, D), sidx]
        w_old = jnp.where(st.t - wold_delay >= st.admit_t, w_old,
                          nic * tau)
        ecn = (jnp.max(jnp.where(valid,
                                 fluid._marking(q_obs, buf_cat[path],
                                                cfg_slot), 0.0), axis=1)
               if law.uses_ecn else jnp.zeros_like(tau))

        upd = active & (t_sec >= st.next_update)
        dt_obs = jnp.maximum(t_sec - st.last_update, dt)
        obs = PathObs(q=q_obs, qdot=qdot_obs, mu=mu_obs, b=b_hop,
                      valid=valid, theta=theta_obs, w_old=w_old,
                      dt_obs=dt_obs, ecn_frac=ecn,
                      pause=pause_obs, incast=inc_obs)

        # -- control law (kernel-composable registry update) ------------
        law_state, w, rate_cap = law.update(
            st.law, obs, st.w, st.rate_cap, upd, cfg_slot, t_sec)
        w = jnp.clip(w, MTU, _nofma(_pin(8.0 * nic * tau)) +
                     _nofma(_pin(8.0 * nic * theta_now)))
        period = jnp.where(cfg.update_period > 0.0, cfg.update_period,
                           theta_now)
        next_update = jnp.where(upd, t_sec + period, st.next_update)
        last_update = jnp.where(upd, t_sec, st.last_update)

        # -- flow progress; completions park in the pending buffer ------
        lam_good = (lam if keep is None else
                    lam * fluid._hop_keep(keep, path, valid))
        remaining = jnp.where(active,
                              st.remaining - _nofma(_pin(lam_good * dt)),
                              st.remaining)
        done = active & (remaining <= 0.0)
        pend = PendingFCT(
            jnp.where(done, st.slot_flow, pend.flow),
            jnp.where(done, t_sec + _nofma(tau / 2.0) - st.start,
                      pend.val))
        expire = (occupied & (t_sec >= st.stop) &
                  (st.free_at == _INT32_MAX) & ~done)
        free_at = jnp.where(done | expire, st.t + hold + 1, st.free_at)

        st = st._replace(
            t=st.t + 1, w=w, rate_cap=rate_cap, q=q_new, out_rate=out,
            hist_lam=hist_lam, hist_q=hist_qoq, hist_w=hist_w,
            remaining=remaining, free_at=free_at,
            next_update=next_update, last_update=last_update,
            law=law_state)
        if law.uses_pause:
            st = st._replace(pause=pause_new)
        return (st, pend, hold, inv, ovf,
                jnp.sum(jnp.where(active, w, 0.0)), jnp.sum(lam),
                jnp.sum(active.astype(jnp.int32)))

    def tick(carry: MegaCarry, due_t):
        st = carry.state
        t_sec = _nofma(st.t.astype(jnp.float32) * dt)
        bw = fluid._bandwidth(topo, bw_fn, t_sec, sim.impair)
        keep, jit = (impair_vectors(t_sec, sim.impair)
                     if sim.impair is not None else (None, None))
        ptr = jnp.mod(st.t, D)
        c = (st, carry.pend, carry.hold, carry.inv, carry.ovf)
        if gate and quiet and law.masked_updates:
            is_quiet = (due_t == st.cursor) & ~jnp.any(st.slot_flow < N)
            st, pend, hold, inv, ovf, w_sum, lam_sum, n_act = jax.lax.cond(
                is_quiet, lambda a: quiet_tick(a, bw, jit, ptr),
                lambda a: busy_tick(a, bw, keep, jit, ptr, due_t), c)
        else:
            st, pend, hold, inv, ovf, w_sum, lam_sum, n_act = busy_tick(
                c, bw, keep, jit, ptr, due_t)
        rec = Record(t=t_sec, q=st.q, w_sum=w_sum, thru=st.out_rate,
                     lam=lam_sum, lam_f=st.hist_lam[jnp.mod(st.t - 1, D)],
                     n_active=n_act.astype(jnp.int32))
        return MegaCarry(st, pend, hold, inv, ovf), rec

    tick.init_carry = init_carry
    return tick


def make_block_fn(tick: Callable, record: bool,
                  record_every: int = 1) -> Callable:
    """Wrap a megakernel tick into the K-tick block function the Pallas
    lowering runs as ONE kernel invocation:
    ``block_fn(carry, due_block) -> (carry', records)`` with K the length
    of ``due_block`` (the same function serves full and remainder
    blocks). Records accumulate in [K]-row buffers inside the block and
    leave it subsampled by ``record_every`` — the only per-block output
    traffic besides the final state."""
    re = max(int(record_every), 1)

    def block_fn(carry, due_block):
        K = int(due_block.shape[0])
        rec_shape = jax.eval_shape(tick, carry, due_block[0])[1]
        racc0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros((K,) + s.shape, s.dtype), rec_shape)

        def body(k, c):
            carry, racc = c
            carry, rec = tick(carry, due_block[k])
            racc = jax.tree_util.tree_map(
                lambda a, v: a.at[k].set(v), racc, rec)
            return carry, racc

        carry, racc = jax.lax.fori_loop(0, K, body, (carry, racc0))
        recs = (jax.tree_util.tree_map(lambda a: a[re - 1::re], racc)
                if record else None)
        return carry, recs

    return block_fn


def default_impl() -> str:
    """Lowering choice: the flat XLA scan on every platform. The Pallas
    whole-tick harness (``kernels.fused_tick``) cannot lower for TPU —
    Mosaic has no lowering for the tick's gathers, scatter and
    ``dynamic_slice`` — so off-TPU it only runs interpreted."""
    return "xla"


def _unpack_state(carry: MegaCarry, N: int, Q1: int) -> SlotState:
    """Final flush of pending FCTs + unpacking of the telemetry ring back
    into the public SlotState layout."""
    st, pend = carry.state, carry.pend
    fct, _ = _flush_pending(st.fct, pend, pend.flow < N, N)
    return st._replace(fct=fct, hist_q=st.hist_q[:, :Q1],
                       hist_out=st.hist_q[:, Q1:2 * Q1])


def simulate_slots_mega(sim, bw_fn=None, record: bool = True,
                        impl: Optional[str] = None,
                        block: Optional[int] = None,
                        gate: Optional[bool] = None,
                        quiet: bool = False):
    """Run one schedule through the megakernel backend.

    Called by ``fluid.simulate_slots``/``simulate_slots_batch`` when
    ``backend="megakernel"``; same return contract as the reference
    engine: ``(final SlotState, Record pytree | None)``. ``impl`` forces
    a lowering ("pallas" / "xla", default per ``default_impl``);
    ``block`` overrides the Pallas K-tick block size; ``gate``/``quiet``
    control the idle-tick conds (see ``make_tick`` — the batched vmap
    entry disables them).
    """
    impl = impl or default_impl()
    if impl == "pallas" and jax.default_backend() == "tpu":
        raise NotImplementedError(
            "impl='pallas' (the whole-tick Pallas harness) cannot compile "
            "for TPU: Mosaic has no lowering for the tick's gathers, "
            "scatter and dynamic_slice. Use impl='xla' (the default).")
    cfg = sim.cfg
    T = int(cfg.steps)
    re = max(int(cfg.record_every), 1) if record else 1
    if record and re > 1 and T % re:
        raise ValueError(f"steps ({T}) must be divisible by "
                         f"record_every ({re})")
    gate = True if gate is None else gate
    tick = make_tick(sim, bw_fn, gate=gate, quiet=quiet)
    N = fluid._slot_n(sim)
    Q1 = int(sim.topo.num_queues) + 1

    if impl == "pallas":
        K = max(1, min(int(block) if block else DEFAULT_BLOCK, T))
        if re > 1:
            K = max(re, K - K % re)   # whole record rows per block
        block_fn = make_block_fn(tick, record, re)
        run_block = functools.partial(fused_tick_block, block_fn)
        nb, rem = T // K, T % K

        @jax.jit
        def run():
            state0 = fluid.init_slot_state(sim)
            fluid.audit_carry_dtypes(state0)
            carry = tick.init_carry(state0)
            due = _due_table(sim.sched, T, cfg.dt)
            recs = None
            if nb:
                carry, recs = jax.lax.scan(
                    lambda c, d: run_block(c, d), carry,
                    due[:nb * K].reshape(nb, K))
                if record:
                    recs = jax.tree_util.tree_map(
                        lambda x: x.reshape((-1,) + x.shape[2:]), recs)
            if rem:
                carry, rrem = run_block(carry, due[nb * K:])
                if record:
                    recs = (rrem if recs is None else
                            jax.tree_util.tree_map(
                                lambda a, b: jnp.concatenate([a, b]),
                                recs, rrem))
            return _unpack_state(carry, N, Q1), recs

        return run()

    # XLA lowering: the tick scans flat through the reference engine's
    # scan driver (identical record_every chunking) — the whole carry is
    # born inside the jitted program (the strong form of buffer
    # donation: nothing crosses the jit boundary to double-buffer)
    @jax.jit
    def run():
        state0 = fluid.init_slot_state(sim)
        fluid.audit_carry_dtypes(state0)
        carry = tick.init_carry(state0)
        due = _due_table(sim.sched, T, cfg.dt)

        def step_fn(sim_, c, bw_fn=None, alloc_fn=None):
            return tick(c, due[c.state.t])

        carry, recs = fluid._scan_scenario(sim, carry, None, None, record,
                                           step_fn=step_fn)
        return _unpack_state(carry, N, Q1), recs

    return run()
