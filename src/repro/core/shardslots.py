"""Device-parallel single-scenario slot engine (DESIGN.md section 15).

Every other engine in the repo scales by batching *scenarios*; one large
scenario was still bounded by a single device. This module shards ONE
scenario's slot-pool tick over the device mesh: the flow-slot axis (and
the queue-arrival accumulation) are partitioned over the mesh's ``data``
axis via the ``"slot"``/``"queue"``/``"halo"`` rules in
``sharding/axes.py``, while the cheap-but-sequential parts of the tick
stay replicated. The result is bit-for-bit identical to the
single-device slot engine (``fluid.slot_step``) — the exactness anchor
of the whole repo — which pins the layout:

Replicated on every shard (identical computation per tick):
  * the admit/retire pass's integer bookkeeping and the [S] slot
    metadata it selects (``_admit_global`` mirrors ``_admit_retire``'s
    code line for line) — cumsum-based slot assignment is inherently
    sequential in slot order and costs O(S) int ops;
  * queue state ``q``/``out_rate`` [Q+1], their telemetry rings
    [D, Q+1], the fluid integration (elementwise in Q), and the
    pause/incast feedback rings when the law declares them;
  * the per-tick impairment draws: ``link_bw_at``/``impair_vectors``
    are stateless counter-hash functions of (t, queue), so evaluating
    the full-[Q] vectors once per shard is bitwise-free — only the
    *fold* of loss into the accumulated arrivals and of jitter into the
    hop latencies touches sharded data (the replicated-eval /
    sharded-fold rule).

Sharded [Sl = S/ndev] per shard (the per-tick float work):
  * window/rate/law state and the per-slot rings [D, Sl] — send rates,
    delayed observations, the control-law update;
  * the queue-arrival accumulation: each shard owns a contiguous
    queue-row block and replays its queues' in-order add chains (each
    chain lives wholly on one shard, so the accumulation order — and
    hence every bit — matches the reference scatter);
  * the [N] FCT output (each flow is admitted to exactly one shard's
    slot; per-shard buffers merge by first-finite).

Halo exchange (the communication diet): a slot's compiled fabric path
may cross any shard's queue block, but a full ``[S, H]`` contribution
all-gather moves ndev times more data than any block consumes. Instead
each shard *routes*: at (batched) CSR-rebuild ticks it sorts its local
``[Sl*H]`` hop list by destination queue block and builds a ``[ndev,
cap]`` send-selection table plus, from one ``all_to_all`` of the queue
ids, the receive-side ``[Qb, maxdeg]`` gather table into the ``[ndev *
cap]`` halo buffer. Steady ticks then move only the compacted
per-destination-block contribution rows through one ``all_to_all``.
Receive order is source-major and each source pre-sorts by (queue, flat
index), so every queue's replayed add chain is exactly the reference
scatter's flat slot-major order — bit-for-bit. Every other exchange —
the integrated per-block queue/out (and incast-count) rows plus the
per-slot tail (retire/hold, and the recorded ``lam``/``active``/``w``)
— is concatenated flat and rides ONE packed all-gather at the tail of
the tick: two collectives per steady tick. A ``psum`` of per-shard
partial sums would be cheaper still but is NOT bit-safe (float addition
does not associate).

Replicated per-tick work is kept O(block + slots/ndev): Dynamic-
Thresholds buffer caps fold block-locally from static per-device
switch tables (``_block_caps``), per-slot metadata (paths, delays,
windows) lives slot-sharded in ``ShardLoc``, and the [D, Q] telemetry
ring rows are written *deferred* — tick t's row lands at the start of
tick t+1, before any ring read (every delayed read is >= 1 tick past,
so values are unchanged), which keeps the rings update-in-place under
XLA buffer assignment instead of copying them every tick.

Structure rebuilds are batched: a freshly admitted slot's delayed
contribution is exactly +0.0 until ``tf_steps`` ticks after admission
(the ``admit_t`` ring guard), and +0.0 is an additive identity on the
non-negative arrivals, so the stale tables stay bit-exact for up to
``min(tf) `` ticks. The engine therefore rebuilds on the first
admission-dirty tick of every ``rb_every = min_tf + 1`` window instead
of on every admission — at fabric scale that amortizes the dominant
replicated sort several-fold. Overflow of either table (a hot
destination block beyond ``cap``, a hot queue beyond ``maxdeg``) is
psum-agreed and drops the tick to a bit-identical full-gather scatter
fallback until the next rebuild.

Chunk-streamed schedules compose: the host driver re-anchors a C-entry
schedule window at the replicated cursor between segments, exactly as
``fluid._simulate_slots_chunked`` (same ``_safe_ticks`` proof), so
100k+-flow traces run sharded without resident O(N*H) hop tables.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..kernels.queue_arrivals import (apply_loss, csr_gather_arrivals,
                                      ordered_scatter_add, seg_ranks,
                                      stable_sort_ids, suggest_maxdeg)
from ..launch.mesh import make_mesh
from ..sharding.axes import axes_to_pspec
from . import obs
from .fluid import (_CHUNK_SEG_MAX, _INT32_MAX, _bandwidth, _buffer_caps,
                    _check_impair, _gather_law_cfg, _hop_keep, _hop_sum,
                    _host_window, _incast_count, _marking, _pause_step,
                    _resolve_law, _safe_ticks, _slot_n, SlotSim,
                    audit_carry_dtypes, default_law_config, resolve_devices)
from .impair import (impair_vectors, link_bw_at, link_jitter_at,
                     link_loss_at)
from .laws import Law, LawConfig, _nofma, _pin
from .types import (MTU, FlowSchedule, PathObs, Record, SimConfig,
                    SlotState, Topology)

_AX = "data"


class ShardInfo(NamedTuple):
    """Static shard geometry, fixed at trace time."""
    ndev: int
    Sl: int          # slots per shard (S / ndev)
    Qb: int          # CSR rows per shard (Q+1 rounded up to ndev blocks)
    use_csr: bool    # small pools keep the unrolled scatter, replicated
    maxdeg: int
    cap: int         # halo rows per (source shard, destination block)
    rb_every: int    # admission-batched rebuild cadence (<= min tf + 1)


class ShardGlob(NamedTuple):
    """Replicated tick state: identical bits on every shard.

    Only what the admission bookkeeping genuinely needs globally (the
    integer pool state) and the queue-side rings every slot observes
    stay replicated; all per-slot flow metadata lives in ``ShardLoc``
    so the admit-time selects and schedule gathers run at [Sl], not
    [S]. In CSR mode the queue vectors are carried at the padded block
    width ``q1p = Qb * ndev`` (the pad rows are exactly 0.0 forever, so
    the ring reads — always through ``path < Q`` — never see them)."""
    t: jnp.ndarray
    cursor: jnp.ndarray
    hw: jnp.ndarray
    slot_flow: jnp.ndarray       # [S]
    free_at: jnp.ndarray         # [S]
    q: jnp.ndarray               # [q1p] (CSR) / [Q+1]
    out_rate: jnp.ndarray        # [q1p] / [Q+1]
    hist_q: jnp.ndarray          # [D, q1p] / [D, Q+1]
    hist_out: jnp.ndarray        # [D, q1p] / [D, Q+1]
    # feedback channels: materialized only when the law declares them
    # (None leaves keep the compiled program identical otherwise)
    pause: Optional[jnp.ndarray] = None        # like q
    hist_pause: Optional[jnp.ndarray] = None   # like hist_q
    hist_inc: Optional[jnp.ndarray] = None     # like hist_q
    inc_prev: Optional[jnp.ndarray] = None     # like q


class ShardLoc(NamedTuple):
    """Shard-local tick state: this shard's contiguous [Sl] slot block."""
    w: jnp.ndarray               # [Sl]
    rate_cap: jnp.ndarray        # [Sl]
    remaining: jnp.ndarray       # [Sl]
    next_update: jnp.ndarray     # [Sl]
    last_update: jnp.ndarray     # [Sl]
    admit_t: jnp.ndarray         # [Sl]
    path: jnp.ndarray            # [Sl, H]
    tf_steps: jnp.ndarray        # [Sl, H]
    rtt_steps: jnp.ndarray       # [Sl]
    tau: jnp.ndarray             # [Sl]
    nic_rate: jnp.ndarray        # [Sl]
    start: jnp.ndarray           # [Sl]
    stop: jnp.ndarray            # [Sl]
    hist_lam: jnp.ndarray        # [D, Sl]
    hist_w: jnp.ndarray          # [D, Sl]
    law: object                  # law-state pytree of [Sl] leaves
    fct: jnp.ndarray             # [1, N] per-shard buffer (merged outside)


class ShardCarry(NamedTuple):
    g: ShardGlob
    l: ShardLoc
    inv: Optional[jnp.ndarray]     # [Qb, maxdeg] gather into halo recv
    ovf: Optional[jnp.ndarray]     # replicated structure-overflow flag
    sel: Optional[jnp.ndarray]     # [ndev, cap] send-side gather table
    rb_cur: Optional[jnp.ndarray]  # replicated cursor at last rebuild
    fallback: Optional[jnp.ndarray] = None  # ticks that took ``_full``


def _admit_global(simw: SlotSim, g: ShardGlob, t_sec):
    """The replicated half of ``fluid._admit_retire``: integer slot
    bookkeeping only, identical on every shard (all inputs replicated).
    Returns the updated globals and the admit mask / schedule indices;
    the metadata gathers, float resets and the law re-init are applied
    per shard by ``_shard_tick`` on its own [Sl] slice."""
    sched = simw.sched
    S = int(g.slot_flow.shape[0])
    N = _slot_n(simw)
    sidx = jnp.arange(S, dtype=jnp.int32)

    occupied = g.slot_flow < N
    freeable = occupied & (g.t >= g.free_at)
    slot_flow = jnp.where(freeable, N, g.slot_flow)
    occupied = slot_flow < N

    due = jnp.searchsorted(sched.start, t_sec,
                           side="right").astype(jnp.int32)
    if simw.win_off is not None:
        due = simw.win_off + due
    n_free = S - jnp.sum(occupied.astype(jnp.int32))
    n_admit = jnp.minimum(due - g.cursor, n_free)
    free = ~occupied
    fresh = free & (sidx >= g.hw)
    n_fresh = jnp.minimum(n_admit, jnp.sum(fresh.astype(jnp.int32)))
    take_fresh = fresh & (jnp.cumsum(fresh.astype(jnp.int32)) - 1 < n_fresh)
    recycled = free & (sidx < g.hw)
    take_rec = recycled & (jnp.cumsum(recycled.astype(jnp.int32)) - 1 <
                           n_admit - n_fresh)
    admit = take_fresh | take_rec
    rank = jnp.cumsum(admit.astype(jnp.int32)) - 1
    slot_flow = jnp.where(admit, g.cursor + rank, slot_flow)

    gf = jnp.clip(slot_flow, 0, N - 1)
    if simw.win_off is None:
        gw = gf
    else:
        gw = jnp.clip(slot_flow - simw.win_off, 0,
                      int(sched.start.shape[0]) - 1)

    g = g._replace(
        slot_flow=slot_flow,
        cursor=g.cursor + n_admit,
        hw=g.hw + n_fresh,
        free_at=jnp.where(admit, _INT32_MAX, g.free_at),
    )
    return g, occupied | admit, admit, gw, gf


def _halo_send_tables(path_l: jnp.ndarray, mi: ShardInfo, Q: int):
    """Route this shard's [Sl, H] hop list to destination queue blocks.

    Returns ``(sel, qid, ovf)``: ``sel[d, j]`` is the local flat index of
    the j-th element destined for block d (sentinel ``Sl*H`` when j is
    past the block's count — the consumer maps it to +0.0), ``qid[d, j]``
    the element's row id local to block d (sentinel ``Qb``), and ``ovf``
    whether any destination count exceeds ``cap``. One stable sort by
    global queue id orders elements by (block, queue, flat index) at
    once — blocks are contiguous queue ranges — which is exactly the
    order the receive side needs to replay reference accumulation.
    Invalid (sentinel-queue) hops are dropped: their contributions are
    structurally +0.0 and the sentinel row's sum is +0.0 either way."""
    Sl, H = path_l.shape
    nnz_l = Sl * H
    Qpad = mi.Qb * mi.ndev
    flatq = jnp.where(path_l < Q, path_l, Qpad).reshape(-1)
    sq, order = stable_sort_ids(flatq, Qpad)
    dest = sq // mi.Qb
    dix = jnp.arange(mi.ndev, dtype=jnp.int32)
    starts = jnp.searchsorted(dest, dix, side="left").astype(jnp.int32)
    ends = jnp.searchsorted(dest, dix, side="right").astype(jnp.int32)
    cnt = ends - starts
    ovf = jnp.any(cnt > mi.cap)
    j = jnp.arange(mi.cap, dtype=jnp.int32)
    pos = jnp.minimum(starts[:, None] + j[None, :], nnz_l - 1)
    inside = j[None, :] < jnp.minimum(cnt, mi.cap)[:, None]
    sel = jnp.where(inside, jnp.take(order, pos).astype(jnp.int32), nnz_l)
    qid = jnp.where(inside,
                    jnp.take(sq, pos).astype(jnp.int32) - dix[:, None] * mi.Qb,
                    mi.Qb)
    return sel, qid, ovf


def _halo_recv_csr(rqid: jnp.ndarray, mi: ShardInfo):
    """Invert the received [ndev, cap] halo row ids into the per-block
    CSR gather table [Qb, maxdeg] over the flat [ndev*cap] halo buffer.
    Receive order is source-major and each source's run is (queue, flat)
    sorted, so a stable sort of the flat buffer by queue id yields, per
    queue, exactly the global flat slot-major order — the reference
    scatter's add order. One pack-key sort + one unique-index scatter-set
    per rebuild; overflowing ``maxdeg`` ranks report ``ovf``."""
    R = mi.ndev * mi.cap
    sq, order = stable_sort_ids(rqid.reshape(R), mi.Qb)
    rank = seg_ranks(sq)
    real = sq < mi.Qb
    ovf = jnp.any(real & (rank >= mi.maxdeg))
    cell = jnp.where(real & (rank < mi.maxdeg),
                     sq * mi.maxdeg + jnp.minimum(rank, mi.maxdeg - 1),
                     mi.Qb * mi.maxdeg)
    inv = jnp.full((mi.Qb * mi.maxdeg + 1,), R,
                   jnp.int32).at[cell].set(order.astype(jnp.int32),
                                           mode="drop")
    return inv[:-1].reshape(mi.Qb, mi.maxdeg), ovf


def _block_caps_tables(topo, mi: ShardInfo, q1p: int):
    """Static per-device tables for block-local Dynamic-Thresholds caps.

    Each device needs ``caps`` only for its own queue block, but a
    switch's shared buffer sums over ALL of the switch's queues — which
    may live in other blocks. The queue depths are replicated, so each
    device folds just the switches its block touches: ``swq[d]`` lists
    those switches' queue ids in ascending order (the reference
    scatter-add's per-switch add order; pads index an appended 0.0 —
    an exact +0.0 identity), ``swb[d]`` their shared-buffer sizes,
    ``locrow[d]`` maps each local queue to its switch's fold row and
    ``bufb[d]`` carries the per-queue hard caps (sentinel/pad 1e30)."""
    Q = int(topo.num_queues)
    Qb, ndev = mi.Qb, mi.ndev
    sw = np.asarray(topo.switch_of_queue)
    sbuf = np.broadcast_to(np.asarray(topo.switch_buffer, np.float32),
                           (int(topo.num_switches),))
    buf = np.asarray(topo.buffer, np.float32)
    counts = np.bincount(sw, minlength=int(topo.num_switches))
    deg = int(counts.max()) if counts.size else 0
    full = np.full((int(topo.num_switches), max(deg, 1)), q1p, np.int32)
    order = np.argsort(sw, kind="stable")
    col = np.concatenate([np.arange(c) for c in counts]) \
        if counts.size else np.zeros((0,), np.int64)
    full[sw[order], col] = order.astype(np.int32)

    per_dev = [np.unique(sw[d * Qb:min((d + 1) * Qb, Q)])
               if d * Qb < Q else np.zeros((0,), sw.dtype)
               for d in range(ndev)]
    nswm = max(1, max(len(p) for p in per_dev))
    swq = np.full((ndev, nswm, max(deg, 1)), q1p, np.int32)
    swb = np.zeros((ndev, nswm), np.float32)
    locrow = np.zeros((ndev, Qb), np.int32)
    bufb = np.full((ndev, Qb), 1e30, np.float32)
    for d, sws in enumerate(per_dev):
        swq[d, :len(sws)] = full[sws]
        swb[d, :len(sws)] = sbuf[sws]
        g = np.arange(d * Qb, d * Qb + Qb)
        real = g < Q
        gr = g[real]
        locrow[d, real] = np.searchsorted(sws, sw[gr]).astype(np.int32)
        bufb[d, real] = buf[gr]
    return (jnp.asarray(swq), jnp.asarray(swb), jnp.asarray(locrow),
            jnp.asarray(bufb))


def _block_caps(topo, tabs, q_full: jnp.ndarray, did, gidx: jnp.ndarray):
    """Block slice of ``fluid._buffer_caps`` from the replicated depths —
    bit-equal values, O(block) instead of O(Q) per device."""
    swq, swb, locrow, bufb = tabs
    bufb_d = jnp.take(bufb, did, axis=0)
    if topo.dt_alpha <= 0:
        return bufb_d
    qp = jnp.concatenate([q_full, jnp.zeros((1,), q_full.dtype)])
    swq_d = jnp.take(swq, did, axis=0)                 # [nswm, deg]
    used = jnp.zeros((swq.shape[1],), q_full.dtype)
    for j in range(swq.shape[2]):
        used = used + qp[swq_d[:, j]]
    free = jnp.maximum(jnp.take(swb, did, axis=0) - used, 0.0)
    thr = topo.dt_alpha * free[jnp.take(locrow, did, axis=0)]
    return jnp.where(gidx < int(topo.num_queues),
                     jnp.minimum(thr, bufb_d), bufb_d)


def _shard_tick(simw: SlotSim, mi: ShardInfo, off, blk0,
                carry: ShardCarry, bw_fn, record: bool):
    """One tick, sharded: mirrors ``fluid.slot_step`` operation for
    operation — every local float computation is an elementwise/gather
    slice of the single-device [S] computation (bit-equal under the
    repo's pin/_nofma discipline), and every cross-shard value moves in
    reference order so full-order arithmetic never reassociates."""
    g, loc = carry.g, carry.l
    topo, cfg, law = simw.topo, simw.cfg, simw.law
    N = _slot_n(simw)
    D = cfg.hist
    dt = cfg.dt
    Q = topo.num_queues
    Sl = mi.Sl
    S = Sl * mi.ndev
    q1p = mi.Qb * mi.ndev if mi.use_csr else Q + 1
    with jax.named_scope("rates"):
        t_sec = _nofma(g.t.astype(jnp.float32) * dt)  # mirror of slot_step
        ptr = jnp.mod(g.t, D)

    # -- deferred ring-row writes: tick t-1's queue row lands here, at
    #    the start of tick t — its first possible read (every delayed
    #    read is >= 1 tick in the past). Writing before any ring read
    #    keeps the big [D, q1p] rings update-in-place under XLA buffer
    #    assignment, while every row VALUE stays exactly the reference
    #    one (the driver applies the last pending row on exit).
    with jax.named_scope("queue"):
        ptr_prev = jnp.mod(g.t - 1, D)
        hist_q = g.hist_q.at[ptr_prev].set(g.q)
        hist_out = g.hist_out.at[ptr_prev].set(g.out_rate)
        hist_pause = (g.hist_pause.at[ptr_prev].set(g.pause)
                      if law.uses_pause else None)
        hist_inc = (g.hist_inc.at[ptr_prev].set(g.inc_prev)
                    if law.uses_incast else None)

    with jax.named_scope("rates"):
        if simw.impair is not None and mi.use_csr and mi.ndev > 1:
            # Impairment processes are stateless counter-based draws keyed
            # on the GLOBAL link id, so each shard evaluates only its own
            # queue-block slice of the regime (qid0 offset) and one small
            # [3, Qb] all-gather assembles the full vectors — bitwise the
            # replicated evaluation, at 1/ndev the per-device hash cost.
            pz = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(
                    jnp.concatenate([a, jnp.zeros((q1p - Q,), a.dtype)]),
                    blk0, mi.Qb, 0),
                simw.impair)
            rows = jnp.stack([link_bw_at(t_sec, pz, qid0=blk0),
                              1.0 - link_loss_at(t_sec, pz, qid0=blk0),
                              link_jitter_at(t_sec, pz, qid0=blk0)])
            gathered = jax.lax.all_gather(rows, _AX, axis=1, tiled=True)
            bw = jnp.concatenate([gathered[0, :Q],
                                  jnp.asarray([1e15], jnp.float32)])
            keep = _pin(jnp.concatenate([gathered[1, :Q],
                                         jnp.asarray([1.0], jnp.float32)]))
            jit_v = _pin(jnp.concatenate([gathered[2, :Q],
                                          jnp.asarray([0.0], jnp.float32)]))
        else:
            bw = _bandwidth(topo, bw_fn, t_sec, simw.impair)  # [Q+1]
            keep, jit_v = (impair_vectors(t_sec, simw.impair)
                           if simw.impair is not None else (None, None))

    def sl(x):
        return jax.lax.dynamic_slice_in_dim(x, off, Sl, 0)

    # -- admit / retire: replicated int bookkeeping, local metadata -------
    with jax.named_scope("admit"):
        g2, occupied, admit, gw, gf = _admit_global(simw, g, t_sec)

        adm_l = sl(admit)
        gw_l, gf_l = sl(gw), sl(gf)
        free_at_l, occ_l = sl(g2.free_at), sl(occupied)
        sched = simw.sched
        cfg_slot = _gather_law_cfg(simw.law_cfg, gf_l, N)

        # schedule gathers at [Sl]: same elementwise selects as the reference
        # [S] ones, restricted to this shard's slice
        adm2 = adm_l[:, None]
        path_l = jnp.where(adm2, sched.path[gw_l], loc.path)
        tf_l = jnp.where(adm2, sched.tf_steps[gw_l], loc.tf_steps)
        rtt_l = jnp.where(adm_l, sched.rtt_steps[gw_l], loc.rtt_steps)
        tau_l = jnp.where(adm_l, sched.tau[gw_l], loc.tau)
        nic_l = jnp.where(adm_l, sched.nic_rate[gw_l], loc.nic_rate)
        start_l = jnp.where(adm_l, sched.start[gw_l], loc.start)
        stop_l = jnp.where(adm_l, sched.stop[gw_l], loc.stop)
        admit_t_l = jnp.where(adm_l, g.t, loc.admit_t)

    # -- halo-table rebuild: batched to rb_every-tick windows (a freshly
    #    admitted slot contributes exactly +0.0 for its first min-tf
    #    ticks, so the stale tables stay bit-exact until then) ------------
    with jax.named_scope("halo"):
        if mi.use_csr:
            def rebuild(_):
                s_tab, qid, ovf_cap = _halo_send_tables(path_l, mi, Q)
                rqid = jax.lax.all_to_all(qid, _AX, split_axis=0,
                                          concat_axis=0)
                inv2, ovf_deg = _halo_recv_csr(rqid, mi)
                ovf2 = jax.lax.psum((ovf_cap | ovf_deg).astype(jnp.int32),
                                    _AX) > 0
                return s_tab, inv2, ovf2, g2.cursor

            def keep_tabs(_):
                return carry.sel, carry.inv, carry.ovf, carry.rb_cur

            do_rb = ((g2.cursor > carry.rb_cur) &
                     (jnp.mod(g.t, mi.rb_every) == 0))
            sel_t, inv, ovf, rb_cur = jax.lax.cond(do_rb, rebuild,
                                                   keep_tabs, 0)
        else:
            sel_t, inv, ovf, rb_cur = None, None, None, None

    def _sel(new, old):
        m = adm_l.reshape(adm_l.shape + (1,) * (old.ndim - 1))
        return jnp.where(m, new, old)

    with jax.named_scope("admit"):
        law_state = jax.tree_util.tree_map(
            _sel, law.init(Sl, cfg_slot), loc.law)
        w_cur = _sel(nic_l * tau_l, loc.w)
        rate_cap = _sel(jnp.full((Sl,), jnp.inf, jnp.float32), loc.rate_cap)
        remaining = _sel(sched.size[gw_l].astype(jnp.float32), loc.remaining)
        next_update = _sel((start_l + tau_l).astype(jnp.float32),
                           loc.next_update)
        last_update = _sel(start_l.astype(jnp.float32), loc.last_update)

    # -- instantaneous RTT and send rates (this shard's slot block) -------
    with jax.named_scope("rates"):
        sidx_l = jnp.arange(Sl)
        active = (occ_l & (t_sec >= start_l) & (remaining > 0.0) &
                  (t_sec < stop_l))
        q_hop = g2.q[path_l]                              # [Sl, H]
        b_hop = _pin(bw[path_l])
        valid = path_l < Q
        qb_now = q_hop / b_hop
        if jit_v is not None:
            qb_now = qb_now + jit_v[path_l]
        theta_now = tau_l + _hop_sum(jnp.where(valid, qb_now, 0.0))
        lam = jnp.where(active,
                        jnp.minimum(jnp.minimum(_pin(w_cur / theta_now),
                                                rate_cap),
                                    nic_l), 0.0)
        hist_lam = loc.hist_lam.at[ptr].set(lam)
        hist_w = loc.hist_w.at[ptr].set(w_cur)

    with jax.named_scope("queue"):
        hop_delay_idx = jnp.mod(ptr - tf_l, D)            # [Sl, H]
        lam_del = hist_lam[hop_delay_idx, sidx_l[:, None]]
        lam_del = jnp.where(g.t - tf_l >= admit_t_l[:, None], lam_del, 0.0)
        contrib_l = jnp.where(valid, lam_del, 0.0)

    # -- delayed observation (local reads of replicated rings) ------------
    # Every ring read is at least one tick in the past (tb, wold_delay
    # >= 1 and < D), so the observation/law half never touches this
    # tick's queue fold — which lets its gather rows ride the same
    # collective as the queue blocks below.
    with jax.named_scope("observe"):
        if law.feedback == "hop":
            tb_steps = jnp.clip(tf_l, 1, D - 2)
        else:
            tb_steps = jnp.clip(rtt_l[:, None] - tf_l, 1, D - 2)
        ohidx = jnp.mod(ptr - tb_steps, D)                # [Sl, H]
        ohprev = jnp.mod(ohidx - 1, D)
        q_obs = hist_q[ohidx, path_l]
        q_obs_prev = hist_q[ohprev, path_l]
        qdot_obs = _nofma((q_obs - q_obs_prev) * (1.0 / dt))
        mu_obs = hist_out[ohidx, path_l]
        qb_obs = q_obs / b_hop
        if jit_v is not None:
            qb_obs = qb_obs + jit_v[path_l]
        theta_obs = tau_l + _hop_sum(jnp.where(valid, qb_obs, 0.0))
        wold_delay = jnp.clip(jnp.round(theta_obs / dt).astype(jnp.int32),
                              1, D - 2)
        w_old = hist_w[jnp.mod(ptr - wold_delay, D), sidx_l]
        w_old = jnp.where(g.t - wold_delay >= admit_t_l, w_old,
                          nic_l * tau_l)
        buf_hop = jnp.concatenate(
            [topo.buffer, jnp.asarray([1e30], jnp.float32)])[path_l]
        ecn = jnp.max(jnp.where(valid, _marking(q_obs, buf_hop, cfg_slot),
                                0.0), axis=1)

        upd = active & (t_sec >= next_update)
        dt_obs = jnp.maximum(t_sec - last_update, dt)
        obs = PathObs(q=q_obs, qdot=qdot_obs, mu=mu_obs, b=b_hop,
                      valid=valid, theta=theta_obs, w_old=w_old,
                      dt_obs=dt_obs, ecn_frac=ecn,
                      pause=(hist_pause[ohidx, path_l]
                             if law.uses_pause else None),
                      incast=(hist_inc[ohidx, path_l]
                              if law.uses_incast else None))

    # -- control-law update (shard-local) ---------------------------------
    with jax.named_scope("law"):
        law_state, w_new, rate_cap = law.update(
            law_state, obs, w_cur, rate_cap, upd, cfg_slot, t_sec)
        w_new = jnp.clip(w_new, MTU, _nofma(_pin(8.0 * nic_l * tau_l)) +
                         _nofma(_pin(8.0 * nic_l * theta_now)))
        period = jnp.where(cfg.update_period > 0.0, cfg.update_period,
                           theta_now)
        next_update = jnp.where(upd, t_sec + period, next_update)
        last_update = jnp.where(upd, t_sec, last_update)

    # -- flow progress; FCT scatters into this shard's [N] buffer ---------
    with jax.named_scope("progress"):
        lam_good = (lam if keep is None
                    else lam * _hop_keep(keep, path_l, valid))
        remaining = jnp.where(active,
                              remaining - _nofma(_pin(lam_good * dt)),
                              remaining)
        done = active & (remaining <= 0.0)
        fct = loc.fct.at[0, jnp.where(done, sl(g2.slot_flow), N)].set(
            jnp.where(done, t_sec + _nofma(tau_l / 2.0) - start_l, jnp.nan),
            mode="drop")
        hold = jnp.max(jnp.where(valid, tf_l, 0), axis=1)
        expire = (occ_l & (t_sec >= stop_l) & (free_at_l == _INT32_MAX) &
                  ~done)

        # packed per-slot tail rows: retire/hold (+ the recorded rows);
        # hold <= D-2 < 2^24 is exact in f32
        trows = [(done | expire).astype(jnp.float32),
                 hold.astype(jnp.float32)]
        if record:
            trows += [lam, active.astype(jnp.float32),
                      jnp.where(active, w_new, 0.0)]
        k = len(trows)

    # -- queue update (mirror of fluid._queue_update, reference path) -----
    # Each queue's in-order add chain is replayed wholly on the shard
    # that owns its block, and the whole integration (loss fold, clip,
    # out rate) runs per block; only the folded [Qb] rows — packed with
    # the per-slot tail rows into ONE all-gather — cross shards. On
    # structure overflow the tick falls back to the full contribution
    # table (bit-identical).
    nb = 2 if law.uses_incast else 1
    if mi.use_csr:
        def _halo(cl):
            pad = jnp.concatenate([cl.reshape(-1),
                                   jnp.zeros((1,), jnp.float32)])
            send = pad[sel_t]                          # [ndev, cap]
            if law.uses_incast:
                send = jnp.concatenate(
                    [send, (send > 0.0).astype(jnp.float32)], axis=1)
            recv = jax.lax.all_to_all(send, _AX, split_axis=0,
                                      concat_axis=0)
            zero = jnp.zeros((mi.Qb,), jnp.float32)
            arr_b = csr_gather_arrivals(recv[:, :mi.cap], inv, zero)
            if law.uses_incast:
                return jnp.stack(
                    [arr_b, csr_gather_arrivals(recv[:, mi.cap:], inv,
                                                zero)])
            return arr_b[None]

        def _full(cl):
            contrib = jax.lax.all_gather(cl, _AX, axis=0, tiled=True)
            path_f = jax.lax.all_gather(path_l, _AX, axis=0, tiled=True)
            rows = [ordered_scatter_add(jnp.zeros_like(g2.q), path_f,
                                        contrib)]
            if law.uses_incast:
                rows.append(ordered_scatter_add(
                    jnp.zeros_like(g2.q), path_f,
                    (contrib > 0.0).astype(jnp.float32)))
            return jax.lax.dynamic_slice_in_dim(jnp.stack(rows), blk0,
                                                mi.Qb, 1)

        with jax.named_scope("halo"):
            ab = jax.lax.cond(ovf, _full, _halo, contrib_l)   # [nb, Qb]
            fallback = carry.fallback + ovf.astype(jnp.int32)
        # block-local integration: elementwise slices of the reference
        # [Q+1] chain (identical bits), pad rows pinned at exactly 0.0
        with jax.named_scope("queue"):
            gidx = blk0 + jnp.arange(mi.Qb, dtype=jnp.int32)
            zpad = jnp.zeros((q1p - (Q + 1),), jnp.float32)
            bw_b = jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([bw, zpad]), blk0, mi.Qb, 0)
            cap_tabs = _block_caps_tables(topo, mi, q1p)
            if cap_tabs[0].shape[2] <= 64:
                caps_b = _block_caps(topo, cap_tabs, g2.q, blk0 // mi.Qb,
                                     gidx)
            else:   # pathological switch degree: replicated reference caps
                caps = _buffer_caps(topo,
                                    jax.lax.slice_in_dim(g2.q, 0, Q + 1))
                caps_b = jax.lax.dynamic_slice_in_dim(
                    jnp.concatenate([caps, jnp.full_like(zpad, 1e30)]),
                    blk0, mi.Qb, 0)
            q_b = jax.lax.dynamic_slice_in_dim(g2.q, blk0, mi.Qb, 0)
            arr_b = ab[0]
            if keep is not None:
                # loss folds into the ACCUMULATED arrivals — elementwise
                # on the block, exactly as the reference full-vector fold
                keep_b = jax.lax.dynamic_slice_in_dim(
                    jnp.concatenate([keep, jnp.ones_like(zpad)]),
                    blk0, mi.Qb, 0)
                arr_b = apply_loss(arr_b, keep_b)
            qn_b = jnp.clip(q_b + _nofma(_pin((arr_b - bw_b) * dt)),
                            0.0, caps_b)
            out_b = jnp.where(q_b > 0.0, bw_b, jnp.minimum(arr_b, bw_b))
            qn_b = jnp.where(gidx >= Q, 0.0, qn_b)   # sentinel + pad rows
            brows = [qn_b, out_b] + ([ab[1]] if law.uses_incast else [])
            nb2 = len(brows)

        # ONE packed all-gather moves the queue blocks and the slot tail
        with jax.named_scope("halo"):
            flat = jnp.concatenate([jnp.stack(brows).reshape(-1),
                                    jnp.stack(trows).reshape(-1)])
            gg = jax.lax.all_gather(flat, _AX, axis=0, tiled=False)
            blk = (gg[:, :nb2 * mi.Qb].reshape(mi.ndev, nb2, mi.Qb)
                   .transpose(1, 0, 2).reshape(nb2, q1p))
            tail = (gg[:, nb2 * mi.Qb:].reshape(mi.ndev, k, Sl)
                    .transpose(1, 0, 2).reshape(k, S))
        q_new, out = blk[0], blk[1]
        inc_now = blk[2] if law.uses_incast else None
    else:
        fallback = None
        with jax.named_scope("halo"):
            contrib = jax.lax.all_gather(contrib_l, _AX, axis=0, tiled=True)
            path_f = jax.lax.all_gather(path_l, _AX, axis=0, tiled=True)
        with jax.named_scope("queue"):
            caps = _buffer_caps(topo, g2.q)
            arr = ordered_scatter_add(jnp.zeros_like(g2.q), path_f, contrib)
            inc_now = (_incast_count(g2.q, path_f, path_f < Q, contrib)
                       if law.uses_incast else None)
            if keep is not None:
                arr = apply_loss(arr, keep)
            q_new = jnp.clip(g2.q + _nofma(_pin((arr - bw) * dt)), 0.0,
                             caps)
            out = jnp.where(g2.q > 0.0, bw, jnp.minimum(arr, bw))
            q_new = q_new.at[-1].set(0.0)
        with jax.named_scope("halo"):
            tail = jax.lax.all_gather(jnp.stack(trows), _AX, axis=1,
                                      tiled=True)

    # -- feedback channels (replicated; mirror of slot_step). The fresh
    #    rows (q_new/out/pause_new/inc_now) stay in the flat carry
    #    leaves; next tick's deferred write rings them. -------------------
    with jax.named_scope("queue"):
        pause_new = (_pause_step(q_new, g2.pause, cfg_slot)
                     if law.uses_pause else None)

    with jax.named_scope("progress"):
        free_at = jnp.where(tail[0] > 0.0,
                            g.t + tail[1].astype(jnp.int32) + 1, g2.free_at)

        new_carry = ShardCarry(
            g=g2._replace(t=g.t + 1, q=q_new, out_rate=out, hist_q=hist_q,
                          hist_out=hist_out, free_at=free_at,
                          pause=pause_new, hist_pause=hist_pause,
                          hist_inc=hist_inc,
                          inc_prev=inc_now if law.uses_incast else None),
            l=ShardLoc(w=w_new, rate_cap=rate_cap, remaining=remaining,
                       next_update=next_update, last_update=last_update,
                       admit_t=admit_t_l, path=path_l, tf_steps=tf_l,
                       rtt_steps=rtt_l, tau=tau_l, nic_rate=nic_l,
                       start=start_l, stop=stop_l,
                       hist_lam=hist_lam, hist_w=hist_w, law=law_state,
                       fct=fct),
            inv=inv, ovf=ovf, sel=sel_t, rb_cur=rb_cur, fallback=fallback)
        if record:
            lam_full, act_f, w_act = tail[2], tail[3], tail[4]
            rec = Record(t=t_sec, q=q_new[:Q + 1], w_sum=jnp.sum(w_act),
                         thru=out[:Q + 1], lam=jnp.sum(lam_full),
                         lam_f=lam_full,
                         n_active=jnp.sum(act_f.astype(jnp.int32)))
        else:
            rec = None
    return new_carry, rec


def _init_carry(simw: SlotSim, mi: ShardInfo) -> ShardCarry:
    """Mirror of ``fluid.init_slot_state``, split into the replicated and
    shard-local halves (identical inert values). The halo tables start
    all-sentinel — the initial pool is empty, so the first admission's
    rebuild (cadence-aligned before any contribution turns nonzero)
    populates them."""
    topo, cfg, law = simw.topo, simw.cfg, simw.law
    S = int(simw.slots)
    N = _slot_n(simw)
    H = int(simw.sched.path.shape[1])
    Q = topo.num_queues
    D = cfg.hist
    Sl = mi.Sl
    q1p = mi.Qb * mi.ndev if mi.use_csr else Q + 1
    g = ShardGlob(
        t=jnp.asarray(0, jnp.int32),
        cursor=jnp.asarray(0, jnp.int32),
        hw=jnp.asarray(0, jnp.int32),
        slot_flow=jnp.full((S,), N, jnp.int32),
        free_at=jnp.zeros((S,), jnp.int32),
        q=jnp.zeros((q1p,), jnp.float32),
        out_rate=jnp.zeros((q1p,), jnp.float32),
        hist_q=jnp.zeros((D, q1p), jnp.float32),
        hist_out=jnp.zeros((D, q1p), jnp.float32),
        pause=(jnp.zeros((q1p,), jnp.float32)
               if law.uses_pause else None),
        hist_pause=(jnp.zeros((D, q1p), jnp.float32)
                    if law.uses_pause else None),
        hist_inc=(jnp.zeros((D, q1p), jnp.float32)
                  if law.uses_incast else None),
        inc_prev=(jnp.zeros((q1p,), jnp.float32)
                  if law.uses_incast else None))
    tau0 = jnp.full((Sl,), 20e-6, jnp.float32)
    nic0 = jnp.full((Sl,), 1e9, jnp.float32)
    w0 = nic0 * tau0
    cfg0 = _gather_law_cfg(simw.law_cfg, jnp.zeros((Sl,), jnp.int32), N)
    loc = ShardLoc(
        w=w0,
        rate_cap=jnp.full((Sl,), jnp.inf, jnp.float32),
        remaining=jnp.full((Sl,), jnp.inf, jnp.float32),
        next_update=jnp.full((Sl,), jnp.inf, jnp.float32),
        last_update=jnp.zeros((Sl,), jnp.float32),
        admit_t=jnp.zeros((Sl,), jnp.int32),
        path=jnp.full((Sl, H), Q, jnp.int32),
        tf_steps=jnp.ones((Sl, H), jnp.int32),
        rtt_steps=jnp.ones((Sl,), jnp.int32),
        tau=tau0,
        nic_rate=nic0,
        start=jnp.full((Sl,), jnp.inf, jnp.float32),
        stop=jnp.full((Sl,), jnp.inf, jnp.float32),
        hist_lam=jnp.zeros((D, Sl), jnp.float32),
        hist_w=jnp.broadcast_to(w0, (D, Sl)).astype(jnp.float32),
        law=law.init(Sl, cfg0),
        fct=jnp.full((1, N), jnp.nan, jnp.float32))
    if mi.use_csr:
        inv = jnp.full((mi.Qb, mi.maxdeg), mi.ndev * mi.cap, jnp.int32)
        ovf = jnp.asarray(False)
        sel = jnp.full((mi.ndev, mi.cap), Sl * H, jnp.int32)
        rb_cur = jnp.asarray(0, jnp.int32)
        fallback = jnp.asarray(0, jnp.int32)
    else:
        inv, ovf, sel, rb_cur, fallback = None, None, None, None, None
    return ShardCarry(g=g, l=loc, inv=inv, ovf=ovf, sel=sel,
                      rb_cur=rb_cur, fallback=fallback)


def _carry_specs(mesh, law_template, law: Law,
                 use_csr: bool) -> ShardCarry:
    """PartitionSpec tree for a ShardCarry on ``mesh``: globals
    replicated, slot-axis leaves on the ``"slot"`` rule, CSR rows on
    ``"queue"``, halo send tables on ``"halo"``."""
    slot = axes_to_pspec(("slot",), mesh)
    slot2 = axes_to_pspec(("slot", None), mesh)
    hist = axes_to_pspec((None, "slot"), mesh)
    rep = P()
    g = ShardGlob(*([rep] * 9),
                  pause=rep if law.uses_pause else None,
                  hist_pause=rep if law.uses_pause else None,
                  hist_inc=rep if law.uses_incast else None,
                  inc_prev=rep if law.uses_incast else None)
    law_specs = jax.tree_util.tree_map(lambda _: slot, law_template)
    loc = ShardLoc(w=slot, rate_cap=slot, remaining=slot,
                   next_update=slot, last_update=slot,
                   admit_t=slot, path=slot2, tf_steps=slot2,
                   rtt_steps=slot, tau=slot, nic_rate=slot,
                   start=slot, stop=slot,
                   hist_lam=hist, hist_w=hist, law=law_specs, fct=slot)
    return ShardCarry(g=g, l=loc,
                      inv=axes_to_pspec(("queue",), mesh) if use_csr
                      else None,
                      ovf=rep if use_csr else None,
                      sel=axes_to_pspec(("halo", None), mesh) if use_csr
                      else None,
                      rb_cur=rep if use_csr else None,
                      fallback=rep if use_csr else None)


def _merge_fct(fct_parts: jnp.ndarray) -> jnp.ndarray:
    """[ndev, N] per-shard FCT buffers -> [N]: every flow is admitted to
    exactly one shard's slot, so at most one row is finite per column;
    nanmax selects it without arithmetic (all-NaN columns stay NaN)."""
    return jnp.nanmax(fct_parts, axis=0)


def _shard_geometry(sched_np, S: int, Q: int, ndev: int) -> ShardInfo:
    """Static shard geometry: halo capacity sized to ~2x the uniform
    per-(source, destination-block) element count (skew beyond it drops
    to the bit-identical full-gather fallback until the next rebuild;
    ECMP-routed fabrics sit many sigma inside 2x, and pathological
    skew — e.g. a pure incast block — exceeds ANY per-pair cap and
    lives on the fallback regardless), and the rebuild cadence bounded
    by the schedule's minimum forward hop delay (the +0.0 stale-table
    window; module docstring)."""
    H = int(sched_np.path.shape[1])
    use_csr = S * H > 128
    nnz = S * H
    Sl = S // ndev
    if not use_csr:
        return ShardInfo(ndev=ndev, Sl=Sl, Qb=-(-(Q + 1) // ndev),
                         use_csr=False, maxdeg=1, cap=1, rb_every=1)
    cap = min(Sl * H, max(8, ((2 * nnz // (ndev * ndev)) + 7) // 8 * 8))
    validm = np.asarray(sched_np.path) < Q
    tfv = np.asarray(sched_np.tf_steps)[validm]
    min_tf = int(tfv.min()) if tfv.size else 1
    return ShardInfo(ndev=ndev, Sl=Sl, Qb=-(-(Q + 1) // ndev),
                     use_csr=True,
                     maxdeg=suggest_maxdeg(sched_np.path, Q, S),
                     cap=cap, rb_every=int(min(64, max(1, min_tf + 1))))


def shard_geometry(sched, slots: int, num_queues: int,
                   devices: int) -> ShardInfo:
    """Public wrapper of the static shard-geometry solver: the ShardInfo
    a ``simulate_slots_sharded(..., devices=devices)`` run would use for
    this schedule, without tracing anything. Feed it to ``comm_census``
    for the per-tick communication table (tools/profile_tick.py,
    launch/roofline.py, the fabric16 benchmark leg)."""
    sched_np = jax.tree_util.tree_map(np.asarray, sched)
    return _shard_geometry(sched_np, int(slots), int(num_queues),
                           int(devices))


def comm_census(mi: ShardInfo, S: int, H: int, Q: int,
                record: bool = True, uses_incast: bool = False) -> dict:
    """Analytic per-steady-tick communication table of the sharded tick.

    Returns exchanges per tick and f32 payload bytes moved per device
    per tick for each exchange (``tools/profile_tick.py`` prints it;
    the fabric benchmark emits it as ``fct_fabric16_comm_*``). Rebuild
    ticks add one [ndev, cap] int32 all_to_all plus one scalar psum,
    amortized over ``rb_every``-tick windows; the pre-diet layout —
    full [S, H] contribution gather plus three separate per-slot
    gathers — is reported alongside as the baseline."""
    f32 = 4
    k = 5 if record else 2
    if not mi.use_csr:
        ex = [("contrib_gather", mi.ndev * mi.Sl * H * f32),
              ("path_gather", mi.ndev * mi.Sl * H * f32),
              ("tail_gather", mi.ndev * k * mi.Sl * f32)]
    else:
        width = mi.cap * (2 if uses_incast else 1)
        nb2 = 3 if uses_incast else 2
        ex = [("halo_all_to_all", mi.ndev * width * f32),
              ("packed_gather",
               mi.ndev * (nb2 * mi.Qb + k * mi.Sl) * f32)]
    old = (mi.ndev * (mi.Sl * H + 2 * mi.Sl) * f32 +
           mi.ndev * mi.Qb * f32 + mi.ndev * 2 * mi.Sl * f32 +
           (mi.ndev * mi.Sl * f32 if record else 0))
    total = sum(b for _, b in ex)
    return {
        "exchanges_per_tick": len(ex),
        "bytes_per_tick": total,
        "bytes_per_exchange": dict(ex),
        "rebuild_every": mi.rb_every,
        "rebuild_bytes": (mi.ndev * mi.cap * f32 if mi.use_csr else 0),
        "baseline_exchanges_per_tick": 4 if record else 3,
        "baseline_bytes_per_tick": old,
    }


def _sharded_programs(sim: SlotSim, mi: ShardInfo, mesh, bw_fn,
                      record: bool):
    """(init, get_seg) of a sharded run on ``mesh``: ``init(win, w0)``
    builds the sharded carry from the first schedule window, and
    ``get_seg(L)`` is the jitted program advancing that carry ``L`` ticks
    against a window (memoized per ``L``). Split from
    ``simulate_slots_sharded`` so a mesh of described devices can compile
    the same programs (tests/test_tpu_compile.py)."""
    N = int(sim.sched.start.shape[0])
    law, law_cfg = sim.law, sim.law_cfg
    law_template = jax.eval_shape(
        lambda: law.init(1, _gather_law_cfg(
            law_cfg, jnp.zeros((1,), jnp.int32), N)))
    cspecs = _carry_specs(mesh, law_template, law, mi.use_csr)
    rep = P()

    def init_fn(win, w0):
        simw = sim._replace(sched=win, n_flows=N, win_off=w0)
        carry = _init_carry(simw, mi)
        audit_carry_dtypes(carry)
        return carry

    init_j = jax.jit(shard_map(init_fn, mesh=mesh, in_specs=(rep, rep),
                               out_specs=cspecs, check_vma=False))
    seg_cache = {}

    def get_seg(L):
        if L in seg_cache:
            return seg_cache[L]

        def seg_fn(carry, win, w0):
            simw = sim._replace(sched=win, n_flows=N, win_off=w0)
            ax = jax.lax.axis_index(_AX)
            off = ax * mi.Sl
            blk0 = ax * mi.Qb

            def body(c, _):
                return _shard_tick(simw, mi, off, blk0, c, bw_fn, record)

            return jax.lax.scan(body, carry, None, length=L)

        f = jax.jit(shard_map(seg_fn, mesh=mesh,
                              in_specs=(cspecs, rep, rep),
                              out_specs=(cspecs, rep), check_vma=False))
        seg_cache[L] = f
        return f

    return init_j, get_seg


def simulate_slots_sharded(topo: Topology, sched: FlowSchedule,
                           law_name: Union[str, Law], slots: int,
                           law_cfg: Optional[LawConfig] = None,
                           cfg: Optional[SimConfig] = None,
                           bw_fn: Optional[Callable] = None,
                           record: bool = True,
                           devices=None,
                           chunk: Optional[int] = None,
                           impair=None):
    """Run one schedule with the slot pool sharded over ``devices``.

    Same contract and BIT-IDENTICAL results as
    ``fluid.simulate_slots(topo, sched, law_name, slots, ...)`` on the
    reference backend, for every device count (tests/test_shard_scenario
    holds the property for every registry law — feedback-channel laws
    included — and for impaired regimes; benchmarks gate it at the
    256-host anchor). ``slots`` must divide evenly over the resolved
    device count. ``chunk=C`` streams the schedule in C-entry windows
    exactly as ``simulate_slots(..., chunk=)`` — the two features
    compose, which is what lets a 100k-flow fat-tree trace run sharded.

    ``impair=ImpairmentParams(...)`` applies the per-link impairment
    layer (core/impair.py): the stateless counter-hash draws are
    evaluated replicated on the full [Q] view and only the folds touch
    sharded data, so impaired runs keep the bitwise anchor. Mutually
    exclusive with ``bw_fn`` (same contract as the reference driver).

    ``devices``: None/1 build the same sharded program on a 1-device
    mesh (the collectives no-op; this is the honest single-device
    baseline for scaling numbers), ``"auto"`` uses every local device.
    """
    obs.count("slots.calls")
    with obs.span("slots.prepare"):
        cfg = cfg or SimConfig()
        _check_impair(impair, bw_fn, "reference")
        law = _resolve_law(law_name, "reference")
        law_cfg = law_cfg or default_law_config(sched)
        ndev = resolve_devices(devices)
        S = int(slots)
        if S % ndev:
            raise ValueError(f"slots={S} must divide over {ndev} devices")
        if record and int(cfg.record_every) > 1:
            raise ValueError("sharded runs record every tick; "
                             "record_every > 1 is not supported")
        sim = SlotSim(topo, sched, law, law_cfg, cfg, S, "reference",
                      impair=impair)
        sched_np = jax.tree_util.tree_map(np.asarray, sched)
        N = int(sched_np.start.shape[0])
        Q = int(topo.num_queues)
        T = int(cfg.steps)
        mi = _shard_geometry(sched_np, S, Q, ndev)
        # C >= S keeps the 1-tick fallback exact (see _safe_ticks)
        C = N if chunk is None else min(max(int(chunk), S), max(N, 1))
        start_np = np.asarray(sched_np.start, np.float32)

        mesh = make_mesh((ndev,), (_AX,))
        init_j, get_seg = _sharded_programs(sim, mi, mesh, bw_fn, record)
        win = _host_window(sched_np, 0, C, Q)
    with obs.span("slots.call", program="init", ticks=0):
        carry = init_j(win, jnp.asarray(0, jnp.int32))
    recs = []
    t0 = 0
    while t0 < T:
        with obs.span("chunk.sync"):
            w0 = int(jax.device_get(carry.g.cursor))
        with obs.span("chunk.window"):
            safe = _safe_ticks(start_np, w0, C, t0, T, cfg.dt)
            if w0 + C >= N:
                L = T - t0        # window covers the tail: one segment
            else:
                allowed = max(1, min(max(safe, 1), T - t0, _CHUNK_SEG_MAX))
                L = 1 << (allowed.bit_length() - 1)
            win = _host_window(sched_np, w0, C, Q)
        with obs.span("slots.call", program="segment", ticks=L):
            carry, rec = get_seg(L)(carry, win, jnp.asarray(w0, jnp.int32))
        obs.count("chunk.segments")
        obs.count("slots.ticks", L)
        if record:
            recs.append(rec)
        t0 += L
    if carry.fallback is not None:
        obs.count("halo.fallback_ticks", carry.fallback)

    with obs.span("slots.finish"):
        if record:
            recs = jax.tree_util.tree_map(
                lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
                *recs)
        else:
            recs = None
        g, loc = carry.g, carry.l
        # ring the pending last row (the tick loop defers each row write to
        # the next tick's start; see _shard_tick) so the returned histories
        # match the reference state exactly
        last = jnp.mod(g.t - 1, int(cfg.hist))

        def _ring(h, row):
            return None if h is None else h.at[last].set(row)[:, :Q + 1]

        state = SlotState(
            t=g.t, cursor=g.cursor, hw=g.hw, slot_flow=g.slot_flow,
            admit_t=loc.admit_t, free_at=g.free_at, path=loc.path,
            tf_steps=loc.tf_steps, rtt_steps=loc.rtt_steps, tau=loc.tau,
            nic_rate=loc.nic_rate, start=loc.start, stop=loc.stop, w=loc.w,
            rate_cap=loc.rate_cap, q=g.q[:Q + 1], out_rate=g.out_rate[:Q + 1],
            hist_lam=loc.hist_lam, hist_q=_ring(g.hist_q, g.q),
            hist_out=_ring(g.hist_out, g.out_rate),
            hist_w=loc.hist_w, remaining=loc.remaining,
            next_update=loc.next_update, last_update=loc.last_update,
            law=loc.law, fct=_merge_fct(loc.fct), incidence=None,
            pause=None if g.pause is None else g.pause[:Q + 1],
            hist_pause=_ring(g.hist_pause, g.pause),
            hist_inc=_ring(g.hist_inc, g.inc_prev))
        return state, recs
