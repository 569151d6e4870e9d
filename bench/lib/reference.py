"""Plain reference of the fluid model: every flow of a scenario, every
tick, in straightforward ``jax.numpy``.

It imports nothing of the program and takes nothing the program made:
routes come from ``fabrics.py``, the link processes from the
configuration file, the flows from the traffic generator. It runs no
slot pool, no chunk window and no sharded tick. It follows the model the
program documents (DESIGN.md sections 9, 12, 14 and 17 of the program):

  rate        lam_i = min(w_i / theta_i, rate_cap_i, nic_i), theta_i the
              base RTT plus the queueing delay of the path now
  queues      q_j += (sum of lam_i(t - tf_ij) over flows through j, times
              the link's keep fraction, - b_j) * dt, clipped to
              [0, cap_j], cap_j the Dynamic-Thresholds share of the
              switch's free buffer
  telemetry   each hop's queue, queue gradient and egress rate as they
              were rtt_i - tf_ij ticks ago, the window of one measured
              RTT ago
  links       each queue's capacity and keep fraction at the tick's time,
              from its link process (``build_links``, ``link_state``)
  laws        each a file ``bench/laws/<law>.py`` (PowerTCP with INT,
              HPCC, TIMELY, reTCP as published), on a timer of
              ``update_period``; a law sees the run's ``Links`` as
              ``constants["links"]``
  progress    remaining_i -= lam_i * keep(path_i) * dt; the FCT is the
              tick's time plus half the base RTT minus the start

``dtype`` sets the precision of the flow and queue state and of its
arithmetic; the clock (tick times, timers, starts, FCTs) stays float32.
``jnp.bfloat16`` gives the control that ``correct`` must refuse.

Link processes come from the configuration's ``impairments`` group:
``{"rules": [...], "default": {...}}``, each rule naming the class it
applies to as ``"links": [<src tier>, <dst tier>]`` (a later rule of a
class wins; queues of no rule's class take ``default``, else no
process). A process has a ``kind``:

  const       the link's own capacity
  oscillate   a triangle wave between ``bw_lo_gbps`` (required) and the
              link's capacity over ``period_s``
  schedule    a circuit's day/night square wave: ``bw_hi_gbps`` (default
              the link's capacity) for the first ``up_s`` of every
              ``period_s``, ``bw_lo_gbps`` (required) for the rest

and may add ``t0_s``, the phase (default 0): ``ph = mod(t - t0 +
nudge, period)``, the triangle's position or the circuit up while
``0 <= ph < up_s`` (DESIGN.md section 17). ``"t0_s": "fabric"`` gives
each queue of the rule its phase from the fabric kind's description,
``phase_s`` ([Q] seconds, NaN where the kind declares none); asking for
it where there is none is an error. ``loss`` (a fraction), with
``random_loss`` drawn per ``period_s``-long epoch from ``seed``, is
kept on any kind. The reference models no jitter and refuses a process
that asks for it.
"""
from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from . import fabrics
from .spec import BENCH, load_module

MTU = 1000.0
_NUDGE = 1e-7          # keeps an epoch edge off the tick grid
_SALT_LOSS = 0x2c1b3c6d
LOSS_MAX = 0.999


class Flows(NamedTuple):
    path: jnp.ndarray      # [F, H] int32, Q for an unused hop
    tf: jnp.ndarray        # [F, H] int32 forward delay to each hop (ticks)
    rtt: jnp.ndarray       # [F] int32 base RTT (ticks)
    tau: jnp.ndarray       # [F] f32 base RTT (s)
    nic: jnp.ndarray       # [F] f32 NIC rate (bytes/s)
    size: jnp.ndarray      # [F] f32 bytes
    start: jnp.ndarray     # [F] f32 s


class Links(NamedTuple):
    bw: jnp.ndarray        # [Q] f32 bytes/s (the wave's peak, the day's)
    buf: jnp.ndarray       # [Q] f32 bytes
    sw: jnp.ndarray        # [Q] int32 switch that owns the queue
    osc: jnp.ndarray       # [Q] bool: capacity follows a triangle wave
    bw_lo: jnp.ndarray     # [Q] f32 bytes/s at the trough, the night's
    period: jnp.ndarray    # [Q] f32 s (wave period or week, loss epoch)
    loss: jnp.ndarray      # [Q] f32 loss fraction (or its cap if random)
    loss_random: jnp.ndarray   # [Q] bool
    seed: jnp.ndarray      # [Q] uint32
    sched: jnp.ndarray     # [Q] bool: capacity follows a day/night wave
    up: jnp.ndarray        # [Q] f32 s of each period at bw (the day)
    t0: jnp.ndarray        # [Q] f32 s phase


def build_flows(desc, groups, dt: float, horizon_s: float) -> Flows:
    """Route the flow groups of a scenario and keep, in start order
    (stable, as the program's schedule sorts), the flows that start
    inside the horizon: the first entries of the program's
    schedule-ordered FCT vector."""
    parts = []
    for g in groups:
        path, tf, rtt = desc.route(g["src"], g["dst"], g["ecmp_seed"])
        parts.append((path, tf, rtt, g["size"], g["start"],
                      np.full(len(g["src"]), desc.host_bw)))
    cat = [np.concatenate(x) for x in zip(*parts)]
    path, tf, rtt, size, start, nic = cat
    start32 = start.astype(np.float32)
    order = np.argsort(start32, kind="stable")
    keep = order[start32[order] < np.float32(horizon_s)]
    return Flows(
        path=jnp.asarray(path[keep], jnp.int32),
        tf=jnp.asarray(np.round(tf[keep] / dt), jnp.int32),
        rtt=jnp.asarray(np.maximum(np.round(rtt[keep] / dt), 1), jnp.int32),
        tau=jnp.asarray(rtt[keep].astype(np.float32)),
        nic=jnp.asarray(nic[keep].astype(np.float32)),
        size=jnp.asarray(size[keep].astype(np.float32)),
        start=jnp.asarray(start32[keep]))


def pad(fl: Flows, n_queues: int, multiple: int = 512) -> Flows:
    """``fl`` padded with flows that never start (no hop, start at
    infinity) to a multiple of ``multiple`` flows, so that scenarios of
    nearby sizes share one compiled reference; their FCTs stay NaN and
    they add nothing to any queue."""
    F = fl.tau.shape[0]
    n = -F % multiple

    def ext(x, v):
        fill = jnp.full((n,) + x.shape[1:], v, x.dtype)
        return jnp.concatenate([x, fill])

    return Flows(path=ext(fl.path, n_queues), tf=ext(fl.tf, 0),
                 rtt=ext(fl.rtt, 1), tau=ext(fl.tau, 1e-6),
                 nic=ext(fl.nic, 1.0), size=ext(fl.size, 1.0),
                 start=ext(fl.start, np.inf))


def build_links(desc, impair: dict | None) -> Links:
    """Per-queue capacities, buffers and link processes from the
    configuration's ``fabric`` and ``impairments`` groups (the schema in
    the module docstring)."""
    Q = desc.Q
    bw = desc.bandwidth.astype(np.float32)
    osc = np.zeros(Q, bool)
    sched = np.zeros(Q, bool)
    bw_lo = bw.copy()
    period = np.zeros(Q, np.float32)
    up = np.zeros(Q, np.float32)
    t0 = np.zeros(Q, np.float32)
    loss = np.zeros(Q, np.float32)
    loss_random = np.zeros(Q, bool)
    seed = np.zeros(Q, np.uint32)
    rules = (impair or {}).get("rules", [])
    default = (impair or {}).get("default")
    for q in range(Q):
        cls = tuple(int(x) for x in desc.link_class[q])
        proc = default
        for r in rules:
            if tuple(fabrics.TIERS[t] for t in r["links"]) == cls:
                proc = r
        if proc is None:
            continue
        if proc.get("jitter_s", 0.0):
            raise ValueError("the reference has no jitter")
        if proc["kind"] == "oscillate":
            osc[q] = True
            bw_lo[q] = np.float32(proc["bw_lo_gbps"] * fabrics.GBPS)
        elif proc["kind"] == "schedule":
            sched[q] = True
            bw_lo[q] = np.float32(proc["bw_lo_gbps"] * fabrics.GBPS)
            if "bw_hi_gbps" in proc:
                bw[q] = np.float32(proc["bw_hi_gbps"] * fabrics.GBPS)
            up[q] = proc["up_s"]
        elif proc["kind"] != "const":
            raise ValueError(f"the reference has no {proc['kind']!r} "
                             f"link process")
        t0[q] = _phase(desc, proc.get("t0_s", 0.0), q)
        period[q] = proc.get("period_s", 0.0)
        loss[q] = proc.get("loss", 0.0)
        loss_random[q] = proc.get("random_loss", False)
        seed[q] = proc.get("seed", 0)
    buf = np.full(Q, desc.buffer_per_port, np.float32)
    return Links(jnp.asarray(bw), jnp.asarray(buf),
                 jnp.asarray(desc.switch_of_queue, jnp.int32),
                 jnp.asarray(osc), jnp.asarray(bw_lo), jnp.asarray(period),
                 jnp.asarray(loss), jnp.asarray(loss_random),
                 jnp.asarray(seed), jnp.asarray(sched), jnp.asarray(up),
                 jnp.asarray(t0))


def _phase(desc, t0, q: int) -> float:
    """A rule's ``t0_s`` for queue ``q``: the number, or with
    ``"fabric"`` the fabric kind's ``phase_s[q]``."""
    if t0 != "fabric":
        return t0
    phase = getattr(desc, "phase_s", None)
    if phase is None or np.isnan(phase[q]):
        raise ValueError(f"the fabric kind declares no phase for queue {q}")
    return phase[q]


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7feb352d)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846ca68b)
    return x ^ (x >> 16)


def circuit_up(t_sec, L: Links):
    """[Q] bool: is each scheduled link in its day at ``t_sec``?"""
    ph = jnp.mod(t_sec - L.t0 + _NUDGE, L.period)
    return L.sched & (ph >= 0.0) & (ph < L.up)


def link_state(t_sec, L: Links):
    """(capacity [Q], keep fraction [Q]) of every link at ``t_sec``."""
    ph = jnp.mod(t_sec - L.t0 + _NUDGE, L.period)
    tri = 1.0 - jnp.abs(2.0 * (ph / L.period) - 1.0)
    bw = jnp.where(L.osc, L.bw_lo + (L.bw - L.bw_lo) * tri, L.bw)
    bw = jnp.where(L.sched & ~circuit_up(t_sec, L), L.bw_lo, bw)
    epoch = jnp.floor((t_sec - L.t0 + _NUDGE) / jnp.maximum(L.period, 1e-6))
    qid = jnp.arange(L.bw.shape[0], dtype=jnp.uint32)
    h = _mix32(L.seed ^ jnp.uint32(_SALT_LOSS))
    h = _mix32(h ^ (qid * jnp.uint32(0x9E3779B9)))
    h = _mix32(h ^ epoch.astype(jnp.int32).astype(jnp.uint32))
    u = (h >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    loss = jnp.where(L.loss_random, L.loss * u, L.loss)
    return bw, 1.0 - jnp.clip(loss, 0.0, LOSS_MAX)


def smooth(prev, new, dt_obs, tau):
    """Exponential smoothing over ``tau`` of a reading taken ``dt_obs``
    after the last (the laws' shared filter)."""
    d = jnp.clip(dt_obs, 0.0, tau)
    return (prev * (tau - d) + new * d) / tau


@lru_cache(maxsize=None)
def law_rule(name: str, bench_dir: str = BENCH):
    """The control law ``name``: ``<bench_dir>/laws/<name>.py``, with
    ``init(flows, constants, dtype) -> state`` and
    ``update(state, obs, w, cap, due, flows, constants, t) -> (state, w,
    cap)``; ``constants`` holds the configuration's ``law_config``,
    ``beta`` and the run's ``Links`` as ``links``. A later law is added
    as a file alone."""
    return load_module(os.path.join(bench_dir, "laws", name + ".py"))


class Sim(NamedTuple):
    """Static settings of one reference run."""
    law: str
    dt: float
    steps: int
    hist: int
    update_period: float
    n_switches: int
    switch_buffer: float
    dt_alpha: float
    dtype: str = "float32"
    bench_dir: str = BENCH       # where ``law_rule`` finds the law


def law_constants(law_cfg: dict, fl: Flows, L: Links, ft):
    c = {k: v for k, v in law_cfg.items() if k != "expected_flows"}
    c["beta"] = (fl.nic * fl.tau / law_cfg["expected_flows"]).astype(ft)
    c["links"] = L
    return c


@partial(jax.jit, static_argnums=(0,))
def simulate(sim: Sim, fl: Flows, L: Links, law_cfg: dict):
    """Run ``sim.steps`` ticks; returns the [F] FCT vector (NaN where the
    flow did not finish)."""
    ft = jnp.dtype(sim.dtype)
    F, Hh = fl.path.shape
    Q = L.bw.shape[0]
    D, dt = sim.hist, sim.dt
    rule = law_rule(sim.law, sim.bench_dir)
    c = law_constants(law_cfg, fl, L, ft)
    valid = fl.path < Q
    fidx = jnp.arange(F)
    nic = fl.nic.astype(ft)
    tau = fl.tau.astype(ft)
    w0 = nic * tau
    w_max_base = 8.0 * nic * tau
    tb = jnp.clip(fl.rtt[:, None] - fl.tf, 1, D - 2)     # telemetry age
    sentinel = jnp.asarray([1e15], ft)

    def tick(s, _):
        t = s["t"]
        t_sec = t.astype(jnp.float32) * jnp.float32(dt)
        ptr = t % D
        bw_l, keep_l = link_state(t_sec, L)
        bw = jnp.concatenate([bw_l.astype(ft), sentinel])
        keep = jnp.concatenate([keep_l.astype(ft), jnp.ones((1,), ft)])
        started = t_sec >= fl.start
        active = started & (s["rem"] > 0)
        b_hop = bw[fl.path]
        qb = jnp.where(valid, s["q"][fl.path] / b_hop, 0.0)
        theta_now = tau + qb.sum(axis=1)
        lam = jnp.where(active, jnp.minimum(
            jnp.minimum(s["w"] / theta_now, s["cap"]), nic), 0.0)
        h_lam = s["h_lam"].at[ptr].set(lam)
        h_w = s["h_w"].at[ptr].set(s["w"])
        # arrivals: each hop sees the flow's rate of tf ticks ago
        lam_del = h_lam[(ptr - fl.tf) % D, fidx[:, None]]
        arr = jax.ops.segment_sum(jnp.where(valid, lam_del, 0.0).ravel(),
                                  fl.path.ravel(), num_segments=Q + 1)
        arr = arr * keep
        used = jax.ops.segment_sum(s["q"][:Q], L.sw,
                                   num_segments=sim.n_switches)
        free = jnp.maximum(sim.switch_buffer - used, 0.0)
        cap_q = jnp.minimum(sim.dt_alpha * free[L.sw], L.buf.astype(ft))
        caps = jnp.concatenate([cap_q, jnp.asarray([1e30], ft)])
        q_new = jnp.clip(s["q"] + (arr - bw) * dt, 0.0, caps).at[Q].set(0.0)
        out = jnp.where(s["q"] > 0, bw, jnp.minimum(arr, bw))
        h_q = s["h_q"].at[ptr].set(q_new)
        h_out = s["h_out"].at[ptr].set(out)
        # what the sender learns now: hop state of tb ticks ago
        oi = (ptr - tb) % D
        q_obs = h_q[oi, fl.path]
        q_prev = h_q[(oi - 1) % D, fl.path]
        theta_obs = tau + jnp.where(valid, q_obs / b_hop, 0.0).sum(axis=1)
        w_age = jnp.clip(jnp.round(theta_obs / dt).astype(jnp.int32),
                         1, D - 2)
        obs = dict(q=q_obs, qdot=(q_obs - q_prev) * (1.0 / dt),
                   mu=h_out[oi, fl.path], b=b_hop, valid=valid,
                   theta=theta_obs, theta32=theta_obs.astype(jnp.float32),
                   w_old=h_w[(ptr - w_age) % D, fidx],
                   dt_obs=jnp.maximum(t_sec - s["last"], dt).astype(ft))
        upd = active & (t_sec >= s["next"])
        lw, w, cap = rule.update(s["law"], obs, s["w"], s["cap"], upd, fl,
                                c, t_sec)
        w = jnp.clip(w, MTU, w_max_base + 8.0 * nic * theta_now)
        w = jnp.where(started, w, s["w"])
        nxt = jnp.where(upd, t_sec + jnp.float32(sim.update_period),
                        s["next"])
        last = jnp.where(upd, t_sec, s["last"])
        path_keep = jnp.prod(jnp.where(valid, keep[fl.path], 1.0), axis=1)
        rem = jnp.where(active, s["rem"] - lam * path_keep * dt, s["rem"])
        done = active & (rem <= 0) & jnp.isnan(s["fct"])
        fct = jnp.where(done, t_sec + fl.tau / 2.0 - fl.start, s["fct"])
        return dict(t=t + 1, w=w.astype(ft), cap=cap.astype(ft), q=q_new,
                    rem=rem, fct=fct, next=nxt, last=last, law=lw,
                    h_lam=h_lam, h_w=h_w, h_q=h_q, h_out=h_out), None

    s0 = dict(t=jnp.asarray(0, jnp.int32), w=w0,
              cap=jnp.full((F,), jnp.inf, ft), q=jnp.zeros((Q + 1,), ft),
              rem=fl.size.astype(ft), fct=jnp.full((F,), jnp.nan, jnp.float32),
              next=fl.start + fl.tau, last=fl.start,
              law=rule.init(fl, c, ft),
              h_lam=jnp.zeros((D, F), ft), h_w=jnp.broadcast_to(w0, (D, F)),
              h_q=jnp.zeros((D, Q + 1), ft), h_out=jnp.zeros((D, Q + 1), ft))
    s, _ = jax.lax.scan(tick, s0, None, length=sim.steps)
    return s["fct"]
