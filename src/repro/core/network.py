"""Topology and scenario builders for the fluid simulator.

Since the fabric-graph refactor (DESIGN.md section 14) every topology is
an instance of the declarative fabric graph + routing compiler in
``core/fabric.py``:

  * ``single_bottleneck`` — the paper's analytical model (one shared
    queue), derived from ``fabric.single_bottleneck_fabric``.
  * ``LeafSpine``          — a thin facade over
    ``fabric.leaf_spine_fabric``: oversubscribed datacenter fabric for
    the FCT experiments (server 25G links, 100G fabric links, per-queue
    model of ToR uplinks / spine downlinks / host downlinks, ECMP by
    deterministic per-flow hash). Multi-spine is just ``spines=N``.
  * fat-tree and anything else — build straight through ``core.fabric``
    (``fat_tree(k)``, or your own ``FabricBuilder`` graph).

The facade keeps the historical queue layout and per-flow arithmetic
bit-for-bit (tests/test_fabric.py anchors compiled-vs-legacy paths); the
one behavioral change is sanctioned and documented there: multi-spine
path selection is a seedable deterministic ECMP hash
(``fabric.ecmp_hash``) instead of a hidden global-RNG draw.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from . import obs
from .fabric import (FabricRoutes, compile_routes, leaf_spine_fabric,
                     single_bottleneck_fabric)
from .types import Flows, FlowSchedule, Topology, GBPS, US


def make_schedule(flows: Flows) -> FlowSchedule:
    """Sort a ``Flows`` batch by arrival time into a ``FlowSchedule``.

    The sort is stable, so flows sharing a start time keep their original
    relative order — together with the slot engine's fresh-first slot
    assignment this is what makes the ``S >= N`` exactness anchor
    bit-for-bit (slot i holds schedule entry i; see DESIGN.md section 12).
    ``order`` records the original index of each schedule entry.
    """
    with obs.span("schedule.build", flows=int(flows.start.shape[0])):
        start = np.asarray(flows.start)
        perm = np.argsort(start, kind="stable")

        def take(x):    # on the host: a device gather compiles per N
            return jnp.asarray(np.asarray(x)[perm])

        return FlowSchedule(
            path=take(flows.path), tf_steps=take(flows.tf_steps),
            rtt_steps=take(flows.rtt_steps), tau=take(flows.tau),
            nic_rate=take(flows.nic_rate), size=take(flows.size),
            start=take(flows.start), stop=take(flows.stop),
            weight=take(flows.weight),
            order=jnp.asarray(perm.astype(np.int32)))


def schedule_as_flows(sched: FlowSchedule) -> Flows:
    """View a schedule as a plain ``Flows`` batch (schedule order kept).

    This is the padded-engine twin the slot engine is asserted against:
    ``simulate(topo, schedule_as_flows(s), ...)`` and
    ``simulate_slots(topo, s, ..., slots >= N)`` must produce identical
    trajectories.
    """
    return Flows(path=sched.path, tf_steps=sched.tf_steps,
                 rtt_steps=sched.rtt_steps, tau=sched.tau,
                 nic_rate=sched.nic_rate, size=sched.size,
                 start=sched.start, stop=sched.stop, weight=sched.weight)


def single_bottleneck(bandwidth: float = 25 * GBPS,
                      buffer: float = 6e6,
                      dt_alpha: float = 0.0) -> Topology:
    """One shared queue — emitted by the fabric compiler (bit-identical
    to the historical hand-built ``Topology``)."""
    return single_bottleneck_fabric(bandwidth=bandwidth, buffer=buffer,
                                    dt_alpha=dt_alpha).topology()


def make_flows_single(n: int, tau: float, nic: float,
                      sizes=None, starts=None, stops=None,
                      weights=None, sim_dt: float = 1e-6,
                      hops_fwd_delay: float = 0.5) -> Flows:
    """All n flows traverse the single queue 0."""
    size = jnp.full((n,), jnp.inf, jnp.float32) if sizes is None \
        else jnp.asarray(sizes, jnp.float32)
    start = jnp.zeros((n,), jnp.float32) if starts is None \
        else jnp.asarray(starts, jnp.float32)
    stop = jnp.full((n,), jnp.inf, jnp.float32) if stops is None \
        else jnp.asarray(stops, jnp.float32)
    weight = jnp.ones((n,), jnp.float32) if weights is None \
        else jnp.asarray(weights, jnp.float32)
    tf = int(round(hops_fwd_delay * tau / sim_dt))
    return Flows(
        path=jnp.zeros((n, 1), jnp.int32),
        tf_steps=jnp.full((n, 1), tf, jnp.int32),
        rtt_steps=jnp.full((n,), max(int(round(tau / sim_dt)), 1), jnp.int32),
        tau=jnp.full((n,), tau, jnp.float32),
        nic_rate=jnp.full((n,), nic, jnp.float32),
        size=size, start=start, stop=stop, weight=weight,
    )


@dataclasses.dataclass
class LeafSpine:
    """Thin facade over ``fabric.leaf_spine_fabric`` (queue layout:
      up[r, s]      ToR r -> spine s uplink          idx = r*S + s
      down[s, r]    spine s -> ToR r downlink        idx = R*S + s*R + r
      host[r, h]    ToR r -> host (r,h) downlink     idx = 2*R*S + r*H + h
    — preserved bit-for-bit by the compiler's queued-link declaration
    order). Path compilation, forward delays, RTTs and ECMP live in
    ``core.fabric``; this class only carries the parameterization and
    the workload-facing protocol (``n_hosts`` / ``host_group`` /
    ``load_capacity`` / ``make_flows``)."""
    racks: int = 4
    hosts_per_rack: int = 16
    spines: int = 1
    host_bw: float = 25 * GBPS                   # 25 Gbps server links
    fabric_bw: float = 100 * GBPS                # 100 Gbps fabric links
    d_host: float = 1 * US                       # host<->ToR propagation
    d_fabric: float = 5 * US                     # ToR<->spine propagation
    buffer_per_port: float = 6e6
    switch_buffer: float = 24e6                  # Tofino-like shallow shared
    dt_alpha: float = 1.0
    ecmp_seed: int = 0

    def __post_init__(self):
        R, S, H = self.racks, self.spines, self.hosts_per_rack
        self.n_hosts = R * H
        self.num_queues = 2 * R * S + R * H
        self._routes: Optional[FabricRoutes] = None

    def routes(self) -> FabricRoutes:
        """The compiled fabric (built lazily, cached)."""
        if self._routes is None:
            self._routes = compile_routes(leaf_spine_fabric(
                racks=self.racks, hosts_per_rack=self.hosts_per_rack,
                spines=self.spines, host_bw=self.host_bw,
                fabric_bw=self.fabric_bw, d_host=self.d_host,
                d_fabric=self.d_fabric,
                buffer_per_port=self.buffer_per_port,
                switch_buffer=self.switch_buffer,
                dt_alpha=self.dt_alpha), seed=self.ecmp_seed)
        return self._routes

    def oversubscription(self) -> float:
        return (self.hosts_per_rack * self.host_bw) / (self.spines * self.fabric_bw)

    def topology(self) -> Topology:
        return self.routes().topology()

    def host_down_queue(self, r, h):
        R, S, H = self.racks, self.spines, self.hosts_per_rack
        return 2 * R * S + r * H + h

    def host_group(self) -> np.ndarray:
        """[n_hosts] rack id per host (the workload cross-group key)."""
        return np.arange(self.n_hosts) // self.hosts_per_rack

    def host_ingress_queue(self, host: int) -> int:
        H = self.hosts_per_rack
        return self.host_down_queue(host // H, host % H)

    def load_capacity(self) -> float:
        """Offered-load base: aggregate ToR uplink bandwidth (the paper's
        load definition on this oversubscribed fabric — kept as the exact
        historical product, not the compiler's link sum, so workload
        arrival processes are bit-stable across the migration)."""
        return self.racks * self.spines * self.fabric_bw

    def make_flows(self, src: np.ndarray, dst: np.ndarray, sizes: np.ndarray,
                   starts: np.ndarray, sim_dt: float,
                   weights: Optional[np.ndarray] = None,
                   rng: Optional[np.random.Generator] = None,
                   seed: Optional[int] = None) -> Flows:
        """src/dst are host ids in [0, racks*hosts_per_rack).

        Paths come from the routing compiler with deterministic per-flow
        ECMP hashing (``fabric.ecmp_hash``; seedable via ``seed`` /
        ``ecmp_seed``). ``rng`` is accepted for backwards compatibility
        but no longer consulted — the historical implementation drew the
        spine pick from it, which made compiled paths depend on global
        RNG call order across processes.
        """
        del rng
        return self.routes().make_flows(src, dst, sizes, starts, sim_dt,
                                        weights=weights, seed=seed)
