"""The program under test, as a configuration file describes it.

Everything that touches the program's public API to build a deployment
lives here, in ``bench/deploy/<fabric kind>.py`` and in
``bench/entries/``: the fabric, its link processes, the law constants
and the routing of a scenario's flow arrays.

The ``impairments`` group (its schema in ``reference.py``) becomes the
program's ``ImpairmentParams``: each rule one ``LinkProcess`` for its
(src tier, dst tier) class, compiled by ``fabric_impairments``, with
every field the configuration states (``bw_hi_gbps``, ``bw_lo_gbps``,
``period_s``, ``up_s``, ``t0_s``, ``loss``, ``random_loss``,
``jitter_s``, ``seed``). A rule with ``"t0_s": "fabric"`` takes each
queue's phase from ``phases(f, fabric)`` of ``bench/deploy/<kind>.py``
(``f`` the ``fabric`` group, ``fabric`` the program's ``Fabric`` graph;
[Q] seconds in the program's queue order, NaN where the kind declares
none), put into the ``t0`` leaf; a kind without ``phases`` is an
error.
"""
from __future__ import annotations

import os
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (GBPS, US, LinkProcess, SimConfig, fabric_impairments,
                        make_schedule)
from repro.core.fabric import AGG, CORE, HOST, TOR

from .spec import BENCH, load_module

_TIERS = {"HOST": HOST, "TOR": TOR, "AGG": AGG, "CORE": CORE}


class Deployment(NamedTuple):
    fabric: object          # routes flows: .make_flows(...), .topology()
    topo: object
    impair: object          # ImpairmentParams or None
    sim: SimConfig


def _fabric_kwargs(f: dict) -> dict:
    return dict(host_bw=f["host_gbps"] * GBPS, fabric_bw=f["fabric_gbps"] * GBPS,
                d_host=f["d_host_us"] * US, d_fabric=f["d_fabric_us"] * US,
                buffer_per_port=f["buffer_per_port"],
                switch_buffer=f["switch_buffer"], dt_alpha=f["dt_alpha"])


def _process(p: dict) -> LinkProcess:
    t0 = p.get("t0_s", 0.0)
    return LinkProcess(kind=p["kind"],
                       bw_hi=p.get("bw_hi_gbps", 0.0) * GBPS,
                       bw_lo=p.get("bw_lo_gbps", 0.0) * GBPS,
                       period=p.get("period_s", 0.0), up=p.get("up_s", 0.0),
                       t0=0.0 if t0 == "fabric" else t0,
                       loss=p.get("loss", 0.0),
                       random_loss=p.get("random_loss", False),
                       jitter=p.get("jitter_s", 0.0), seed=p.get("seed", 0))


def _impairments(imp: dict, kind, f: dict, routes):
    """The ``ImpairmentParams`` of an ``impairments`` group."""
    fab = getattr(routes, "fabric", routes)
    by_class = {tuple(_TIERS[t] for t in r["links"]): r
                for r in imp.get("rules", [])}
    default = imp.get("default")
    impair = fabric_impairments(
        routes, rules={k: _process(r) for k, r in by_class.items()},
        default=None if default is None else _process(default))
    ql = fab.queued_links()
    procs = [by_class.get((int(fab.tier[fab.link_src[l]]),
                           int(fab.tier[fab.link_dst[l]])), default)
             for l in ql]
    mine = np.array([p is not None and p.get("t0_s") == "fabric"
                     for p in procs])
    if not mine.any():
        return impair
    if not hasattr(kind, "phases"):
        raise ValueError("the fabric kind declares no phases")
    phase = np.asarray(kind.phases(f, fab), np.float64)
    if np.isnan(phase[mine]).any():
        raise ValueError("the fabric kind declares no phase for queues "
                         f"{np.flatnonzero(mine & np.isnan(phase))}")
    t0 = np.where(mine, phase, np.asarray(impair.t0))
    return impair._replace(t0=jnp.asarray(t0, jnp.float32))


def deploy(cfg: dict, bench_dir: str = BENCH) -> Deployment:
    """The program's deployment of a configuration; the fabric kind's
    constructor is ``<bench_dir>/deploy/<kind>.py`` (``bench_dir`` the
    cell's)."""
    f = cfg["fabric"]
    kind = load_module(os.path.join(bench_dir, "deploy", f["kind"] + ".py"))
    fab, routes = kind.build(f, _fabric_kwargs(f))
    imp = cfg.get("impairments")
    impair = None
    if imp:
        impair = _impairments(imp, kind, f, routes)
    s = cfg["sim"]
    sim = SimConfig(dt=s["dt"], steps=s["steps"], hist=s["hist"],
                    update_period=s["update_period"])
    return Deployment(fab, fab.topology(), impair, sim)


def law_kwargs(cfg: dict) -> dict:
    """Keyword arguments of ``default_law_config``: the law constants the
    configuration states."""
    return dict(cfg["law_config"])


def route(dep: Deployment, groups, dt: float):
    """The program's ``Flows`` of a scenario: each group routed by one
    ``make_flows`` call with its ECMP seed, concatenated in group order."""
    parts = [dep.fabric.make_flows(g["src"], g["dst"], g["size"], g["start"],
                                   dt, seed=g["ecmp_seed"]) for g in groups]
    if len(parts) == 1:
        return parts[0]
    return jax.tree_util.tree_map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *parts)


def schedule(dep: Deployment, groups, dt: float):
    return make_schedule(route(dep, groups, dt))
