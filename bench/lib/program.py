"""The program under test, as a configuration file describes it.

Everything that touches the program's public API to build a deployment
lives here, in ``bench/deploy/<fabric kind>.py`` and in
``bench/entries/``: the fabric, its link processes, the law constants
and the routing of a scenario's flow arrays.
"""
from __future__ import annotations

import os
from typing import NamedTuple

import numpy as np

import jax

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
    return LinkProcess(kind=p["kind"],
                       bw_lo=p.get("bw_lo_gbps", 0.0) * GBPS,
                       period=p.get("period_s", 0.0), loss=p.get("loss", 0.0),
                       random_loss=p.get("random_loss", False),
                       seed=p.get("seed", 0))


def deploy(cfg: dict) -> Deployment:
    f = cfg["fabric"]
    kind = load_module(os.path.join(BENCH, "deploy", f["kind"] + ".py"))
    fab, routes = kind.build(f, _fabric_kwargs(f))
    imp = cfg.get("impairments")
    impair = None
    if imp:
        rules = {tuple(_TIERS[t] for t in r["links"]): _process(r)
                 for r in imp.get("rules", [])}
        default = _process(imp["default"]) if "default" in imp else None
        impair = fabric_impairments(routes, rules=rules, default=default)
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
