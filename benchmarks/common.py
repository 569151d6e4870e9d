"""Shared benchmark utilities: FCT bookkeeping, law runners, pretty tables.

``run_law`` accepts either one scenario (a ``Flows``) or a list of
scenarios; a list is padded + stacked (``stack_flows``) and executed through
``core.simulate_batch`` as ONE jitted program — the whole sweep (seeds,
loads, fan-ins) compiles once and runs with a leading batch axis, instead
of one compile + one serial scan per point.
"""
from __future__ import annotations

import os
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core import (Flows, FlowSchedule, LeafSpine, SimConfig,
                        default_law_config, homa_alloc_fn, pad_flows,
                        simulate, simulate_batch, simulate_slots_batch,
                        stack_flow_schedules, stack_flows)
from repro.core.sweep import tree_index as _tree_index

def use_compile_cache() -> str:
    """Put JAX's persistent compilation cache where
    ``$JAX_COMPILATION_CACHE_DIR`` says (JAX reads the variable itself),
    else at the fixed ``<repo>/.jax_cache``: the path is part of the
    cache key, so it never moves. Entry points call this; importing
    ``repro`` sets no cache."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


SHORT = 10e3            # <10 KB   (paper Fig. 6 buckets)
MEDIUM_LO = 100e3
MEDIUM_HI = 1e6


def fct_stats(st, flows, percentile=99.9) -> Dict[str, float]:
    """FCT percentiles by flow-size bucket. ``st`` is a final SimState (or a
    raw fct array), possibly batched (leading axis) — padded flows carry
    ``size = inf`` and are excluded by the finite-size mask, so batched
    results aggregate across scenarios."""
    fct = np.asarray(getattr(st, "fct", st)).ravel()
    size = np.asarray(flows.size).ravel()
    done = np.isfinite(fct) & np.isfinite(size)
    out = {}
    buckets = {
        "short": size < SHORT,
        "medium": (size >= MEDIUM_LO) & (size <= MEDIUM_HI),
        "long": size > MEDIUM_HI,
        "all": np.ones_like(done),
    }
    for name, m in buckets.items():
        sel = done & m
        if sel.sum() == 0:
            out[f"{name}_p"] = float("nan")
            out[f"{name}_mean"] = float("nan")
            continue
        out[f"{name}_p"] = float(np.percentile(fct[sel], percentile))
        out[f"{name}_mean"] = float(fct[sel].mean())
    out["completed"] = int(done.sum())
    out["total"] = int(np.isfinite(size).sum())
    return out


def run_law(topo, flows, law: str, cfg: SimConfig, fabric: Optional[LeafSpine]
            = None, expected_flows: float = 4.0, record: bool = True,
            homa_overcommit: int = 0, backend: str = "reference",
            devices=None):
    """Run one law over one scenario (``Flows``) or a sweep (list of
    ``Flows``). Lists return results with a leading batch axis; ``devices``
    shards the batch axis across the device mesh (DESIGN.md section 11).

    Window/rate laws run through ``simulate_batch`` (one compile for the
    whole sweep). ``law='homa'`` uses the receiver-driven allocator whose
    grant bookkeeping is tied to concrete per-scenario receiver ids, so it
    loops serially — over flows padded to a common size so the results still
    stack into the same batched shape."""
    # NB: Flows is itself a NamedTuple — a bare isinstance(tuple) would
    # misread a single scenario as a sweep of its fields.
    batched = isinstance(flows, (list, tuple)) and not isinstance(flows,
                                                                  Flows)
    scenarios: List = list(flows) if batched else [flows]
    t0 = time.time()

    if law == "homa":
        n = max(int(f.tau.shape[0]) for f in scenarios)
        outs = []
        for fl in scenarios:
            fl = pad_flows(fl, n, topo.num_queues)
            recv = _receiver_ids(fl, fabric)
            alloc_fn = homa_alloc_fn(recv, fabric.host_bw,
                                     max(homa_overcommit, 1), fl.tau,
                                     fl.start)
            lcfg = default_law_config(fl, expected_flows=expected_flows)
            # window non-binding; grants cap the rate
            outs.append(simulate(topo, fl, "reno", lcfg, cfg,
                                 alloc_fn=alloc_fn, record=record))
        st, rec = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *outs)
    else:
        fb = stack_flows(scenarios, topo.num_queues)
        st, rec = simulate_batch(topo, fb, law, cfg=cfg, record=record,
                                 backend=backend,
                                 expected_flows=expected_flows,
                                 devices=devices)
    if not batched:
        st, rec = _tree_index(st, 0), (None if rec is None else
                                       _tree_index(rec, 0))
    return st, rec, time.time() - t0


def run_law_slots(topo, scheds, law: str, cfg: SimConfig, slots: int,
                  expected_flows: float = 4.0, record: bool = False,
                  backend: str = "reference", devices=None):
    """Slot-path twin of ``run_law``: run one ``FlowSchedule`` or a list of
    them through the flow-slot streaming engine (``simulate_slots_batch``),
    one jitted program whose per-tick cost is O(slots * hops) regardless of
    total flow count — this is what lets fig6/fig7 reach the paper's
    256-host scale. Results carry a leading batch axis for lists;
    ``st.fct`` rows are in schedule order (``fct_stats`` against the
    stacked schedule handles that, since its sizes are sorted the same
    way). HOMA's receiver-grant allocator stays on the padded path
    (``run_law``)."""
    batched = (isinstance(scheds, (list, tuple)) and
               not isinstance(scheds, FlowSchedule))
    lst = list(scheds) if batched else [scheds]
    t0 = time.time()
    sb = stack_flow_schedules(lst, topo.num_queues)
    st, rec = simulate_slots_batch(topo, sb, law, slots, cfg=cfg,
                                   record=record, backend=backend,
                                   expected_flows=expected_flows,
                                   devices=devices)
    jax.block_until_ready(st.fct)
    if not batched:
        st, rec = _tree_index(st, 0), (None if rec is None else
                                       _tree_index(rec, 0))
    return st, rec, time.time() - t0


def _receiver_ids(flows, fabric: LeafSpine):
    """Recover receiver host id from the last real hop (host downlink)."""
    path = np.asarray(flows.path)
    R, S, H = fabric.racks, fabric.spines, fabric.hosts_per_rack
    base = 2 * R * S
    recv = np.zeros(path.shape[0], np.int64)
    for i in range(path.shape[0]):
        hops = path[i][path[i] < fabric.num_queues]
        host_q = [q for q in hops if q >= base]
        recv[i] = (host_q[-1] - base) if host_q else 0
    return recv


def table(rows: List[dict], cols: List[str], title: str = "") -> str:
    out = []
    if title:
        out.append(f"\n== {title} ==")
    hdr = " | ".join(f"{c:>14s}" for c in cols)
    out.append(hdr)
    out.append("-" * len(hdr))
    for r in rows:
        out.append(" | ".join(
            f"{r.get(c, ''):>14.6g}" if isinstance(r.get(c), (int, float))
            else f"{str(r.get(c, '')):>14s}" for c in cols))
    return "\n".join(out)


def emit(name: str, value, unit: str = ""):
    print(f"BENCH,{name},{value},{unit}")
    sys.stdout.flush()
