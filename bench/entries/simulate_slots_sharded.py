"""Entry ``simulate_slots_sharded``: one scenario per call, its slot pool
and queue arrivals sharded over the cell's chips, the schedule streamed
in ``chunk``-entry windows."""
from __future__ import annotations

import numpy as np

from repro.core import (default_law_config, schedule_as_flows,
                        simulate_slots_sharded)

from bench.lib import program


def run(dep, cfg, job, span):
    (pt,) = job["points"]
    with span("schedule"):
        sched = program.schedule(dep, pt["groups"], dep.sim.dt)
        lcfg = default_law_config(schedule_as_flows(sched),
                                  **program.law_kwargs(cfg))
    with span("simulate"):
        st, _ = simulate_slots_sharded(
            dep.topo, sched, pt["law"], cfg["slots"], lcfg, dep.sim,
            record=False, devices=cfg["devices"], chunk=cfg.get("chunk"),
            impair=dep.impair)
    with span("fetch"):
        return [np.asarray(st.fct)]
