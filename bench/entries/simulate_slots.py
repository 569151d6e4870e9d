"""Entry ``simulate_slots``: one scenario per call of the program's
flow-slot engine, on the configuration's backend and slot pool."""
from __future__ import annotations

import numpy as np

from repro.core import default_law_config, schedule_as_flows, simulate_slots

from bench.lib import program


def run(dep, cfg, job, span):
    (pt,) = job["points"]
    with span("schedule"):
        sched = program.schedule(dep, pt["groups"], dep.sim.dt)
        lcfg = default_law_config(schedule_as_flows(sched),
                                  **program.law_kwargs(cfg))
    with span("simulate"):
        st, _ = simulate_slots(dep.topo, sched, pt["law"], cfg["slots"], lcfg,
                               dep.sim, record=False,
                               backend=cfg.get("backend", "reference"),
                               chunk=cfg.get("chunk"), impair=dep.impair)
    with span("fetch"):
        return [np.asarray(st.fct)]
