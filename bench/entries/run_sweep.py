"""Entry ``run_sweep``: a grid job (every law over the same scenarios,
law-major) in one call of the program's sweep front end,
``repro.core.run_sweep`` on the flow-slot engine: one vmapped
``simulate_slots_batch`` program per law, one lane per scenario.

Each scenario is routed once and handed over as it is routed: the
program pads the lanes to the job's largest scenario itself. A job
outlasts the profiler's device buffer, so a traced run takes samples
of it (``harness.Profile``)."""
from __future__ import annotations

import numpy as np

from repro.core import SweepSpec, run_sweep

from bench.lib import program

TRACE_SAMPLED = True


def lanes(job) -> list:
    """The job's scenarios (the first law's points); raises unless the
    job is every law over those scenarios, law-major."""
    pts = job["points"]
    laws = list(dict.fromkeys(p["law"] for p in pts))
    first = pts[:len(pts) // len(laws)]
    key = [(p["law"], p.get("load"), p["scenario"]) for p in pts]
    if key != [(law, p.get("load"), p["scenario"]) for law in laws
               for p in first]:
        raise ValueError("a sweep job runs every law over the same "
                         "scenarios, law-major")
    return first


def run(dep, cfg, job, span):
    scen = lanes(job)
    kw = program.law_kwargs(cfg)
    expected = kw.pop("expected_flows")
    with span("schedule"):
        flows = [program.route(dep, p["groups"], dep.sim.dt) for p in scen]
        spec = SweepSpec(
            laws=list(dict.fromkeys(p["law"] for p in job["points"])),
            flows=flows, law_cfg_overrides=(kw,), expected_flows=expected,
            backend=cfg.get("backend", "reference"), slots=cfg["slots"],
            impairments=None if dep.impair is None else [dep.impair])
    with span("simulate"):
        res = run_sweep(spec, dep.topo, dep.sim, record=False)
    with span("fetch"):
        fct = {k: np.asarray(st.fct) for k, st in res.states.items()}
        return [fct[p.law_idx][p.row] for p in res.points]
