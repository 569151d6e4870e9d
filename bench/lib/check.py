"""Deciding ``correct``: the window's FCT vectors against the plain
reference.

A sample of the window's points, drawn from the run's seed (``sample``:
one job, and of a grid job one point of each law), is run again
through ``reference.simulate`` at the timed size, and the program's FCT
vector (schedule order) is compared flow by flow, each number taken per
law as ``<number>.<law>`` (the worst over that law's sampled points):

  completion_mismatch  flows that finished in one and not the other
  fct_gap_max          largest |FCT - reference| / reference over flows
                       finished in both
  fct_gap_mean         the mean of that relative gap
  pool_peak            the most flows that would hold a slot at once if
                       none waited for admission, over every point of
                       the window; at most the pool size means no flow
                       waited, which the reference (no pool) assumes

Each number named in ``bench/checks/<cell>.json`` is held to its limit
there, and ``pool_peak`` to the configuration's slot count. A number the
file does not name is printed and not compared.
"""
from __future__ import annotations

import time

import numpy as np

from . import reference


def gaps(fct, ref) -> dict:
    fct = np.asarray(fct, np.float64)
    ref = np.asarray(ref, np.float64)
    done, done_ref = np.isfinite(fct), np.isfinite(ref)
    both = done & done_ref
    rel = np.abs(fct[both] - ref[both]) / ref[both]
    return {"completion_mismatch": int((done != done_ref).sum()),
            "fct_gap_max": float(rel.max()) if rel.size else 0.0,
            "fct_gap_mean": float(rel.mean()) if rel.size else 0.0}


def pool_peak(fl: reference.Flows, fct, dt: float) -> int:
    """Peak slot occupancy implied by the FCTs: a flow holds its slot from
    the tick its start falls due until its last byte has drained past
    its last hop (``max tf`` ticks after completion); an unfinished flow
    holds it to the end."""
    start = np.asarray(fl.start, np.float64)
    tau = np.asarray(fl.tau, np.float64)
    fct = np.asarray(fct, np.float64)[:len(start)]
    due = np.ceil(start / dt - 1e-6)
    done_tick = np.round((fct + start - tau / 2) / dt)
    hold = np.asarray(fl.tf).max(axis=1)
    end = np.where(np.isfinite(fct), done_tick + hold + 1, np.inf)
    ts = np.concatenate([due, end])
    delta = np.concatenate([np.ones_like(due), -np.ones_like(end)])
    order = np.lexsort((delta, ts))
    return int(np.cumsum(delta[order]).max()) if len(ts) else 0


def sample(results, rng):
    """The points to check: one job of the window, drawn from ``rng``,
    and of a job of several points (a grid), one point of each law,
    drawn from ``rng`` after it, in the order of the laws' first
    points."""
    job, fcts = results[int(rng.integers(len(results)))]
    pairs = list(zip(job["points"], fcts))
    if len(pairs) == 1:
        return pairs
    out = []
    for law in dict.fromkeys(p["law"] for p, _ in pairs):
        mine = [pf for pf in pairs if pf[0]["law"] == law]
        out.append(mine[int(rng.integers(len(mine)))])
    return out


def reference_run(cell, desc, point, dtype="float32"):
    cfg = cell.config
    s = cfg["sim"]
    fl = reference.build_flows(desc, point["groups"], s["dt"],
                               s["steps"] * s["dt"])
    links = reference.build_links(desc, cfg.get("impairments"))
    sim = reference.Sim(point["law"], s["dt"], s["steps"], s["hist"],
                        s["update_period"], desc.n_switches,
                        desc.switch_buffer, desc.dt_alpha, dtype, cell.dir)
    fct = reference.simulate(sim, reference.pad(fl, desc.Q), links,
                             dict(cfg["law_config"]))
    return fl, np.asarray(fct)[:fl.tau.shape[0]]


def against(fct_program, fct_ref):
    """``gaps`` over the program's whole schedule-ordered vector: flows
    beyond the reference's (those that start after the horizon) must not
    finish."""
    full = np.full(len(fct_program), np.nan)
    full[:len(fct_ref)] = fct_ref
    return gaps(fct_program, full)


def per_law(rows) -> dict:
    """``{"<number>.<law>": worst}`` over (law, gaps) pairs."""
    out = {}
    for law, g in rows:
        for k, v in g.items():
            key = f"{k}.{law}"
            out[key] = max(out.get(key, v), v)
    return out


def compare(cell, desc, results, rng, err) -> dict:
    cfg = cell.config
    dt = cfg["sim"]["dt"]
    rows = []
    for point, fct in sample(results, rng):
        t0 = time.perf_counter()
        _, ref = reference_run(cell, desc, point)
        g = against(fct, ref)
        print(f"checked {point['law']} scenario {point['scenario']}: "
              f"{int(np.isfinite(fct).sum())} flows finished, reference "
              f"{time.perf_counter() - t0:.1f} s; {g}", file=err)
        rows.append((point["law"], g))
    worst = per_law(rows)
    for k in sorted(set(worst) - set(cell.check)):
        print(f"not compared {k}: {worst[k]}", file=err)
    peak = 0
    for job, fcts in results:
        for point, fct in zip(job["points"], fcts):
            fl = reference.build_flows(desc, point["groups"], dt,
                                       cfg["sim"]["steps"] * dt)
            peak = max(peak, pool_peak(fl, fct, dt))
    out = {k: {"value": worst[k], "limit": lim}
           for k, lim in cell.check.items()}
    out["pool_peak"] = {"value": peak, "limit": cfg["slots"]}
    return out
