"""Benchmark driver — one module per paper table/figure + framework tables.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig4,fig8]
  PYTHONPATH=src python -m benchmarks.run --smoke [--devices auto]

Emits ``BENCH,name,value,unit`` lines (machine-parseable) plus pretty
tables, and finishes with a claims scoreboard. ``--smoke`` times the
batched scenario engine against the serial per-point loop on an 8-seed
sweep plus an RDCN (fig8-style) laws x schedules grid, and writes
``BENCH_sweep.json`` (points/sec for every path, serial-vs-batched
consistency errors) to the repo root — the perf trajectory anchor for
scaling PRs (see benchmarks/README.md for the field reference).
``--devices N|auto`` additionally runs the sweep with the batch axis
sharded across devices (``simulate_batch(devices=...)``, DESIGN.md
section 11) and records the sharded points/sec; ``auto`` on a
single-device host falls back to the vmap path and reports
``devices: 1``, while an explicit N above the local device count
raises. The slot leg
also runs the whole-tick megakernel backend on the identical workload
(``fct_mega_*`` fields: wall time, speedup over the reference slot
stream, the anchor bit-exactness gate and paper-scale consistency —
DESIGN.md section 13). ``--profile`` prints the per-op tick cost
breakdown per backend instead (tools/profile_tick.py). The
dry-run/roofline sweep (benchmarks.dryrun_table) is orchestrated separately
because each cell runs in a subprocess; its persisted results are
summarized here when present.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _dryrun_summary():
    d = os.path.join(os.path.dirname(__file__), "..", "experiments",
                     "dryrun")
    if not os.path.isdir(d):
        print("dryrun: no persisted cells (run benchmarks.dryrun_table)")
        return None
    from repro.launch.roofline import roofline_terms
    cells = []
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            with open(os.path.join(d, fn)) as f:
                cells.append(json.load(f))
    ok = [c for c in cells if "hlo_analysis" in c]
    multi = [c for c in ok if c["mesh"] == "multi"]
    print(f"BENCH,dryrun.cells_compiled,{len(ok)},")
    print(f"BENCH,dryrun.multi_pod_cells,{len(multi)},")
    bots = {}
    for c in ok:
        if c["mesh"] != "single":
            continue
        b = roofline_terms(c)["bottleneck"]
        bots[b] = bots.get(b, 0) + 1
    print(f"BENCH,dryrun.bottleneck_histogram,{bots},")
    return len(ok)


def smoke_sweep(points: int = 8, steps: int = 2000, devices=None) -> dict:
    """Serial-vs-batched(-vs-sharded) scenario engine microbenchmark.

    ``points`` seed scenarios with *distinct* flow counts (as in the real
    load/seed sweeps), so the serial loop recompiles per point while
    ``simulate_batch`` pads + stacks and compiles once. With ``devices`` the
    same batch also runs with the batch axis sharded across the device mesh
    (bit-exactness vs the vmap path is asserted). Returns points/sec for
    every path.
    """
    import numpy as np

    from repro.core import (GBPS, SimConfig, default_law_config,
                            make_flows_single, resolve_devices, simulate,
                            simulate_batch, single_bottleneck, stack_flows)

    B = 100 * GBPS
    topo = single_bottleneck(bandwidth=B, buffer=16e6)
    scenarios = []
    for s in range(points):
        rng = np.random.default_rng(s)
        nf = 8 + s              # distinct flow counts => serial recompiles
        scenarios.append(make_flows_single(
            nf, tau=20e-6, nic=B, sizes=rng.uniform(2e5, 8e5, nf),
            starts=rng.uniform(0.0, 2e-4, nf), sim_dt=1e-6))
    cfg = SimConfig(dt=1e-6, steps=steps, hist=256)

    t0 = time.time()
    serial_fcts = []
    for fl in scenarios:
        st, _ = simulate(topo, fl, "powertcp",
                         default_law_config(fl, expected_flows=8.0), cfg,
                         record=False)
        serial_fcts.append(np.asarray(st.fct))
    serial_s = time.time() - t0

    fb = stack_flows(scenarios, topo.num_queues)
    t0 = time.time()
    stb, _ = simulate_batch(topo, fb, "powertcp", cfg=cfg, record=False,
                            expected_flows=8.0)
    stb.fct.block_until_ready()
    batched_s = time.time() - t0

    # consistency: the batched sweep must reproduce the serial points,
    # including which flows finished (mismatched NaN patterns gate as inf
    # rather than being skipped by a nan-ignoring max)
    def fct_err(batched, ref):
        batched = np.asarray(batched)
        if (np.isnan(batched) != np.isnan(ref)).any():
            return float("inf")
        d = np.abs(batched - ref)
        return float(np.nanmax(d)) if np.isfinite(ref).any() else 0.0

    max_err = max(fct_err(stb.fct[i][:len(f)], f)
                  for i, f in enumerate(serial_fcts))
    data = {
        "points": points,
        "steps_per_point": steps,
        "serial_s": round(serial_s, 3),
        "batched_s": round(batched_s, 3),
        "serial_points_per_s": round(points / serial_s, 3),
        "batched_points_per_s": round(points / batched_s, 3),
        "speedup": round(serial_s / batched_s, 2),
        "fct_max_abs_err_s": max_err,
    }

    ndev = resolve_devices(devices)
    data["devices"] = ndev
    if ndev > 1:
        t0 = time.time()
        sts, _ = simulate_batch(topo, fb, "powertcp", cfg=cfg, record=False,
                                expected_flows=8.0, devices=ndev)
        sts.fct.block_until_ready()
        sharded_s = time.time() - t0
        exact = bool(np.array_equal(np.asarray(sts.fct),
                                    np.asarray(stb.fct), equal_nan=True))
        data.update({
            "sharded_s": round(sharded_s, 3),
            "sharded_points_per_s": round(points / sharded_s, 3),
            "sharded_speedup_vs_serial": round(serial_s / sharded_s, 2),
            "sharded_bitmatches_vmap": exact,
        })
    return data


def smoke_slots(duration: float = 0.03, load: float = 0.6,
                seeds=(1, 2)) -> dict:
    """Flow-slot streaming engine vs the padded engine at EQUAL scenario
    scale: the fig6 paper-scale workload (256-host fabric, 60% load) runs
    through both engines — same seeds, same steps — and the slot pool is
    sized to the *realized* peak concurrency (admissions never wait), so
    any FCT difference is pure cross-program float noise. Also runs the
    bit-exactness gate (``fct_slot_exact_bitmatch``): on a tiny
    single-bottleneck scenario with S >= total flows the slot engine must
    reproduce the padded trajectories bit-for-bit (DESIGN.md section 12).
    """
    import jax
    import numpy as np

    from repro.core import (GBPS, SimConfig, default_law_config,
                            make_flows_single, make_schedule,
                            peak_concurrency, poisson_websearch,
                            schedule_as_flows, simulate, simulate_batch,
                            simulate_slots, simulate_slots_batch,
                            single_bottleneck, stack_flow_schedules,
                            stack_flows)
    from .fig6_fct import paper_fabric

    fab = paper_fabric()
    dt = 1e-6
    topo = fab.topology()
    scenarios = [poisson_websearch(fab, load, duration, dt, seed=s)
                 for s in seeds]
    scheds = [make_schedule(f) for f in scenarios]
    n_total = sum(int(f.tau.shape[0]) for f in scenarios)
    steps = int((duration + 0.01) / dt)
    cfg = SimConfig(dt=dt, steps=steps, hist=512, update_period=2e-6)

    fb = stack_flows(scenarios, topo.num_queues)
    t0 = time.time()
    st_p, _ = simulate_batch(topo, fb, "powertcp", cfg=cfg, record=False,
                             expected_flows=8.0)
    jax.block_until_ready(st_p.fct)
    padded_s = time.time() - t0

    # size the pool from realized concurrency + the post-completion drain
    # hold, so the slot run replays the identical admission pattern
    hold = max(int(np.asarray(s.tf_steps).max()) for s in scheds) * dt
    peak = 0
    for i, s in enumerate(scheds):
        starts = np.asarray(s.start, np.float64)
        fct = np.asarray(st_p.fct[i][:starts.shape[0]], np.float64)
        ends = starts + np.where(np.isfinite(fct), fct, np.inf) + hold
        peak = max(peak, peak_concurrency(starts, ends))
    slots = min(-(-max(peak, 1) // 64) * 64, n_total)

    sb = stack_flow_schedules(scheds, topo.num_queues)
    t0 = time.time()
    st_s, _ = simulate_slots_batch(topo, sb, "powertcp", slots, cfg=cfg,
                                   record=False, expected_flows=8.0)
    jax.block_until_ready(st_s.fct)
    slot_s = time.time() - t0

    # megakernel backend on the identical workload (DESIGN.md section 13):
    # the sequential batch driver keeps one compile for the sweep while
    # letting the idle-tick gate branch at runtime (under vmap a cond
    # runs both branches)
    t0 = time.time()
    st_m, _ = simulate_slots_batch(topo, sb, "powertcp", slots, cfg=cfg,
                                   record=False, expected_flows=8.0,
                                   backend="megakernel", sequential=True)
    jax.block_until_ready(st_m.fct)
    mega_s = time.time() - t0

    # consistency at equal scale: identical completion set, and short-flow
    # tail FCT within cross-program float noise (multihop trajectories are
    # ~1 ulp/step apart between the two compiled engines; DESIGN.md s12)
    fct_p, fct_s, sizes = [], [], []
    for i, s in enumerate(scheds):
        n = int(s.start.shape[0])
        # padded fct is in original flow order; reindex to schedule order
        fct_p.append(np.asarray(st_p.fct[i][:n])[np.asarray(s.order)])
        fct_s.append(np.asarray(st_s.fct[i][:n]))
        sizes.append(np.asarray(s.size))
    fct_p, fct_s = np.concatenate(fct_p), np.concatenate(fct_s)
    sizes = np.concatenate(sizes)
    completed_match = bool((np.isfinite(fct_p) == np.isfinite(fct_s)).all())
    short = np.isfinite(fct_p) & np.isfinite(fct_s) & (sizes < 10e3)
    pp = float(np.percentile(fct_p[short], 99.9))
    ps = float(np.percentile(fct_s[short], 99.9))
    p999_rel_err = abs(ps - pp) / max(pp, 1e-12)

    # megakernel consistency at equal scale: identical completion set and
    # short-flow tail within cross-program float noise (same boundary as
    # the slot-vs-padded comparison above)
    fct_m = np.concatenate(
        [np.asarray(st_m.fct[i][:int(s.start.shape[0])])
         for i, s in enumerate(scheds)])
    mega_completed = bool((np.isfinite(fct_s) == np.isfinite(fct_m)).all())
    pm = float(np.percentile(fct_m[short], 99.9))
    mega_p999_rel_err = abs(pm - ps) / max(ps, 1e-12)

    # bit-exactness gate: tiny single-bottleneck scenario, S >= total flows
    B = 100 * GBPS
    btopo = single_bottleneck(bandwidth=B, buffer=16e6)
    rng = np.random.default_rng(0)
    fl = make_flows_single(12, tau=20e-6, nic=B,
                           sizes=rng.uniform(1e5, 5e5, 12),
                           starts=rng.uniform(0.0, 1e-3, 12), sim_dt=1e-6)
    bsched = make_schedule(fl)
    bcfg = SimConfig(dt=1e-6, steps=3000, hist=256)
    lcfg = default_law_config(schedule_as_flows(bsched), expected_flows=8.0)
    ref_st, ref_rec = simulate(btopo, schedule_as_flows(bsched), "powertcp",
                               lcfg, bcfg)
    slot_st, slot_rec = simulate_slots(btopo, bsched, "powertcp", 16, lcfg,
                                       bcfg)
    # queue trajectory + FCT bit-identity is the asserted contract; final
    # windows may differ by 1 ulp at knife-edge update ticks (XLA
    # cross-program instruction selection, DESIGN.md section 12)
    exact = bool(
        np.array_equal(np.asarray(slot_rec.q), np.asarray(ref_rec.q))
        and np.array_equal(np.asarray(slot_st.fct), np.asarray(ref_st.fct),
                           equal_nan=True)
        and np.allclose(np.asarray(slot_st.w[:12]), np.asarray(ref_st.w),
                        rtol=5e-7))
    # megakernel anchor (DESIGN.md section 13): vs the reference slot
    # engine the contract is stronger — queue trace, FCTs, windows AND
    # per-slot rates bit-for-bit
    mega_st, mega_rec = simulate_slots(btopo, bsched, "powertcp", 16, lcfg,
                                       bcfg, backend="megakernel")
    mega_exact = bool(
        np.array_equal(np.asarray(mega_rec.q), np.asarray(slot_rec.q))
        and np.array_equal(np.asarray(mega_st.fct),
                           np.asarray(slot_st.fct), equal_nan=True)
        and np.array_equal(np.asarray(mega_st.w), np.asarray(slot_st.w))
        and np.array_equal(np.asarray(mega_rec.lam_f),
                           np.asarray(slot_rec.lam_f)))

    points = len(seeds)
    return {
        "fct_slot_hosts": fab.n_hosts,
        "fct_slot_load": load,
        "fct_slot_points": points,
        "fct_slot_steps_per_point": steps,
        "fct_slot_flows": n_total,
        "fct_slot_slots": slots,
        "fct_slot_padded_s": round(padded_s, 3),
        "fct_slot_stream_s": round(slot_s, 3),
        "fct_slot_padded_points_per_s": round(points / padded_s, 3),
        "fct_slot_points_per_s": round(points / slot_s, 3),
        "fct_slot_speedup": round(padded_s / slot_s, 2),
        "fct_slot_completed_match": completed_match,
        "fct_slot_p999_rel_err": round(p999_rel_err, 6),
        "fct_slot_exact_bitmatch": exact,
        "fct_mega_s": round(mega_s, 3),
        "fct_mega_points_per_s": round(points / mega_s, 3),
        "fct_mega_speedup": round(slot_s / mega_s, 2),
        "fct_mega_mode": "sequential",
        "fct_mega_completed_match": mega_completed,
        "fct_mega_p999_rel_err": round(mega_p999_rel_err, 6),
        "fct_mega_exact_bitmatch": mega_exact,
    }


def smoke_rdcn() -> dict:
    """Batched fig8 (RDCN) vs the serial per-case loop on a reduced grid.

    Runs the *exact* fig8 grid (``fig8_rdcn.rdcn_specs``: 3 window laws +
    2 reTCP prebuffer variants, x 2 schedule slots, 1 week) through
    ``run_sweep`` and the same 10 cases through serial ``simulate``, and
    checks that circuit utilization / p99 queuing latency reproduce the
    serially-computed values.
    """
    from repro.core import default_law_config, expand, run_sweep, simulate
    from .fig8_rdcn import point_metrics, rdcn_setup, rdcn_specs

    topo, flows, cfg, scheds = rdcn_setup(weeks=1)
    specs = rdcn_specs(flows, scheds)

    t0 = time.time()
    batched = []
    for spec in specs:
        res = run_sweep(spec, topo, cfg)
        for p in res.points:
            batched.append(point_metrics(res.record(p.index),
                                         scheds[p.sched_idx]))
    batched_s = time.time() - t0

    t0 = time.time()
    serial = []
    for spec in specs:
        for p in expand(spec):
            ov = dict(spec.law_cfg_overrides[p.override_idx])
            sch = scheds[p.sched_idx]
            lcfg = default_law_config(flows,
                                      expected_flows=spec.expected_flows,
                                      sched=sch.params(), **ov)
            _, rec = simulate(topo, flows, p.law, lcfg, cfg,
                              bw_fn=sch.bw_fn())
            serial.append(point_metrics(rec, sch))
    serial_s = time.time() - t0

    n = len(serial)
    util_err = max(abs(b[0] - s[0]) for b, s in zip(batched, serial))
    p99_err = max(abs(b[1] - s[1]) for b, s in zip(batched, serial))
    return {
        "rdcn_points": n,
        "rdcn_serial_s": round(serial_s, 3),
        "rdcn_batched_s": round(batched_s, 3),
        "rdcn_serial_points_per_s": round(n / serial_s, 3),
        "rdcn_batched_points_per_s": round(n / batched_s, 3),
        "rdcn_speedup": round(serial_s / batched_s, 2),
        "rdcn_util_max_abs_err": round(util_err, 6),
        "rdcn_p99_max_abs_err_s": round(p99_err, 9),
    }


def run_smoke(devices=None, out_name: str = "BENCH_sweep.json") -> dict:
    """--smoke entry: seed sweep + slot engine + RDCN grid + fabric +
    fault legs, one BENCH_sweep.json.

    ``devices`` adds the sharded leg to the seed sweep; the RDCN grid (10
    points, compile-dominated) always runs the single-device batched path —
    its job is the serial-vs-batched consistency gate, and carving a tiny
    grid across forced host devices only measures shard_map overhead. The
    slot leg (``fct_slot_*``) runs the fig6 paper-scale scenario (256
    hosts, 60% load) through the padded and slot engines at equal scale.

    Crash-safe by construction (DESIGN.md section 18): each section runs
    isolated — one section's exception lands in the ``failures`` record
    (section name + error) while every other section's fields still make
    it into the JSON — and the file itself is written atomically (temp +
    ``os.replace``), so a died run never leaves a torn BENCH_sweep.json
    for CI to misparse; it either sees the previous file or a complete
    new one. CI gates on ``failures == []``.
    """
    from .fabric_fct import smoke_fabric, smoke_fabric16
    from .feedback_fct import smoke_feedback
    from .impair_fct import smoke_impair
    from .fault_fct import smoke_fault
    sections = [
        ("sweep", lambda: smoke_sweep(devices=devices)),
        ("slots", smoke_slots),
        ("rdcn", smoke_rdcn),
        ("fabric", smoke_fabric),
        ("fabric16", lambda: smoke_fabric16(devices=devices)),
        ("feedback", smoke_feedback),
        ("impair", smoke_impair),
        ("fault", smoke_fault),
    ]
    data: dict = {}
    failures = []
    for name, fn in sections:
        try:
            data.update(fn())
        except Exception as e:          # pragma: no cover - failure path
            failures.append({"section": name,
                             "error": f"{type(e).__name__}: {e}"})
            print(f"SMOKE SECTION FAILED: {name}: "
                  f"{type(e).__name__}: {e}")
    data["failures"] = failures
    out = os.path.join(os.path.dirname(__file__), "..", out_name)
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, out)
    for k, v in data.items():
        print(f"BENCH,sweep.{k},{v},")
    print(f"wrote {os.path.abspath(out)}")
    return data


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="serial-vs-batched sweep microbenchmark only; "
                         "writes BENCH_sweep.json")
    ap.add_argument("--devices", default=None,
                    help="shard sweep batch axes across N devices "
                         "('auto' = all local devices; default: off)")
    ap.add_argument("--profile", action="store_true",
                    help="per-op tick cost breakdown per slot backend "
                         "(tools/profile_tick.py, reduced preset)")
    a = ap.parse_args()
    devices = (None if a.devices in (None, "", "0", "1")
               else ("auto" if a.devices == "auto" else int(a.devices)))

    if a.profile:
        import subprocess
        root = os.path.join(os.path.dirname(__file__), "..")
        return subprocess.call(
            [sys.executable, os.path.join(root, "tools",
                                          "profile_tick.py"),
             "--hosts", "64", "--steps", "4096", "--slots", "64"],
            env={**os.environ,
                 "PYTHONPATH": os.path.join(root, "src") + os.pathsep +
                 os.environ.get("PYTHONPATH", "")})

    from .common import use_compile_cache
    use_compile_cache()
    if a.smoke:
        data = run_smoke(devices=devices)
        return 0 if smoke_ok(data) else 1

    from . import (fabric_fct, feedback_fct, fig3_phase, fig4_incast,
                   fig5_fairness, fig6_fct, fig7_load_sweep, fig8_rdcn,
                   impair_fct, tab_commsched)
    def sharded(fn):
        return lambda quick: fn(quick=quick, devices=devices)

    suite = {
        "fig3": fig3_phase.run,
        "fig4": sharded(fig4_incast.run),
        "fig5": sharded(fig5_fairness.run),
        "fig6": sharded(fig6_fct.run),
        "fig7": sharded(fig7_load_sweep.run),
        "fig8": sharded(fig8_rdcn.run),
        "fabric": sharded(fabric_fct.run),
        "feedback": feedback_fct.run,
        "impair": sharded(impair_fct.run),
        "commsched": tab_commsched.run,
    }
    only = set(a.only.split(",")) if a.only else set(suite)
    unknown = only - set(suite)
    if unknown:
        ap.error(f"unknown --only targets {sorted(unknown)}; "
                 f"have {sorted(suite)}")
    scoreboard = {}
    for name, fn in suite.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            scoreboard[name] = bool(fn(quick=a.quick))
        except Exception as e:          # pragma: no cover
            scoreboard[name] = False
            print(f"ERROR in {name}: {type(e).__name__}: {e}")
        print(f"BENCH,{name}.wall_s,{time.time()-t0:.1f},s")

    _dryrun_summary()
    print("\n== CLAIMS SCOREBOARD ==")
    for k, v in scoreboard.items():
        print(f"  {k:12s} {'PASS' if v else 'FAIL'}")
    print(f"BENCH,claims.passed,{sum(scoreboard.values())},"
          f"/{len(scoreboard)}")
    return 0 if all(scoreboard.values()) else 1


def smoke_ok(data: dict) -> bool:
    """The --smoke pass/fail gate over BENCH_sweep.json fields.

    A failed section leaves its fields missing — the KeyError guard
    turns that into a clean FAIL (plus the section already sits in
    ``failures``, which is gated empty). rdcn_speedup is reported but
    not gated: at 10 compile-dominated points its margin (~1.1x) is
    within runner noise, unlike the ~7x seed sweep. Consistency errors
    ARE gated. (CI additionally asserts devices == 8 and
    sharded_bitmatches_vmap on the JSON, so a silently-ignored device
    forcing cannot pass unnoticed there.)
    """
    try:
        ok = (data["speedup"] > 1.0 and data["fct_max_abs_err_s"] < 1e-6
              and not data["failures"]
              and data["rdcn_util_max_abs_err"] < 5e-3
              and data["rdcn_p99_max_abs_err_s"] < 1e-6
              and data.get("sharded_bitmatches_vmap", True)
              # slot engine: exactness is a hard gate; the >= 2x speedup
              # target is asserted by CI on the JSON (runner-noise margin)
              and data["fct_slot_exact_bitmatch"]
              and data["fct_slot_completed_match"]
              and data["fct_slot_p999_rel_err"] < 1e-3
              and data["fct_slot_speedup"] > 1.0
              # megakernel backend: anchor bit-exactness + paper-scale
              # consistency are hard gates; the speedup floor is CI's
              and data["fct_mega_exact_bitmatch"]
              and data["fct_mega_completed_match"]
              and data["fct_mega_p999_rel_err"] < 1e-3
              and data["fct_mega_speedup"] > 1.0
              # fabric legs (DESIGN.md section 14): fat-tree (5-hop) and
              # incast-burst scenarios bit-for-bit across all three
              # engines, compiled leaf-spine == legacy paths, ECMP
              # deterministic
              and data["fct_fabric_hops"] >= 5
              and data["fct_fabric_ref_slot_bitmatch"]
              and data["fct_fabric_mega_bitmatch"]
              and data["fct_fabric_incast_ref_slot_bitmatch"]
              and data["fct_fabric_incast_mega_bitmatch"]
              and data["fct_fabric_incast_completed_all"]
              and data["fct_fabric_leafspine_paths_match"]
              and data["fct_fabric_ecmp_deterministic"]
              # sharded-scenario leg (DESIGN.md section 15): the k=16
              # fat-tree must stream >=100k flows on the degraded-spine
              # impaired fabric, the 256-host anchor must bit-match the
              # reference engine for every registry law (clean AND the
              # impaired subset) on the full mesh, the mesh run must
              # bit-match the 1-device run at full scale, and the
              # halo-diet tick must move fewer bytes than the pre-diet
              # gather layout. The speedup floor only applies when the
              # timed mesh is actually parallel (>= 2 physical cores
              # backing >= 2 shards) — on a 1-core host the two timed
              # runs are the same program serialized; CI's own leg
              # additionally gates >= 2.0 on its 8-device mesh.
              and data["fct_fabric16_flows"] >= 100_000
              and data["fct_fabric16_impaired"]
              and data["fct_fabric16_exact_bitmatch"]
              and data["fct_fabric16_impaired_bitmatch"]
              and data["fct_fabric16_devices_bitmatch"]
              # ... the diet comparison only means something on a mesh
              # that actually exchanges (a 1-wide mesh runs zero
              # collectives; its analytic census is vacuous)
              and (data["fct_fabric16_devices"] < 2
                   or data["fct_fabric16_comm_bytes_per_tick"]
                   < data["fct_fabric16_comm_baseline_bytes_per_tick"])
              and (data["fct_fabric16_devices"] < 2
                   or os.cpu_count() < 2
                   or data["fct_fabric16_shard_speedup"] > 1.0)
              # feedback-channel laws (DESIGN.md section 16): every new
              # family bit-for-bit across all three engines on the
              # web-search AND incast anchors, with finite mean FCTs
              and data["fct_feedback_bitmatch_all"]
              and data["fct_feedback_bitmatch_fncc"]
              and data["fct_feedback_bitmatch_pulser"]
              and data["fct_feedback_bitmatch_backpressure"]
              and data["fct_feedback_bitmatch_pcc"]
              and all(data[f"fct_feedback_ws_mean_us_{l}"] is not None
                      for l in ("fncc", "pulser", "backpressure", "pcc"))
              # link-impairment layer (DESIGN.md section 17): anchor laws
              # bit-for-bit across all three engines on the mixed
              # (oscillate + loss + jitter) regime, the zero-impairment
              # preset reproduces the unimpaired anchor bitwise, and the
              # KIND_SCHEDULE process reproduces rdcn.circuit_bw_at
              and data["fct_impair_bitmatch_all"]
              and data["fct_impair_zero_baseline"]
              and data["fct_impair_rdcn_equiv"]
              and all(data[f"fct_impair_ws_mean_us_{l}"] is not None
                      for l in ("powertcp", "hpcc", "timely"))
              # fault-tolerance leg (DESIGN.md section 18): the crash-
              # injected paper-scale run resumed from its last durable
              # snapshot must reproduce the uninterrupted run bitwise, a
              # poisoned law under guard must raise DivergenceError (not
              # return NaN output), and one poisoned sweep point must be
              # isolated while every clean point bit-matches a clean run
              and data["fct_resume_crashed"]
              and data["fct_resume_bitmatch"]
              and data["fct_resume_guard_divergence"]
              and data["fct_resume_guard_unguarded_nan"]
              and data["fct_resume_sweep_isolated"]
              and data["fct_resume_sweep_failed_points"] == 1)
    except KeyError as e:               # a failed section's fields
        print(f"SMOKE GATE: missing field {e} (section failed)")
        return False
    return bool(ok)


if __name__ == "__main__":
    sys.exit(main())
