"""Fabric-graph legs (DESIGN.md section 14): fat-tree FCT sweeps and
incast bursts through the routing compiler.

``run`` is the fig6-style leg on a k-ary fat-tree (k=4 quick / k=8 full,
5-hop inter-pod ECMP paths): the web-search Poisson workload compiled by
``core.fabric`` streams through the flow-slot engine for every law, plus
a Pulser-style repeated incast-burst benchmark on the same fabric. The
claims asserted are the paper's relative orderings (PowerTCP <= HPCC <<
TIMELY/DCQCN for short flows) — now on a fabric the old hand-built
leaf-spine could not express.

``smoke_fabric`` is the CI leg (run.py --smoke): the k=4 anchor scenario
runs on all three engines — padded reference, flow-slot stream (S >= N)
and megakernel — and asserts the PR-3/PR-4 exactness discipline on
>= 4-hop paths: queue trace, FCT vector and windows bit-for-bit across
engines, for the web-search AND the incast-burst workloads, plus the
migration anchor (compiled leaf-spine paths == the legacy builder's) and
cross-process-deterministic ECMP. Results land in BENCH_sweep.json as
``fct_fabric_*`` fields (benchmarks/README.md has the reference).
"""
from __future__ import annotations

import time

import numpy as np

import jax

from repro.core import (CircuitSchedule, LinkProcess, SimConfig, US,
                        comm_census, default_law_config, ecmp_hash,
                        fabric_impairments, fat_tree, incast_burst,
                        make_schedule, netem, poisson_websearch,
                        schedule_as_flows, shard_geometry, simulate,
                        simulate_slots, simulate_slots_sharded,
                        suggest_slots)
from repro.core import LAWS as LAW_REGISTRY
from repro.core.fabric import (AGG, CORE, HOST, TOR, leaf_spine_fabric,
                               compile_routes)
from repro.core.fluid import resolve_devices
from repro.core.network import LeafSpine
from .common import emit, fct_stats, run_law_slots, table

LAWS = ["powertcp", "theta_powertcp", "hpcc", "timely", "dcqcn"]
DT = 1e-6


def anchor_scenario(k: int = 4, load: float = 0.25, duration: float = 0.004,
                    seed: int = 3):
    """The k=4 fat-tree anchor: small enough to run the padded engine,
    deep enough to exercise 5-hop inter-pod ECMP paths."""
    ft = fat_tree(k)
    flows = poisson_websearch(ft, load, duration, DT, seed=seed)
    sched = make_schedule(flows)
    steps = int((duration + 0.004) / DT)
    cfg = SimConfig(dt=DT, steps=steps, hist=512, update_period=2e-6)
    return ft, sched, cfg


def _bitmatch_three_engines(topo, sched, cfg, law="powertcp",
                            expected_flows=8.0):
    """Run padded / slot (S>=N) / megakernel; return (wall times, flags)."""
    fl = schedule_as_flows(sched)
    n = int(sched.start.shape[0])
    lcfg = default_law_config(fl, expected_flows=expected_flows)

    t0 = time.time()
    st_p, rec_p = simulate(topo, fl, law, lcfg, cfg)
    padded_s = time.time() - t0
    t0 = time.time()
    st_s, rec_s = simulate_slots(topo, sched, law, n, lcfg, cfg)
    slot_s = time.time() - t0
    t0 = time.time()
    st_m, rec_m = simulate_slots(topo, sched, law, n, lcfg, cfg,
                                 backend="megakernel")
    mega_s = time.time() - t0

    ref_slot = bool(
        np.array_equal(np.asarray(rec_s.q), np.asarray(rec_p.q))
        and np.array_equal(np.asarray(st_s.fct), np.asarray(st_p.fct),
                           equal_nan=True)
        and np.array_equal(np.asarray(st_s.w[:n]), np.asarray(st_p.w)))
    mega = bool(
        np.array_equal(np.asarray(rec_m.q), np.asarray(rec_s.q))
        and np.array_equal(np.asarray(st_m.fct), np.asarray(st_s.fct),
                           equal_nan=True)
        and np.array_equal(np.asarray(st_m.w), np.asarray(st_s.w))
        and np.array_equal(np.asarray(rec_m.lam_f),
                           np.asarray(rec_s.lam_f)))
    completed = int(np.isfinite(np.asarray(st_s.fct)).sum())
    return (padded_s, slot_s, mega_s), (ref_slot, mega), completed, st_s


def _leafspine_migration_anchor() -> bool:
    """Compiled leaf-spine == the legacy hand-rolled path arithmetic.

    The pre-refactor ``LeafSpine.make_flows`` formulas are replicated
    here verbatim (spine pick substituted with the compiled ECMP choice
    — the one sanctioned behavior change) and must match the compiler's
    output bit-for-bit on paths, forward delays, RTT steps and taus.
    """
    for (R, H, S) in ((4, 16, 1), (8, 32, 2)):
        ls = LeafSpine(racks=R, hosts_per_rack=H, spines=S)
        routes = ls.routes()
        rng = np.random.default_rng(7)
        n = 256
        src = rng.integers(0, ls.n_hosts, n)
        dst = rng.integers(0, ls.n_hosts, n)
        dst = np.where(dst == src, (dst + 1) % ls.n_hosts, dst)
        fl = ls.make_flows(src, dst, rng.uniform(1e4, 1e6, n),
                           rng.uniform(0, 1e-3, n), DT)
        _, _, _, spine = routes.select(src, dst)
        r1, r2, h2 = src // H, dst // H, dst % H
        PAD = ls.num_queues
        same = r1 == r2
        up = r1 * S + spine
        down = R * S + spine * R + r2
        host = 2 * R * S + r2 * H + h2
        opath = np.stack([np.where(same, host, up),
                          np.where(same, PAD, down),
                          np.where(same, PAD, host)], 1).astype(np.int32)
        d1 = np.full(n, ls.d_host)
        d2 = np.where(same, 0.0, ls.d_host + ls.d_fabric)
        d3 = np.where(same, 0.0, ls.d_host + 2 * ls.d_fabric)
        otf = np.round(np.stack([d1, d2, d3], 1) / DT).astype(np.int32)
        ortt = np.where(same, 4 * ls.d_host,
                        2 * (2 * ls.d_host + 2 * ls.d_fabric))
        ok = (np.array_equal(np.asarray(fl.path), opath)
              and np.array_equal(np.asarray(fl.tf_steps), otf)
              and np.array_equal(
                  np.asarray(fl.rtt_steps),
                  np.maximum(np.round(ortt / DT), 1).astype(np.int32))
              and np.array_equal(np.asarray(fl.tau),
                                 ortt.astype(np.float32)))
        if not ok:
            return False
    return True


def _ecmp_determinism() -> bool:
    """Same inputs -> same hash, different seed -> different picks, and
    pure integer arithmetic (no RNG state involved)."""
    src = np.arange(64) % 16
    dst = (np.arange(64) * 7) % 16
    fid = np.arange(64)
    a = ecmp_hash(src, dst, fid, 0)
    b = ecmp_hash(src, dst, fid, 0)
    c = ecmp_hash(src, dst, fid, 1)
    return bool((a == b).all() and (a != c).any())


def smoke_fabric() -> dict:
    """CI fabric leg: fct_fabric_* fields for BENCH_sweep.json."""
    ft, sched, cfg = anchor_scenario()
    topo = ft.topology()
    hops = int(np.max(np.sum(np.asarray(sched.path) < ft.num_queues,
                             axis=1)))
    walls, (ref_slot, mega), completed, _ = _bitmatch_three_engines(
        topo, sched, cfg)

    # incast bursts on the same fabric (Pulser-style microbursts)
    fl_i, bqs = incast_burst(ft, fan_in=8, req_bytes=2e5, n_bursts=3,
                             period=2e-3, sim_dt=DT, seed=1)
    si = make_schedule(fl_i)
    cfg_i = SimConfig(dt=DT, steps=9000, hist=512, update_period=2e-6)
    _, (inc_ref_slot, inc_mega), inc_done, st_i = _bitmatch_three_engines(
        topo, si, cfg_i)
    inc_all = bool(np.isfinite(np.asarray(st_i.fct)).all())

    return {
        "fct_fabric_hosts": ft.n_hosts,
        "fct_fabric_queues": ft.num_queues,
        "fct_fabric_hops": hops,
        "fct_fabric_flows": int(sched.start.shape[0]),
        "fct_fabric_padded_s": round(walls[0], 3),
        "fct_fabric_slot_s": round(walls[1], 3),
        "fct_fabric_mega_s": round(walls[2], 3),
        "fct_fabric_completed": completed,
        "fct_fabric_ref_slot_bitmatch": ref_slot,
        "fct_fabric_mega_bitmatch": mega,
        "fct_fabric_incast_flows": int(si.start.shape[0]),
        "fct_fabric_incast_completed_all": inc_all,
        "fct_fabric_incast_ref_slot_bitmatch": inc_ref_slot,
        "fct_fabric_incast_mega_bitmatch": inc_mega,
        "fct_fabric_leafspine_paths_match": _leafspine_migration_anchor(),
        "fct_fabric_ecmp_deterministic": _ecmp_determinism(),
    }


def fabric16_scenario(load: float = 0.6, duration: float = 0.085,
                      fan_in: int = 16, n_bursts: int = 64, seed: int = 5):
    """The headline sharded-scenario workload: one k=16 fat-tree (1024
    hosts, 5120 queues) under a web-search + rotating-incast mix, >=100k
    flows in one time-sorted schedule. Far too many ticks and flows for
    a single whole-trace compile — the chunk-streamed sharded engine is
    the only way through it."""
    ft = fat_tree(16)
    fl_w = poisson_websearch(ft, load, duration, DT, seed=seed)
    fl_i, _ = incast_burst(ft, fan_in=fan_in, req_bytes=1.5e5,
                           n_bursts=n_bursts, period=duration / n_bursts,
                           sim_dt=DT, seed=seed + 1, start=1e-4)
    fl = jax.tree_util.tree_map(
        lambda a, b: np.concatenate([np.asarray(a), np.asarray(b)]),
        fl_w, fl_i)
    return ft, make_schedule(fl)


def fabric16_impairments(ft):
    """The headline's degraded spine: AGG<->CORE capacity flaps between
    40G and line rate twice a millisecond; every other link sees 0.2%
    random loss."""
    deg = LinkProcess(kind="oscillate", bw_lo=40e9, period=500e-6, seed=7)
    return fabric_impairments(ft, rules={(AGG, CORE): deg, (CORE, AGG): deg},
                              default=netem(loss=0.002, jitter=0.0, seed=13))


def anchor256():
    """The sharded engine's exactness anchor on the 256-host leaf-spine
    (the fig6 paper fabric): ``(topo, sched, slots, law_cfg, cfg,
    impair)`` with the mixed impairment regime (oscillating edge
    capacity + stochastic loss + jitter)."""
    ls = compile_routes(leaf_spine_fabric(racks=8, hosts_per_rack=32,
                                          spines=2))
    sched = make_schedule(poisson_websearch(ls, 0.3, 0.0012, DT, seed=11))
    S = -(-suggest_slots(sched, DT) // 8) * 8
    cfg = SimConfig(dt=DT, steps=3000, hist=512, update_period=2e-6)
    sp = CircuitSchedule(day=50 * US, night=10 * US, matchings=4).params()
    lcfg = default_law_config(schedule_as_flows(sched), expected_flows=8.0,
                              sched=sp)
    imp = fabric_impairments(
        ls, rules={(TOR, HOST): LinkProcess(kind="oscillate", bw_lo=2.5e9,
                                            period=200e-6, seed=5)},
        default=netem(loss=0.01, jitter=1e-6, seed=9))
    return ls.topology(), sched, S, lcfg, cfg, imp


def _fabric16_anchor_bitmatch(devices):
    """Sharded == reference slot engine, bit for bit, for EVERY law in
    the registry — feedback-channel laws (pause, incast, hop-local)
    included — at the 256-host leaf-spine anchor (the fig6 paper
    fabric), plus a megakernel spot-check. Queue trace, FCT vector,
    final windows and per-slot rate trajectories all compared with
    ``array_equal`` — any reordered reduction or FMA contraction in the
    sharded tick would trip this. A second pass reruns a feedback-
    channel-covering law subset under the mixed impairment regime
    (oscillating edge capacity + stochastic loss + jitter) and returns
    its verdict separately: (clean_ok, impaired_ok)."""
    topo, sched, S, lcfg, cfg, imp = anchor256()

    def _same(law, **kw):
        st_r, rec_r = simulate_slots(topo, sched, law, S, lcfg, cfg, **kw)
        st_d, rec_d = simulate_slots_sharded(topo, sched, law, S, lcfg,
                                             cfg, devices=devices, **kw)
        return bool(
            np.array_equal(np.asarray(rec_d.q), np.asarray(rec_r.q))
            and np.array_equal(np.asarray(st_d.fct), np.asarray(st_r.fct),
                               equal_nan=True)
            and np.array_equal(np.asarray(st_d.w), np.asarray(st_r.w))
            and np.array_equal(np.asarray(rec_d.lam_f),
                               np.asarray(rec_r.lam_f)))

    ok = True
    for law in LAW_REGISTRY:
        same = _same(law)
        if not same:
            print(f"fabric16 anchor MISMATCH: {law}")
        ok &= same
    st_m, rec_m = simulate_slots(topo, sched, "powertcp", S, lcfg, cfg,
                                 backend="megakernel")
    st_d, rec_d = simulate_slots_sharded(topo, sched, "powertcp", S, lcfg,
                                         cfg, devices=devices)
    ok &= bool(
        np.array_equal(np.asarray(rec_d.q), np.asarray(rec_m.q))
        and np.array_equal(np.asarray(st_d.fct), np.asarray(st_m.fct),
                           equal_nan=True)
        and np.array_equal(np.asarray(st_d.w), np.asarray(st_m.w)))

    # impaired pass: one law per feedback channel (receiver telemetry,
    # pause frames, incast notifications) — the full 13-law impaired
    # conformance matrix lives in tests/test_shard_scenario.py
    imp_ok = True
    for law in ("powertcp", "backpressure", "pulser"):
        same = _same(law, impair=imp)
        if not same:
            print(f"fabric16 impaired anchor MISMATCH: {law}")
        imp_ok &= same
    return bool(ok), bool(imp_ok)


def smoke_fabric16(devices=None) -> dict:
    """CI sharded-scenario leg: fct_fabric16_* fields for
    BENCH_sweep.json.

    One k=16 fat-tree scenario is chunk-streamed through the sharded
    slot engine twice — across the FULL device mesh and pinned to one
    device — over a bounded tick horizon (the schedule itself spans
    ~85 ms; the leg simulates the first 10 ms of it). Both timed legs
    run a degraded-spine impairment regime: every AGG<->CORE link's
    capacity oscillates (a flapping spine) and every other link takes
    light stochastic loss — the headline is a multi-device run of an
    *impaired* fabric, not just the clean one. Headline figures:
    completed flows per wall-second and the sharded-vs-single-device
    wall-clock speedup (CI gates ``>= 2.0`` on its 8-device mesh).
    ``fct_fabric16_devices_bitmatch`` additionally pins the mesh run to
    the 1-device run bit-for-bit at full scale, and the exactness
    anchors (`fct_fabric16_exact_bitmatch`, ``_impaired_bitmatch``)
    compare sharded vs reference for the whole law registry on the
    256-host leaf-spine. ``fct_fabric16_comm_*`` reports the analytic
    per-steady-tick communication volume of the mesh run (halo
    all_to_all + packed gather) next to the pre-diet baseline layout."""
    ndev = resolve_devices("auto" if devices is None else devices)
    ft, sched = fabric16_scenario()
    n = int(sched.start.shape[0])
    S, steps, chunk = 1024, 10_000, 2048
    cfg = SimConfig(dt=DT, steps=steps, hist=512, update_period=2e-6)
    lcfg = default_law_config(schedule_as_flows(sched), expected_flows=8.0)
    topo = ft.topology()
    imp = fabric16_impairments(ft)

    t0 = time.time()
    st_n, _ = simulate_slots_sharded(topo, sched, "powertcp", S, lcfg, cfg,
                                     record=False, devices=ndev,
                                     chunk=chunk, impair=imp)
    wall_n = time.time() - t0
    t0 = time.time()
    st_1, _ = simulate_slots_sharded(topo, sched, "powertcp", S, lcfg, cfg,
                                     record=False, devices=1, chunk=chunk,
                                     impair=imp)
    wall_1 = time.time() - t0

    completed = int(np.isfinite(np.asarray(st_n.fct)).sum())
    dev_bits = bool(
        np.array_equal(np.asarray(st_n.fct), np.asarray(st_1.fct),
                       equal_nan=True)
        and np.array_equal(np.asarray(st_n.w), np.asarray(st_1.w))
        and np.array_equal(np.asarray(st_n.q), np.asarray(st_1.q)))
    mi = shard_geometry(sched, S, ft.num_queues, ndev)
    census = comm_census(mi, S, int(np.asarray(sched.path).shape[1]),
                         ft.num_queues, record=False)
    exact_bits, impaired_bits = _fabric16_anchor_bitmatch(ndev)
    out = {
        "fct_fabric16_hosts": ft.n_hosts,
        "fct_fabric16_queues": ft.num_queues,
        "fct_fabric16_flows": n,
        "fct_fabric16_slots": S,
        "fct_fabric16_steps": steps,
        "fct_fabric16_chunk": chunk,
        "fct_fabric16_devices": ndev,
        "fct_fabric16_devices_avail": jax.local_device_count(),
        "fct_fabric16_impaired": True,
        "fct_fabric16_wall_s": round(wall_n, 3),
        "fct_fabric16_wall_1dev_s": round(wall_1, 3),
        "fct_fabric16_completed": completed,
        "fct_fabric16_flows_per_wall_s": round(completed / wall_n, 1),
        "fct_fabric16_shard_speedup": round(wall_1 / wall_n, 3),
        "fct_fabric16_comm_exchanges_per_tick": census[
            "exchanges_per_tick"],
        "fct_fabric16_comm_bytes_per_tick": census["bytes_per_tick"],
        "fct_fabric16_comm_rebuild_every": census["rebuild_every"],
        "fct_fabric16_comm_rebuild_bytes": census["rebuild_bytes"],
        "fct_fabric16_comm_baseline_bytes_per_tick": census[
            "baseline_bytes_per_tick"],
        "fct_fabric16_devices_bitmatch": dev_bits,
        "fct_fabric16_exact_bitmatch": exact_bits,
        "fct_fabric16_impaired_bitmatch": impaired_bits,
    }
    for k, v in out.items():
        emit(k, v)
    return out


def run_fat_tree_fct(k: int, load: float, duration: float, laws, seeds,
                     tag: str):
    """Web-search FCT on a compiled fat-tree through the slot engine."""
    ft = fat_tree(k)
    scheds = [make_schedule(poisson_websearch(ft, load, duration, DT,
                                              seed=s)) for s in seeds]
    slots = max(suggest_slots(s, DT) for s in scheds)
    n = sum(int(s.start.shape[0]) for s in scheds)
    steps = int((duration + 0.02) / DT)
    cfg = SimConfig(dt=DT, steps=steps, hist=512, update_period=2e-6)
    emit(f"{tag}.hosts", ft.n_hosts)
    emit(f"{tag}.load{int(load*100)}.slots", slots)
    rows = []
    from repro.core import stack_flow_schedules
    stacked = stack_flow_schedules(scheds, ft.num_queues)
    for law in laws:
        st, rec, wall = run_law_slots(ft.topology(), scheds, law, cfg,
                                      slots, expected_flows=8.0,
                                      record=False)
        s = fct_stats(st, stacked)
        rows.append({"law": law, "n_flows": n,
                     "short_p999_us": s["short_p"] * 1e6,
                     "med_p999_us": s["medium_p"] * 1e6,
                     "long_p999_us": s["long_p"] * 1e6,
                     "done": s["completed"], "wall_s": wall})
        for b in ("short", "med", "long"):
            emit(f"{tag}.load{int(load*100)}.{law}.{b}_p999_us",
                 f"{rows[-1][f'{b}_p999_us']:.1f}")
    print(table(rows, ["law", "short_p999_us", "med_p999_us",
                       "long_p999_us", "done", "n_flows", "wall_s"],
                f"{tag} — p99.9 FCT, web-search @ {int(load*100)}% load, "
                f"k={k} fat-tree ({ft.n_hosts} hosts, 5-hop ECMP)"))
    return {r["law"]: r for r in rows}


def run_incast_bench(k: int, fan_in: int, quick: bool):
    """Repeated incast bursts: victim-queue pressure + burst FCTs."""
    ft = fat_tree(k)
    n_bursts = 3 if quick else 6
    flows, bqs = incast_burst(ft, fan_in=fan_in, req_bytes=5e5,
                              n_bursts=n_bursts, period=3e-3, sim_dt=DT,
                              seed=1)
    sched = make_schedule(flows)
    cfg = SimConfig(dt=DT, steps=int(n_bursts * 3e-3 / DT) + 8000,
                    hist=512, update_period=2e-6)
    rows = []
    for law in (["powertcp", "hpcc"] if quick else
                ["powertcp", "theta_powertcp", "hpcc", "dcqcn"]):
        lcfg = default_law_config(schedule_as_flows(sched),
                                  expected_flows=float(fan_in))
        st, rec = simulate_slots(ft.topology(), sched, law,
                                 int(sched.start.shape[0]), lcfg, cfg)
        fct = np.asarray(st.fct)
        qmax = max(float(np.asarray(rec.q)[:, b].max()) for b in bqs)
        rows.append({"law": law, "done": int(np.isfinite(fct).sum()),
                     "fct_p99_us": float(np.nanpercentile(fct, 99)) * 1e6,
                     "victim_qmax_kb": qmax / 1e3})
        emit(f"fabric_incast.{law}.fct_p99_us",
             f"{rows[-1]['fct_p99_us']:.1f}")
    print(table(rows, ["law", "fct_p99_us", "victim_qmax_kb", "done"],
                f"fabric incast — {fan_in}:1 bursts x{n_bursts}, "
                f"k={k} fat-tree"))
    return {r["law"]: r for r in rows}


def run(quick: bool = False, devices=None):
    k = 4 if quick else 8
    laws = ["powertcp", "theta_powertcp", "hpcc"] if quick else LAWS
    load = 0.4
    duration = 0.006 if quick else 0.02
    r = run_fat_tree_fct(k, load, duration, laws, seeds=(1,),
                         tag="fabric_fct")
    p = r["powertcp"]
    ok = p["short_p999_us"] <= 1.10 * r["hpcc"]["short_p999_us"]
    ok &= r["theta_powertcp"]["short_p999_us"] <= \
        1.15 * r["hpcc"]["short_p999_us"]
    if not quick:
        ok &= p["short_p999_us"] <= 1.02 * r["timely"]["short_p999_us"]
        ok &= p["short_p999_us"] <= 1.02 * r["dcqcn"]["short_p999_us"]
    fan_in = 8 if quick else 16
    n_bursts = 3 if quick else 6
    inc = run_incast_bench(k, fan_in=fan_in, quick=quick)
    # every burst response must complete under PowerTCP, and PowerTCP
    # must keep the victim queue no worse than the other laws
    ok &= inc["powertcp"]["done"] == fan_in * n_bursts
    ok &= inc["powertcp"]["victim_qmax_kb"] <= \
        1.05 * min(v["victim_qmax_kb"] for v in inc.values())
    emit("fabric.claims_hold", ok)
    return bool(ok)


if __name__ == "__main__":
    run()
