"""Per-op cost breakdown of one simulator tick, per backend.

Future perf PRs should start from data, not guesses — per-tick cost on
CPU is dominated by which ops escape XLA fusion (scatters lower to
per-row while loops, gathers mostly fuse), and that is invisible from
wall-clock alone. This tool reports, for each requested slot-engine
backend:

  * wall-clock per tick (compile and steady-state separated, medians
    over repeats — single runs on shared machines swing 1.5x);
  * XLA cost analysis of the compiled program (flops / bytes accessed);
  * an HLO histogram of the scan body: op counts by kind, with the
    non-fusible kinds (scatter/gather/while/sort/reduce-window) called
    out — these are the per-tick cost centers.

Its wall clocks are those of the backend it runs on, and on the CPU no
device speed; device time per tick and per tick phase comes from the
benchmark's traced runs on the chip (``bench/run.py --trace 1``,
``bench/lib/progtrace.py``; PERF.md).

Usage:
    PYTHONPATH=src python tools/profile_tick.py [--hosts 256]
        [--load 0.6] [--steps 4096] [--slots 128] [--law powertcp]
        [--backends reference,megakernel] [--repeats 3]

Also wired as ``python -m benchmarks.run --profile`` (a reduced preset).
"""
from __future__ import annotations

import argparse
import collections
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# ops that do not fuse on XLA CPU: each instance is a per-tick thunk (and
# scatters are per-ROW while loops) — the usual suspects when a tick is
# slower than its arithmetic
NON_FUSIBLE = ("scatter", "gather", "while", "sort", "reduce-window",
               "dynamic-update-slice", "dynamic-slice", "reduce", "copy")


def build_scenario(hosts: int, load: float, dt: float, seed: int = 1):
    import numpy as np
    from repro.core import LeafSpine, make_schedule, poisson_websearch

    if hosts >= 256:
        fab = LeafSpine(racks=8, hosts_per_rack=32, spines=2)
    else:
        fab = LeafSpine()
    duration = 0.01 if hosts < 256 else 0.03
    flows = poisson_websearch(fab, load, duration, dt, seed=seed)
    return fab.topology(), make_schedule(flows)


def body_histogram(hlo_text: str):
    """Op-kind counts for every computation in the optimized HLO, plus
    the 'scan body' view: the largest computation (the while body of the
    time scan dominates instruction count)."""
    comps = collections.defaultdict(collections.Counter)
    cur = None
    for line in hlo_text.splitlines():
        if line and not line.startswith(" "):
            tok = line.split()
            if tok and (tok[0].startswith("%") or tok[0] == "ENTRY"):
                cur = tok[0] if tok[0] != "ENTRY" else tok[1]
        m = re.match(r"(?:ROOT )?%?\S+ = \S+ ([a-z][a-z0-9._-]*)\(",
                     line.strip())
        if m and cur:
            comps[cur][m.group(1)] += 1
    if not comps:
        return {}, {}

    def nf_count(c):
        return sum(v for k, v in c.items()
                   if any(s in k for s in NON_FUSIBLE))

    # the time-scan while body is the computation with the most
    # non-fusible ops (fusions just count 1 each there); tie-break on size
    body = max(comps.items(),
               key=lambda kv: (nf_count(kv[1]), sum(kv[1].values())))[1]
    total = collections.Counter()
    for c in comps.values():
        total.update(c)
    return dict(body), dict(total)


def profile_backend(topo, sched, law: str, slots: int, steps: int,
                    backend: str, repeats: int = 3):
    import numpy as np
    import jax
    from repro.core import SimConfig, simulate_slots

    cfg = SimConfig(dt=1e-6, steps=steps, hist=512, update_period=2e-6)

    # build the backend's scan program once and time the COMPILED
    # executable (simulate_slots re-traces per call; first_call_s below
    # reports that whole-pipeline cost separately)
    from repro.core.fluid import (SlotSim, _resolve_law,
                                  default_law_config, init_slot_state,
                                  slot_step)
    sim = SlotSim(topo, sched, _resolve_law(law, backend),
                  default_law_config(sched), cfg, int(slots), backend)
    if backend == "megakernel":
        from repro.core.megakernel import _due_table, make_tick
        tick = make_tick(sim)
        arg0 = tick.init_carry(init_slot_state(sim))
        due = _due_table(sched, steps, cfg.dt)

        def prog(c):
            # return the whole final carry: a scalar-only result would
            # let XLA dead-code-eliminate the simulation
            return jax.lax.scan(lambda cc, d: (tick(cc, d)[0], None),
                                c, due)[0]
    else:
        arg0 = init_slot_state(sim)

        def prog(s):
            return jax.lax.scan(
                lambda ss, _: (slot_step(sim, ss)[0], None), s, None,
                length=steps)[0]

    t0 = time.time()
    compiled = jax.jit(prog).lower(arg0).compile()
    out = compiled(arg0)
    jax.block_until_ready(out)
    first_s = time.time() - t0
    walls = []
    for _ in range(repeats):
        t0 = time.time()
        jax.block_until_ready(compiled(arg0))
        walls.append(time.time() - t0)
    wall_s = float(np.median(walls))

    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
    body, total = body_histogram(compiled.as_text())

    out = {
        "backend": backend,
        "wall_s": round(wall_s, 3),
        "compile_plus_first_run_s": round(first_s, 3),
        "us_per_tick": round(wall_s / steps * 1e6, 2),
        "flops_per_tick": round(float(cost.get("flops", 0)) / steps, 1),
        "bytes_per_tick": round(
            float(cost.get("bytes accessed", 0)) / steps, 1),
        "body_ops": int(sum(body.values())),
        "body_non_fusible": {k: v for k, v in sorted(body.items())
                             if any(s in k for s in NON_FUSIBLE)},
    }
    # accelerator roofline for the same tick (launch/roofline.py): what
    # the per-tick flops/bytes would cost compute- and memory-bound on
    # the reference chip — the measured-vs-roofline ratio separates
    # "the tick is doing too much work" from "CPU dispatch overhead"
    from repro.launch.roofline import tick_roofline
    rf = tick_roofline(out["flops_per_tick"], out["bytes_per_tick"])
    out["roofline"] = {
        "compute_us": round(rf["compute_us"], 4),
        "memory_us": round(rf["memory_us"], 4),
        "bound": rf["bound"],
        "intensity_flops_per_byte": round(
            rf["intensity_flops_per_byte"], 3),
        "measured_over_roofline": round(
            out["us_per_tick"] / max(rf["roofline_us"], 1e-9), 1),
    }
    return out


def comm_report(topo, sched, slots: int, devices: int):
    """Analytic per-steady-tick communication census of the sharded
    engine at this mesh width (core.shardslots.comm_census): f32 payload
    bytes per device per tick for each exchange, the rebuild traffic and
    its amortization cadence, the pre-diet gather layout alongside, and
    the reference-interconnect wire time (launch.roofline). Analytic by
    design — collective payloads are static shapes, so the census needs
    no mesh to run on and no profiler to read."""
    import numpy as np
    from repro.core import comm_census, shard_geometry
    from repro.launch.roofline import tick_collective

    mi = shard_geometry(sched, slots, topo.num_queues, devices)
    H = int(np.asarray(sched.path).shape[1])
    census = comm_census(mi, slots, H, int(topo.num_queues), record=False)
    wire = tick_collective(census)
    print(f"\n== sharded comm census (devices={devices}) ==")
    print(f"  geometry: Sl={mi.Sl} Qb={mi.Qb} cap={mi.cap} "
          f"maxdeg={mi.maxdeg} rb_every={mi.rb_every} "
          f"csr={mi.use_csr}")
    for name, b in census["bytes_per_exchange"].items():
        print(f"  {name:42s} {b} B/tick")
    print(f"  {'rebuild (every ' + str(census['rebuild_every']) + ' ticks)':42s} "
          f"{census['rebuild_bytes']} B")
    print(f"  exchanges/tick: {census['exchanges_per_tick']} "
          f"(baseline {census['baseline_exchanges_per_tick']})")
    print(f"  bytes/tick: {census['bytes_per_tick']} "
          f"(baseline {census['baseline_bytes_per_tick']}, "
          f"diet {wire['diet_ratio']:.2f}x)")
    print(f"  wire time: {wire['collective_us']:.3f} us/tick "
          f"(baseline {wire['baseline_collective_us']:.3f})")
    print(f"BENCH,profile_tick.comm.bytes_per_tick,"
          f"{census['bytes_per_tick']},B")
    print(f"BENCH,profile_tick.comm.diet_ratio,"
          f"{wire['diet_ratio']:.2f},x")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--hosts", type=int, default=256)
    ap.add_argument("--load", type=float, default=0.6)
    ap.add_argument("--steps", type=int, default=4096)
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--law", default="powertcp")
    ap.add_argument("--backends", default="reference,megakernel")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--shard-devices", type=int, default=0,
                    help="also print the sharded engine's per-tick "
                         "communication census for this mesh width "
                         "(analytic bytes per exchange, rebuild "
                         "amortization, pre-diet baseline, roofline "
                         "wire time)")
    a = ap.parse_args(argv)

    topo, sched = build_scenario(a.hosts, a.load, 1e-6)
    print(f"scenario: hosts={a.hosts} load={a.load} "
          f"flows={int(sched.start.shape[0])} queues={topo.num_queues} "
          f"slots={a.slots} steps={a.steps} law={a.law}")
    if a.shard_devices > 0:
        comm_report(topo, sched, a.slots, a.shard_devices)
    results = []
    for be in a.backends.split(","):
        if not be.strip():
            continue
        r = profile_backend(topo, sched, a.law, a.slots, a.steps,
                            be.strip(), a.repeats)
        results.append(r)
        print(f"\n== {be} ==")
        for k, v in r.items():
            if k in ("body_non_fusible", "roofline"):
                print(f"  {k}:")
                for kk, vv in v.items():
                    print(f"    {kk:42s} {vv}")
            else:
                print(f"  {k}: {v}")
        print(f"BENCH,profile_tick.{be}.us_per_tick,"
              f"{r['us_per_tick']},us")
        print(f"BENCH,profile_tick.{be}.roofline_{r['roofline']['bound']}"
              f"_bound_us,{max(r['roofline']['compute_us'], r['roofline']['memory_us']):.4f},us")
    if len(results) == 2:
        sp = results[0]["wall_s"] / max(results[1]["wall_s"], 1e-9)
        print(f"\nBENCH,profile_tick.speedup,{sp:.2f},x")
    return 0


if __name__ == "__main__":
    sys.exit(main())
