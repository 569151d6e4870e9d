#!/usr/bin/env python3
"""Bring-up check of the PowerTCP fluid simulator on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded paths on four chips

One chip runs three phases through the simulator's public entry points,
each checked against the repo's reference engine on the same chip:

  anchor      the 12-flow single-bottleneck scenario on the padded
              ``simulate``, the slot engine and the megakernel;
  deployment  the paper's 256-host 4:1 leaf-spine, PowerTCP, Poisson
              web-search at 60% load: 30 ms of arrivals + 10 ms of drain
              (40,000 ticks at 1 us) on the padded reference, the slot
              engine, the megakernel and the ``fused`` Pallas backend;
  sweep       the fig8 RDCN grid through ``run_sweep`` against serial
              ``simulate``.

``--chips 4`` runs only the sharded paths: the k=16 fat-tree under a
degraded spine on four devices against one, and the 256-host anchor
sharded over four devices against the reference slot engine.

Everything runs in this one process, which holds the chip. JAX's
persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<repo>/.jax_cache``. The last line of stdout is one JSON
object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``. With no
TPU the script exits 2 before running anything; a failed gate exits 1.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402

import repro  # noqa: E402,F401  (XLA:CPU flags before JAX starts)
import jax  # noqa: E402

DT = 1e-6
SHORT = 10e3                     # short-flow bucket (< 10 KB, paper Fig. 6)
P999_RTOL = 1e-3                 # cross-engine short-flow p99.9 tolerance
FUSED_RTOL, FUSED_ATOL = 1e-4, 2e-6   # fused backend's documented closeness


class Smoke:
    """One run's record: named pass/fail gates, each printed as it is
    decided, and the backend compile seconds JAX reports (pass the object
    to ``jax.monitoring.register_event_duration_secs_listener``)."""

    def __init__(self):
        self.failed = []
        self.compile_s = 0.0

    def __call__(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def check(self, name: str, ok, detail: str = ""):
        ok = bool(ok)
        print(f"  gate {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip())
        if not ok:
            self.failed.append(name)

    def timed(self, fn):
        """``(result, wall_s, compile_s)``: wall time of ``fn()`` up to
        ready outputs, and the backend compile seconds spent inside it."""
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        return out, time.perf_counter() - t0, self.compile_s - c0


def _short_p999(fct, size, done):
    return float(np.percentile(fct[done & (size < SHORT)], 99.9))


def compare(run: Smoke, name: str, fct, ref, size, rtol=P999_RTOL,
            atol=0.0, report_bits=False):
    """Gate one engine's FCT vector against the reference run's: the same
    completion set, and short-flow p99.9 FCT within ``rtol``/``atol``."""
    fct, ref = np.asarray(fct, np.float64), np.asarray(ref, np.float64)
    done, done_ref = np.isfinite(fct), np.isfinite(ref)
    both = done & done_ref
    diff = float(np.abs(fct[both] - ref[both]).max()) if both.any() else 0.0
    run.check(f"{name}.completion_set", np.array_equal(done, done_ref),
              f"({int(done.sum())} vs {int(done_ref.sum())} completed)")
    if (both & (size < SHORT)).any():
        p, p_ref = _short_p999(fct, size, both), _short_p999(ref, size, both)
        run.check(f"{name}.short_p999",
                  abs(p - p_ref) <= atol + rtol * abs(p_ref),
                  f"({p * 1e6:.6f} us vs {p_ref * 1e6:.6f} us, rel "
                  f"{abs(p - p_ref) / max(abs(p_ref), 1e-30):.3e})")
    bits = np.array_equal(fct, ref, equal_nan=True)
    print(f"  {name}: max |FCT - ref| = {diff:.3e} s"
          + (f", bitwise equal: {bits}" if report_bits else ""))
    return bits


def _report(name, wall, comp, warm=None, warm_comp=None):
    line = f"  {name}: first call {wall:.3f} s (compile {comp:.3f} s)"
    if warm is not None:
        line += f", warm call {warm:.3f} s (compile {warm_comp:.3f} s)"
    print(line)


def phase_anchor(run: Smoke):
    from repro.core import (GBPS, SimConfig, default_law_config,
                            make_flows_single, make_schedule,
                            schedule_as_flows, simulate, simulate_slots,
                            single_bottleneck)
    print("[anchor] 12 flows, single 100G bottleneck, 3000 ticks")
    B = 100 * GBPS
    topo = single_bottleneck(bandwidth=B, buffer=16e6)
    rng = np.random.default_rng(0)
    sched = make_schedule(make_flows_single(
        12, tau=20e-6, nic=B, sizes=rng.uniform(1e5, 5e5, 12),
        starts=rng.uniform(0.0, 1e-3, 12), sim_dt=DT))
    flows = schedule_as_flows(sched)
    cfg = SimConfig(dt=DT, steps=3000, hist=256)
    lcfg = default_law_config(flows, expected_flows=8.0)
    size = np.asarray(sched.size)
    (ref, _), w, c = run.timed(lambda: simulate(topo, flows, "powertcp",
                                                lcfg, cfg))
    _report("padded", w, c)
    for backend in ("reference", "megakernel"):
        (st, _), w, c = run.timed(lambda: simulate_slots(
            topo, sched, "powertcp", 16, lcfg, cfg, backend=backend))
        _report(f"slot/{backend}", w, c)
        compare(run, f"anchor.{backend}", st.fct, ref.fct, size,
                report_bits=True)


def _realized_slots(sched, fct, dt):
    """Pool size that admits every flow on arrival: peak overlap of
    [start, start + FCT + drain hold) from the padded run, in 64s."""
    starts = np.asarray(sched.start, np.float64)
    fct = np.asarray(fct, np.float64)
    hold = int(np.asarray(sched.tf_steps).max()) * dt
    ends = starts + np.where(np.isfinite(fct), fct, np.inf) + hold
    from repro.core import peak_concurrency
    peak = peak_concurrency(starts, ends)
    return min(-(-max(peak, 1) // 64) * 64, int(starts.shape[0]))


def phase_deployment(run: Smoke, duration=0.03, drain=0.01, hosts=(8, 32)):
    from repro.core import (LeafSpine, SimConfig, default_law_config,
                            make_schedule, poisson_websearch,
                            schedule_as_flows, simulate, simulate_slots)
    racks, per_rack = hosts
    fab = LeafSpine(racks=racks, hosts_per_rack=per_rack, spines=2)
    sched = make_schedule(poisson_websearch(fab, 0.6, duration, DT, seed=1))
    flows = schedule_as_flows(sched)
    steps = int(round((duration + drain) / DT))
    cfg = SimConfig(dt=DT, steps=steps, hist=512, update_period=2e-6)
    lcfg = default_law_config(flows, expected_flows=8.0)
    topo = fab.topology()
    size = np.asarray(sched.size)
    n = int(size.shape[0])
    print(f"[deployment] {fab.n_hosts}-host leaf-spine, powertcp, "
          f"web-search 60% load, {n} flows scheduled, {steps} ticks, "
          f"on {jax.devices()[0].device_kind}")

    def run_twice(name, fn):
        (st, _), w, c = run.timed(fn)
        (st, _), w2, c2 = run.timed(fn)
        _report(name, w, c, w2, c2)
        return st

    ref = run_twice("padded (reference)", lambda: simulate(
        topo, flows, "powertcp", lcfg, cfg, record=False))
    print(f"  padded: {int(np.isfinite(np.asarray(ref.fct)).sum())} of "
          f"{n} flows completed")
    S = _realized_slots(sched, ref.fct, DT)
    print(f"  slot pool: {S} slots")
    for backend in ("reference", "megakernel"):
        st = run_twice(f"slot/{backend}", lambda: simulate_slots(
            topo, sched, "powertcp", S, lcfg, cfg, record=False,
            backend=backend))
        compare(run, f"deployment.{backend}", st.fct, ref.fct, size)
    st = run_twice("padded/fused", lambda: simulate(
        topo, flows, "powertcp", lcfg, cfg, record=False, backend="fused"))
    compare(run, "deployment.fused", st.fct, ref.fct, size,
            rtol=FUSED_RTOL, atol=FUSED_ATOL)


def phase_sweep(run: Smoke):
    from benchmarks.run import smoke_rdcn
    print("[sweep] fig8 RDCN grid through run_sweep vs serial simulate")
    r = smoke_rdcn()
    print(f"  {r['rdcn_points']} points; run_sweep {r['rdcn_batched_s']} s, "
          f"serial {r['rdcn_serial_s']} s (both include compiles)")
    run.check("sweep.util", r["rdcn_util_max_abs_err"] < 5e-3,
              f"(max |err| {r['rdcn_util_max_abs_err']})")
    run.check("sweep.p99", r["rdcn_p99_max_abs_err_s"] < 1e-6,
              f"(max |err| {r['rdcn_p99_max_abs_err_s']} s)")


def _check_shards(run: Smoke, name: str, x, ndev: int):
    devs = {s.device for s in x.addressable_shards}
    run.check(f"{name}.shards_on_all_devices", len(devs) == ndev,
              f"({len(devs)} devices hold shards of a {x.shape} leaf)")


def phase_fabric16(run: Smoke, ndev: int, steps=10_000):
    from benchmarks.fabric_fct import fabric16_impairments, fabric16_scenario
    from repro.core import (SimConfig, default_law_config, schedule_as_flows,
                            simulate_slots_sharded)
    ft, sched = fabric16_scenario()
    S, chunk = 1024, 2048
    cfg = SimConfig(dt=DT, steps=steps, hist=512, update_period=2e-6)
    lcfg = default_law_config(schedule_as_flows(sched), expected_flows=8.0)
    topo, imp = ft.topology(), fabric16_impairments(ft)
    size = np.asarray(sched.size)
    print(f"[fabric16] k=16 fat-tree, degraded spine, "
          f"{int(size.shape[0])} flows, S={S}, chunk={chunk}, {steps} ticks")
    out = {}
    for d in (ndev, 1):
        (st, _), w, c = run.timed(lambda: simulate_slots_sharded(
            topo, sched, "powertcp", S, lcfg, cfg, record=False, devices=d,
            chunk=chunk, impair=imp))
        _report(f"devices={d}", w, c)
        print(f"  devices={d}: {int(np.isfinite(np.asarray(st.fct)).sum())}"
              f" flows completed")
        out[d] = st
    _check_shards(run, "fabric16", out[ndev].w, ndev)
    compare(run, f"fabric16.devices{ndev}_vs_1", out[ndev].fct, out[1].fct,
            size, report_bits=True)


def phase_anchor256(run: Smoke, ndev: int):
    from benchmarks.fabric_fct import anchor256
    from repro.core import simulate_slots, simulate_slots_sharded
    topo, sched, S, lcfg, cfg, imp = anchor256()
    size = np.asarray(sched.size)
    print(f"[anchor256] 256-host leaf-spine, {int(size.shape[0])} flows, "
          f"S={S}, {cfg.steps} ticks, mixed impairments")
    for law in ("powertcp", "backpressure", "pulser"):
        (ref, _), w, c = run.timed(lambda: simulate_slots(
            topo, sched, law, S, lcfg, cfg, impair=imp))
        _report(f"{law} slot/reference", w, c)
        (st, _), w, c = run.timed(lambda: simulate_slots_sharded(
            topo, sched, law, S, lcfg, cfg, devices=ndev, impair=imp))
        _report(f"{law} sharded devices={ndev}", w, c)
        _check_shards(run, f"anchor256.{law}", st.w, ndev)
        compare(run, f"anchor256.{law}", st.fct, ref.fct, size,
                report_bits=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded four-chip phases")
    a = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devs) < a.chips:
        print(f"chip_smoke: --chips {a.chips} needs {a.chips} TPU devices, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 2
    from benchmarks.common import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    run = Smoke()
    jax.monitoring.register_event_duration_secs_listener(run)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}")

    t0 = time.perf_counter()
    if a.chips == 4:
        phase_fabric16(run, 4)
        phase_anchor256(run, 4)
    else:
        phase_anchor(run)
        phase_deployment(run)
        phase_sweep(run)
    print(f"total {time.perf_counter() - t0:.1f} s, "
          f"backend compile {run.compile_s:.1f} s")
    if run.failed:
        print(f"FAILED gates: {', '.join(run.failed)}")
    print(json.dumps({"ok": not run.failed, "device": device}))
    return 1 if run.failed else 0


if __name__ == "__main__":
    sys.exit(main())
