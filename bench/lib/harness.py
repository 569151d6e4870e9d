"""One run of one cell: set-up, the measured window, the check.

Set-up builds the deployment from the configuration file, draws from
``--seed`` the window's first job (a scenario) and runs
one more job, drawn apart, to warm every program the window uses. The
window runs job after job of the seed's stream through the
configuration's program entry until ``--seconds`` have passed, finishes
the job in flight, and divides all the work by all the elapsed time.
Each later job's flow arrays are drawn between jobs with the clock
stopped, so the stream never runs out however fast the program gets.
Routing, ``make_schedule``, the entry call and fetching the FCT vectors
to the host are inside the window. Afterwards a sample of the window's
results, drawn from the seed, is compared with the plain reference
(``check.py``).

``--trace 1`` traces the window's first job with the JAX profiler and
reports the per-layer metrics instead; the run then ends after it.
"""
from __future__ import annotations

import contextlib
import gzip
import itertools
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import check, peaks, trace, traffic

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    pass


def devices(chips: int):
    """The cell's devices; raises ``NoChip`` unless JAX finds that many
    TPU chips (a measurement never falls back to the CPU)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


MIN_COMPILE = "jax_persistent_cache_min_compile_time_secs"


def use_compile_cache(root: str) -> float:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    if set, else the fixed ``<checkout>/.jax_cache``, as the program's
    entry points keep it. For set-up every program is kept, however
    quickly it compiled, so that later runs find set-up's small programs
    too. Returns JAX's own threshold, which ``window_cache`` puts back
    before the window, where the program is cached as its users run it."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    default = getattr(jax.config, MIN_COMPILE)
    jax.config.update(MIN_COMPILE, 0.0)
    return default


def window_cache(threshold: float):
    import jax
    jax.config.update(MIN_COMPILE, threshold)


class CompileLog:
    """Host-clock spans of JAX's trace, lowering and compile events (pass
    to ``jax.monitoring.register_event_time_span_listener``)."""

    def __init__(self):
        self.spans = []

    def __call__(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))

    def seconds(self, lo, hi) -> float:
        return trace.length(trace.clip(trace.union(self.spans), lo, hi))


WARM = 2**32 - 1          # the warm-up job's place in the stream, never reached


def make_job(cell, fab: dict, seed: int, j: int) -> dict:
    """Job ``j`` of the stream that ``seed`` gives: one scenario, drawn
    from its own traffic seed."""
    (s,) = traffic.scenario_seeds(seed, j, 1)
    return dict(points=[dict(law=cell.config["law"], scenario=int(s),
                             groups=traffic.scenario(cell.traffic, fab,
                                                     int(s)))])


def run(cell, seed: int, seconds: float, traced: bool, root: str,
        require_tpu: bool = True, out=sys.stdout, err=sys.stderr,
        t_start: float | None = None, keep_trace: str | None = None) -> int:
    """Run ``cell`` once; print its result line on ``out``. Set-up is
    timed from ``t_start`` (the process's start) where given. A traced
    run keeps its profile, gzipped, at ``keep_trace`` where given, and
    beside it (``.json``) what the readers take from the host."""
    import jax
    t_setup = time.perf_counter() if t_start is None else t_start
    devs = devices(cell.chips) if require_tpu else jax.devices()[:cell.chips]
    kind = devs[0].device_kind
    if require_tpu:
        peaks.lookup(kind)              # an unknown chip is an error
    threshold = use_compile_cache(root)
    comp = CompileLog()
    jax.monitoring.register_event_time_span_listener(comp)

    from . import fabrics, program
    cfg = cell.config
    desc = fabrics.describe(cfg["fabric"])
    fab = dict(n_hosts=desc.n_hosts, group=desc.group,
               load_capacity=desc.load_capacity)
    dep = program.deploy(cfg)
    entry = cell.load_module("entries",
                             cell.traffic.get("entry", cfg["entry"]))
    first = make_job(cell, fab, seed, 0)
    entry.run(dep, cfg, make_job(cell, fab, seed, WARM),
              lambda _: contextlib.nullcontext())
    setup_s = time.perf_counter() - t_setup
    window_cache(threshold)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None

    @contextlib.contextmanager
    def span(name):
        if traced:
            with jax.profiler.TraceAnnotation("bench." + name):
                yield
        else:
            yield

    results, attempted, failed = [], 0, 0
    drawing = 0.0                       # clock stopped while jobs are drawn
    t0 = time.time()
    w0 = time.perf_counter()
    if traced:
        jax.profiler.start_trace(trace_dir)
    for i in itertools.count():
        if i:
            d0 = time.perf_counter()
            job = make_job(cell, fab, seed, i)
            drawing += time.perf_counter() - d0
        else:
            job = first
        attempted += len(job["points"])
        try:
            fcts = entry.run(dep, cfg, job, span)
            results.append((job, fcts))
        except Exception as e:          # a failed job counts, and the run goes on
            failed += len(job["points"])
            print(f"job {i} failed: {e!r}", file=err)
        if traced or time.perf_counter() - w0 - drawing >= seconds:
            break
    elapsed = time.perf_counter() - w0 - drawing
    t1 = time.time()
    if traced:
        jax.profiler.stop_trace()
    if not results:
        raise RuntimeError("no job of the window finished")

    mem = [d.memory_stats() or {} for d in devs]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    steps = cfg["sim"]["steps"]
    points = sum(len(j["points"]) for j, _ in results)
    totals = {"sim_ticks_per_s": steps * points / elapsed,
              "points_per_s": points / elapsed, "setup_s": setup_s}

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    metrics, breakdown = {}, None
    if traced:
        r0 = time.perf_counter()
        xplane = trace.find_xplane(trace_dir)
        size = os.path.getsize(xplane)
        if keep_trace:
            with open(xplane, "rb") as f, gzip.open(keep_trace, "wb") as g:
                shutil.copyfileobj(f, g)
            with open(keep_trace + ".json", "w") as f:
                json.dump({"wall": [t0, t1], "elapsed": elapsed,
                           "steps": steps,
                           "compile_spans": trace.clip(comp.spans, t0, t1),
                           "points": [[p["law"] for p in j["points"]]
                                      for j, _ in results]}, f)
        tr = trace.load(xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: {size / 2**20:.1f} MiB, "
              f"{sum(map(len, tr['ops'].values()))} device ops, read in "
              f"{time.perf_counter() - r0:.1f} s", file=err)
        ctx = reading_context(cell, tr, comp, (t0, t1), elapsed, results)
        for m in cell.metrics("per_layer"):
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if ctx.get("busiest") is not None:
            device["busy_s"] = ctx["busy_s"]
            device["window_s"] = ctx["window_s"]
            breakdown = ctx["breakdown"]
    else:
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": totals[m["name"]],
                                  "unit": m["unit"]}
    print(f"window: {len(results)} jobs, {points} points, {elapsed:.3f} s; "
          f"compile inside it {comp.seconds(t0, t1):.3f} s; "
          f"set-up {setup_s:.3f} s", file=err)

    del dep
    rng = np.random.default_rng([seed, 1])
    numbers = check.compare(cell, desc, results, rng, err)
    correct = failed == 0 and all(v["value"] <= v["limit"]
                                  for v in numbers.values())
    for k, v in numbers.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=err)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["check"] = numbers
    print(json.dumps(line), file=out)
    return 0


def reading_context(cell, tr, comp, wall, elapsed, results) -> dict:
    """What the per-layer readers see: the reduced trace, the window's
    bounds on the trace clock, the compile seconds inside its wall-clock
    bounds ``wall`` over its ``elapsed`` seconds, and the number of ticks
    the device stepped."""
    win = trace.window(tr)
    ctx = {"cell": cell.name, "trace": tr, "window": win,
           "chips": cell.chips}
    ctx["device_ticks"] = cell.config["sim"]["steps"] * sum(
        len(j["points"]) for j, _ in results)
    ctx["compile_s"] = comp.seconds(*wall)
    ctx["compile_share"] = ctx["compile_s"] / elapsed
    if win is None:
        return ctx
    lo, hi = win
    ctx["window_s"] = (hi - lo) * 1e-9
    b = trace.busy(tr, lo, hi)
    ctx["busy_s"] = (sum(b.values()) / len(b) * 1e-9) if b else 0.0
    dev = trace.busiest(tr, lo, hi)
    ctx["busiest"] = dev
    if dev is not None:
        ctx["busiest_busy_s"] = b[dev] * 1e-9
        ctx["busiest_ops"] = sum(1 for _, s, e in tr["ops"][dev]
                                 if s >= lo and e <= hi)
        gaps = sorted(trace.idle_gaps(tr, dev, lo, hi), key=lambda g: -g[1])
        ctx["breakdown"] = {
            "device_ops": [[n, t * 1e-9] for n, t in
                           trace.top_ops(tr, dev, lo, hi)],
            "idle_gaps": [[n, t * 1e-9] for n, t in gaps[:10]]}
    return ctx
