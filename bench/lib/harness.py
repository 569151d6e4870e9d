"""One run of one cell: set-up, the measured window, the check.

Set-up builds the deployment from the configuration file, draws from
``--seed`` the window's first job (a scenario, or a grid of them) and runs
one more job, drawn apart, to warm every program the window uses (of a
grid, whose programs follow each job's scenarios, the same job in every
run, and the window compiles its own). The
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
reports the per-layer metrics instead; the run then ends after it. An
entry whose job outlasts the profiler's buffer has it traced in short
samples at even times (``Profile``), and the readers read the samples.
"""
from __future__ import annotations

import contextlib
import gzip
import heapq
import itertools
import json
import math
import os
import shutil
import sys
import tempfile
import threading
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
    from its own traffic seed, run by the configuration's ``law``.

    A mix with ``"grid": {"laws": [...], "loads": [...],
    "seeds_per_load": n}`` makes the job a grid instead: points in the
    order law x load x seed, ``len(loads) * n`` scenarios drawn from the
    job's seeds (each at its load in place of the web-search load), the
    same scenarios for every law."""
    grid = cell.traffic.get("grid")
    if grid is None:
        (s,) = traffic.scenario_seeds(seed, j, 1)
        return dict(points=[dict(law=cell.config["law"], scenario=int(s),
                                 groups=traffic.scenario(cell.traffic, fab,
                                                         int(s)))])
    loads, n = grid["loads"], int(grid["seeds_per_load"])
    seeds = traffic.scenario_seeds(seed, j, len(loads) * n)
    scen = [dict(load=load, scenario=int(s),
                 groups=traffic.scenario(traffic.at_load(cell.traffic, load),
                                         fab, int(s)))
            for load, s in zip([x for x in loads for _ in range(n)], seeds)]
    return dict(points=[dict(sc, law=law) for law in grid["laws"]
                        for sc in scen])


SAMPLE_S = 0.02             # seconds of each sample of a long job
SAMPLE_EVERY_S = 2.0        # ... one begun every so many seconds


def _mark(name):
    import jax
    with jax.profiler.TraceAnnotation("bench." + name):
        pass


class Profile:
    """The JAX profiler over a traced run's first job: the whole job, or,
    for an entry whose job outlasts the profiler's device buffer (which
    holds some six million ops; the entry sets ``TRACE_SAMPLED``),
    samples: ``SAMPLE_S`` seconds every ``SAMPLE_EVERY_S`` from the
    window's start until the job ends, XLA ops alone, each in a
    directory of its own between two markers, ``start`` and ``cut``, and
    labelled with the innermost harness span open at its start. The
    profiler's stop (about 100 us an op) runs on the sampling thread
    while the job goes on; a sample whose time comes while the last stop
    still runs is left out. Samples spread evenly in time weight each
    program of the job by its duration."""

    def __init__(self, sampled: bool = False):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.sampled = sampled
        self.paths, self.labels, self.open = [], [], []
        self.done = threading.Event()
        self.thread = None
        self.stop_s = 0.0
        self.skipped = 0

    def begin(self):
        import jax
        if not self.sampled:
            jax.profiler.start_trace(self.dir)
            self.paths.append(self.dir)
            return
        self.thread = threading.Thread(target=self._sampling)
        self.thread.start()

    def end(self):
        import jax
        if self.sampled:
            self.done.set()
            self.thread.join()
            return
        s0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - s0

    def _sampling(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
        t0 = time.perf_counter()
        for k in itertools.count():
            if self.done.is_set():
                return
            wait = t0 + k * SAMPLE_EVERY_S - time.perf_counter()
            if wait < -SAMPLE_S:
                self.skipped += 1
                continue
            if self.done.wait(max(wait, 0.0)):
                return
            path = os.path.join(self.dir, str(k))
            jax.profiler.start_trace(path, profiler_options=opts)
            self.labels.append((self.open[-1:] or ["harness"])[0])
            _mark("start")
            self.done.wait(SAMPLE_S)
            _mark("cut")
            s0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s += time.perf_counter() - s0
            self.paths.append(path)

    @contextlib.contextmanager
    def span(self, name):
        import jax
        self.open.append(name)
        try:
            with jax.profiler.TraceAnnotation("bench." + name):
                yield
        finally:
            self.open.remove(name)


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
    desc = fabrics.describe(cfg["fabric"], cell.dir)
    fab = dict(n_hosts=desc.n_hosts, group=desc.group,
               load_capacity=desc.load_capacity)
    dep = program.deploy(cfg, cell.dir)
    entry = cell.load_module("entries",
                             cell.traffic.get("entry", cfg["entry"]))
    first = make_job(cell, fab, seed, 0)
    # A grid job's programs take their shapes from its own scenarios (the
    # sweep pads each law's lanes to the job's largest), as a user's
    # fresh sweep does. No warm-up can hold the window's, so the warm-up
    # job is the same in every run (drawn from seed 0) and set-up finds
    # its programs in the cache after the first run; the window keeps
    # none of its own, so that every run compiles what the first did.
    grid = "grid" in cell.traffic
    entry.run(dep, cfg, make_job(cell, fab, 0 if grid else seed, WARM),
              lambda _: contextlib.nullcontext())
    setup_s = time.perf_counter() - t_setup
    window_cache(math.inf if grid else threshold)

    prof = Profile(getattr(entry, "TRACE_SAMPLED", False)) if traced else None
    span = prof.span if traced else (lambda _: contextlib.nullcontext())

    results, attempted, failed = [], 0, 0
    drawing = 0.0                       # clock stopped while jobs are drawn
    t0 = time.time()
    w0 = time.perf_counter()
    if traced:
        prof.begin()
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
    window_cache(threshold)
    if traced:
        prof.end()
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
        trs, size = [], 0
        for k, path in enumerate(prof.paths):
            xplane = trace.find_xplane(path)
            size += os.path.getsize(xplane)
            if keep_trace:
                keep(xplane, keep_trace if k == 0 else f"{keep_trace}.{k}",
                     dict(wall=[t0, t1], elapsed=elapsed, steps=steps,
                          compile_spans=trace.clip(comp.spans,
                                                   t0, t1).tolist(),
                          points=[[p["law"] for p in j["points"]]
                                  for j, _ in results]))
            trs.append(trace.load(xplane))
        shutil.rmtree(prof.dir, ignore_errors=True)
        r1 = time.perf_counter()
        if not prof.sampled:
            ctx = reading_context(cell, trs[0], comp, (t0, t1), elapsed,
                                  results)
        else:
            ctx = sampled_context(cell, trs, prof.labels, comp, (t0, t1),
                                  elapsed)
        what = ("the job" if not prof.sampled else
                f"{len(trs)} samples of {SAMPLE_S} s every "
                f"{SAMPLE_EVERY_S} s ({prof.skipped} left out)")
        print(f"trace: {what}, {size / 2**20:.1f} MiB, "
              f"{sum(len(v) for t in trs for v in t['ops'].values())} "
              f"device ops; stopped in {prof.stop_s:.1f} s, read in "
              f"{r1 - r0:.1f} s, reduced in {time.perf_counter() - r1:.1f} s",
              file=err)
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


def keep(xplane: str, path: str, host: dict):
    """A traced run's profile, gzipped, at ``path``, and beside it
    (``.json``) what the readers take from the host."""
    with open(xplane, "rb") as f, gzip.open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(path + ".json", "w") as f:
        json.dump(host, f)


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
    dev = trace.busiest(b)
    ctx["busiest"] = dev
    if dev is not None:
        ctx["busiest_busy_s"] = b[dev] * 1e-9
        ctx["busiest_ops"] = sum(1 for _, s, e in tr["ops"][dev]
                                 if s >= lo and e <= hi)
        gaps = heapq.nlargest(10, trace.idle_gaps(tr, dev, lo, hi),
                              key=lambda g: g[1])
        ctx["breakdown"] = {
            "device_ops": [[n, t * 1e-9] for n, t in
                           trace.top_ops(tr, dev, lo, hi)],
            "idle_gaps": [[n, t * 1e-9] for n, t in gaps]}
    return ctx


def sampled_context(cell, trs, labels, comp, wall, elapsed) -> dict:
    """What the per-layer readers see of a job traced in samples
    (``Profile``): the compile seconds as ``reading_context`` gives them,
    and of each sample, between its ``start`` and ``cut`` markers, the
    busiest device's busy seconds, its ops and the ticks of a program's
    loop that it holds (``trace.loop_ticks``), under ``samples``. Busy
    and window seconds and the breakdown add the samples; an idle gap is
    named by the sample's label."""
    ctx = {"cell": cell.name, "chips": cell.chips, "device_ticks": None,
           "samples": [], "busiest": None}
    ctx["compile_s"] = comp.seconds(*wall)
    ctx["compile_share"] = ctx["compile_s"] / elapsed
    busy = window = 0.0
    ops, gaps = {}, []
    for tr, label in zip(trs, labels):
        marks = {n: s for n, s, _ in tr["spans"]}
        lo, hi = marks["start"], marks["cut"]
        tr["spans"] = [(label, lo, hi)]
        b = trace.busy(tr, lo, hi)
        window += (hi - lo) * 1e-9
        dev = trace.busiest(b)
        if dev is None:
            gaps.append((label, hi - lo))
            continue
        ctx["busiest"] = dev
        ctx["samples"].append(dict(
            busy_s=b[dev] * 1e-9, window_s=(hi - lo) * 1e-9,
            ticks=trace.loop_ticks(tr, dev, lo, hi),
            ops=sum(1 for _, s, e in tr["ops"][dev] if s >= lo and e <= hi)))
        busy += sum(b.values()) / len(b) * 1e-9
        for n, t in trace.top_ops(tr, dev, lo, hi):
            ops[n] = ops.get(n, 0) + t
        gaps += trace.idle_gaps(tr, dev, lo, hi)
    if ctx["busiest"] is not None:
        ctx["busy_s"], ctx["window_s"] = busy, window
        ctx["breakdown"] = {
            "device_ops": [[n, t * 1e-9] for n, t in
                           heapq.nlargest(10, ops.items(),
                                          key=lambda kv: kv[1])],
            "idle_gaps": [[n, t * 1e-9] for n, t in
                          heapq.nlargest(10, gaps, key=lambda g: g[1])]}
    return ctx
