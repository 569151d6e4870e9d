"""Every per-layer reader against numbers worked out by hand, on a small
trace of hand-made intervals (ns) with the shape the profiler gives:
two devices, the harness's spans, overlapping ops and collectives; the
counter readers on hand-made counters."""
import gzip
import types

import pytest

from bench.lib import harness, spec, trace

TRACE = {
    "spans": [("schedule", 0, 100), ("simulate", 100, 900),
              ("fetch", 900, 1000)],
    "ops": {
        "/device:TPU:0": [("fusion.1", 150, 250), ("fusion.2", 240, 400),
                          ("all-to-all.3", 500, 600), ("fusion.4", 550, 580),
                          ("copy.5", 950, 980)],
        "/device:TPU:1": [("fusion.1", 200, 300), ("all-reduce.2", 700, 760)],
    },
}
# compile spans on the host clock (s); the window is wall (0, 1000)
COMPILE = [(10, 30), (20, 50), (990, 1010)]
NAME = "slot_program_hit_share.deploy"         # read from counters alone


@pytest.fixture
def ctx():
    cell = types.SimpleNamespace(name="c", chips=2,
                                 config={"sim": {"steps": 5}})
    comp = harness.CompileLog()
    comp.spans = list(COMPILE)
    job = {"points": [{"law": "powertcp"}, {"law": "hpcc"}]}
    return harness.reading_context(cell, TRACE, comp, (0, 1000), 1000,
                                   [(job, None)])


def read(name, ctx):
    return spec.reader(name).read(ctx)


def test_window_and_busy(ctx):
    # device 0: [150,400) + [500,600) + [950,980) = 380 ns; device 1: 160
    assert ctx["window_s"] == pytest.approx(1000e-9)
    assert ctx["busiest"] == "/device:TPU:0"
    assert ctx["busiest_busy_s"] == pytest.approx(380e-9)
    assert ctx["busy_s"] == pytest.approx(270e-9)
    assert ctx["device_ticks"] == 10           # 5 ticks x 2 scenarios


def test_readers(ctx):
    assert read("idle_share.deploy", ctx) == pytest.approx(62.0)
    assert read("compile_share.deploy", ctx) == pytest.approx(5.0)
    assert read("tick_us.deploy", ctx) == pytest.approx(0.038)
    assert read("ops_per_tick.deploy", ctx) == pytest.approx(0.5)


def _sample(t0, tick, n, extra=()):
    """A sample from ``t0`` to ``t0 + 1000`` ns between its markers: an
    ``n``-tick loop of ``fusion.a``/``fusion.b`` (``while.w`` twice a
    tick), one tick every ``tick`` ns from ``t0 + 100``, and ``extra``
    ops; one op of another program lies before the sample."""
    ops = [("copy.1", t0 - 50, t0 - 10)] + list(extra)
    for k in range(n):
        t = t0 + 100 + tick * k
        ops += [("fusion.a", t, t + 40), ("while.w", t + 40, t + 60),
                ("while.w", t + 60, t + 80), ("fusion.b", t + 80, t + 100)]
    return {"spans": [("start", t0, t0), ("cut", t0 + 1000, t0 + 1000)],
            "ops": {"/device:TPU:0": ops}}


def test_loop_ticks_counts_the_body_once_a_tick():
    tr = _sample(100, 120, 4)
    # fusion.a 4, fusion.b 4, while.w 8 (twice a tick); copy.1 once
    assert trace.loop_ticks(tr, "/device:TPU:0", 150, 700) == 4
    assert trace.loop_ticks(tr, "/device:TPU:0", 150, 450) == 2
    assert trace.loop_ticks(tr, "/device:TPU:0", 150, 160) == 0


def _sweep_ctx(samples, labels=None):
    cell = types.SimpleNamespace(name="c", chips=1,
                                 config={"sim": {"steps": 40000}})
    comp = harness.CompileLog()
    comp.spans = list(COMPILE)
    return harness.sampled_context(cell, samples,
                                   labels or ["simulate"] * len(samples),
                                   comp, (0, 1000), 1000)


def test_samples_read_the_ticks_they_hold():
    """Two samples of two programs: 8 ticks of 100 ns busy, and 4 of
    100 ns with a 100 ns op beside the loop; and one of an idle device."""
    idle = {"spans": [("start", 9000, 9000), ("cut", 9500, 9500)],
            "ops": {"/device:TPU:0": [("copy.1", 8000, 8100)]}}
    c = _sweep_ctx([_sample(0, 100, 8),
                    _sample(5000, 200, 4, [("copy.9", 5900, 6000)]), idle],
                   ["simulate", "fetch", "simulate"])
    assert [(s["ticks"], s["ops"]) for s in c["samples"]] == [
        (8, 32), (4, 17), (0, 0)]
    assert [s["busy_s"] for s in c["samples"]] == pytest.approx(
        [800e-9, 500e-9, 0.0])
    assert (c["busy_s"], c["window_s"]) == pytest.approx((1300e-9, 2500e-9))
    assert c["compile_share"] == pytest.approx(0.05)
    assert read("compile_share.sweep", c) == pytest.approx(5.0)
    # (800 + 500) ns over 12 ticks; (32 + 17) ops over 12 ticks
    assert read("tick_us.sweep", c) == pytest.approx(1.3 / 12)
    assert read("ops_per_tick.sweep", c) == pytest.approx(49 / 12)
    assert read("tick_us.deploy", c) is None
    assert read("ops_per_tick.deploy", c) is None
    # gaps: 100 + 100 ns in the first sample, 100 + 3 x 100 + 100 in the
    # second, 500 in the idle one; named by the samples' labels
    gaps = [(n, round(t * 1e9)) for n, t in c["breakdown"]["idle_gaps"]]
    assert gaps == [("simulate", 500)] + [("simulate", 100)] * 2 + [
        ("fetch", 100)] * 5
    assert dict(c["breakdown"]["device_ops"]) == pytest.approx({
        "fusion.a": 480e-9, "while.w": 480e-9, "fusion.b": 240e-9,
        "copy": 100e-9})


def test_samples_without_ticks_read_nothing():
    idle = {"spans": [("start", 0, 0), ("cut", 500, 500)],
            "ops": {"/device:TPU:0": [("copy.1", 100, 200)]}}
    c = _sweep_ctx([idle])
    assert read("tick_us.sweep", c) is None
    assert read("ops_per_tick.sweep", c) is None
    none = _sweep_ctx([])
    assert none["busiest"] is None
    assert read("tick_us.sweep", none) is None


def test_exposed_collectives(ctx):
    # device 0: [500,600) less [550,580) = 70 ns; device 1: 60 ns
    assert read("collective_exposed_share.deploy", ctx) == pytest.approx(6.5)


def test_no_collective_reads_nothing(ctx):
    ops = {d: [o for o in ev if not trace.COLLECTIVE.search(o[0])]
           for d, ev in TRACE["ops"].items()}
    ctx = dict(ctx, trace=dict(TRACE, ops=ops))
    assert read("collective_exposed_share.deploy", ctx) is None


def test_no_device_reads_nothing(ctx):
    bare = dict(TRACE, ops={})
    c = harness.reading_context(types.SimpleNamespace(
        name="c", chips=1, config={"sim": {"steps": 5}}), bare,
        harness.CompileLog(), (0, 1000), 1000, [])
    for name in ("idle_share.deploy", "tick_us.deploy", "ops_per_tick.deploy"):
        assert read(name, c) is None


def test_breakdown(ctx):
    b = ctx["breakdown"]
    assert b["device_ops"][0][0] == "fusion"
    assert b["device_ops"][0][1] == pytest.approx(290e-9)
    gaps = [(n, round(t * 1e9)) for n, t in b["idle_gaps"]]
    assert gaps == [("simulate", 350), ("schedule", 150), ("simulate", 100),
                    ("fetch", 20)]


def test_load_reads_the_harness_spans_from_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones(64)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.simulate"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    tr = trace.load(path)
    assert [n for n, _, _ in tr["spans"]] == ["simulate"]
    lo, hi = trace.window(tr)
    assert hi > lo
    with open(path, "rb") as f, gzip.open(str(tmp_path / "t.gz"), "wb") as g:
        g.write(f.read())
    assert trace.load(str(tmp_path / "t.gz")) == tr


def test_reads_nothing_without_the_counters(monkeypatch):
    from repro.core import obs
    monkeypatch.setattr(obs, "counters", lambda: {})
    assert spec.reader(NAME).read({}) is None
    monkeypatch.setattr(obs, "counters", lambda: {
        "slots.calls": 2, "slots.ticks": 80000})     # a program without them
    assert spec.reader(NAME).read({}) is None


def test_hits_over_lookups(monkeypatch):
    from repro.core import obs
    monkeypatch.setattr(obs, "counters", lambda: {
        "slots.calls": 2, "slots.ticks": 80000,
        "slots.program_lookups": 2, "slots.program_misses": 1})
    assert spec.reader(NAME).read({}) == 50.0
    monkeypatch.setattr(obs, "counters", lambda: {
        "slots.program_lookups": 8, "slots.program_misses": 2})
    assert spec.reader(NAME).read({}) == 75.0


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def test_array_reductions_are_the_interval_lists():
    """``union``, ``clip``, ``busy``, ``idle_gaps`` and ``top_ops``
    (numpy, for traces of tens of millions of ops) against the same
    reductions written over plain interval lists."""
    import re

    import numpy as np
    rng = np.random.default_rng(7)
    s = np.round(rng.uniform(0, 1e6, 4000))
    ev = [(f"fusion.{i % 13}", float(a), float(a + d)) for i, (a, d) in
          enumerate(zip(s, np.round(rng.exponential(300.0, len(s)))))]
    tr = {"ops": {"/device:TPU:0": ev, "/device:TPU:1": ev[::3]},
          "spans": [("schedule", 0.0, 2e5), ("simulate", 2e5, 9e5),
                    ("simulate", 4e5, 5e5), ("fetch", 9e5, 9.5e5)]}
    lo, hi = 1e3, 9.9e5
    for d, e in tr["ops"].items():
        on = _clip(_union((a, b) for _, a, b in e), lo, hi)
        assert trace.clip(trace.union((a, b) for _, a, b in e),
                          lo, hi).tolist() == [list(x) for x in on]
        assert trace.busy(tr, lo, hi)[d] == trace.length(on)
        want = []
        for a, b in trace.subtract([(lo, hi)], on):
            mid = 0.5 * (a + b)
            names = [n for n, x, y in tr["spans"] if x <= mid < y]
            want.append((names[-1] if names else "harness", b - a))
        assert trace.idle_gaps(tr, d, lo, hi) == want
        tot = {}
        for name, a, b in e:
            if min(b, hi) - max(a, lo) > 0:
                k = re.sub(r"\.\d+$", "", name)
                tot[k] = tot.get(k, 0) + min(b, hi) - max(a, lo)
        assert trace.top_ops(tr, d, lo, hi) == sorted(
            tot.items(), key=lambda kv: -kv[1])[:10]
    assert trace.busy({"ops": {"d": []}, "spans": []}, lo, hi) == {"d": 0.0}
    assert trace.union([]).shape == trace.clip([], lo, hi).shape == (0, 2)


def test_profile_samples_the_job_at_even_times(monkeypatch):
    import time
    monkeypatch.setattr(harness, "SAMPLE_S", 0.02)
    monkeypatch.setattr(harness, "SAMPLE_EVERY_S", 0.4)
    prof = harness.Profile(sampled=True)
    prof.begin()
    with prof.span("simulate"):
        time.sleep(1.0)
    with prof.span("fetch"):
        time.sleep(0.3)
    prof.end()
    assert not prof.thread.is_alive()
    assert 2 <= len(prof.paths) <= 4 and len(prof.labels) == len(prof.paths)
    assert "simulate" in prof.labels
    assert set(prof.labels) <= {"harness", "simulate", "fetch"}
    for path in prof.paths:
        tr = trace.load(trace.find_xplane(path))
        assert [n for n, _, _ in sorted(tr["spans"], key=lambda x: x[1])
                if n in ("start", "cut")] == ["start", "cut"]
