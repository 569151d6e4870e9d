"""Every per-layer reader against numbers worked out by hand, on a small
trace of hand-made intervals (ns) with the shape the profiler gives:
two devices, the harness's spans, overlapping ops and collectives."""
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

