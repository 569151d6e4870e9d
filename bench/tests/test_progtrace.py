"""The program-side reductions (``bench/lib/progtrace.py``) against
numbers worked out by hand, on a trace of hand-made intervals (ns) with
nested program spans, JAX compile spans and scoped ops inside a
container ``while``; the wire-format reader on a hand-encoded profile;
and both on a small trace recorded on a TPU v5e
(``bench/tests/data``, ``record_trace.py`` on the ``ls256-ws60`` cut
with the profiler's Python tracer off)."""
import json
import os
import types

import pytest

from bench.lib import harness, progtrace, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "ls256-ws60.xplane.pb.gz")
DEV = "/device:TPU:0"

# one job: routing, an init call that compiles, two chunk segments, the
# fetch; the first segment's scan has its own ``while`` event, the
# second's does not (a long scan's may be missing on the chip)
OPS = [  # (phase, start, end, instruction, in an engine program)
    (None, 385, 395, "fusion.1", False),
    (None, 460, 660, "while.9", True),
    ("queue", 470, 520, "fusion.2", True),
    ("halo", 520, 600, "conditional.3", True),
    ("halo", 530, 560, "all-to-all.4", True),
    ("law", 600, 630, "fusion.6", True),
    (None, 630, 640, "copy.7", True),
    ("queue", 740, 780, "fusion.8", True),
    ("law", 780, 800, "fusion.9", True),
    (None, 800, 810, "copy.10", True),
    (None, 950, 960, "copy.11", False),
]
TRACE = {
    "spans": [("schedule", 0, 100), ("simulate", 100, 900),
              ("fetch", 900, 1000)],
    "ops": {DEV: [(i, s, e) for _, s, e, i, _ in OPS]},
}
PROG = {
    "program": [
        ("schedule.route", 10, 60, {"flows": "4"}),
        ("schedule.build", 60, 90, {"flows": "4"}),
        ("slots.prepare", 100, 120, {}),
        ("slots.call", 120, 400, {"program": "init", "ticks": "0"}),
        ("chunk.sync", 400, 420, {}),
        ("chunk.window", 420, 440, {}),
        ("slots.call", 440, 460, {"program": "segment", "ticks": "4"}),
        ("chunk.sync", 460, 700, {}),
        ("chunk.window", 700, 720, {}),
        ("slots.call", 720, 740, {"program": "segment", "ticks": "2"}),
        ("slots.finish", 850, 870, {}),
    ],
    "compile": [("backend_compile_and_load", 30, 50),
                ("trace_to_jaxpr_dynamic", 130, 150),
                ("lower_sharding_computation", 150, 200),
                ("backend_compile_and_load", 200, 380)],
    "ops": {DEV: OPS},
}


def idle_share(tr, wall, elapsed, steps=5):
    cell = types.SimpleNamespace(name="c", chips=1,
                                 config={"sim": {"steps": steps}})
    ctx = harness.reading_context(cell, tr, harness.CompileLog(), wall,
                                  elapsed, [({"points": [{}]}, None)])
    return spec.reader("idle_share.deploy").read(ctx)


def test_idle_split_partitions_idle_share():
    # busy [385,395) [460,660) [740,810) [950,960) = 290 of 1000 ns;
    # compile [30,50) [130,380) = 270; program spans, less compile:
    # [10,30) [50,90) [100,130) [380,385) [395,460) [660,740) [850,870)
    # = 260; the rest [0,10) [90,100) [810,850) [870,950) [960,1000) = 180
    r = progtrace.report(TRACE, PROG)
    assert r["idle_share"] == pytest.approx(71.0)
    assert r["idle_compile_share"] == pytest.approx(27.0)
    assert r["idle_host_share"] == pytest.approx(26.0)
    assert r["idle_unattributed_share"] == pytest.approx(18.0)
    assert (r["idle_compile_share"] + r["idle_host_share"] +
            r["idle_unattributed_share"]) == pytest.approx(
                idle_share(TRACE, (0, 1000), 1000))


def test_calls_compiles_and_segments():
    r = progtrace.report(TRACE, PROG)
    # the compile at [200,380) lies inside slots.call [120,400); the one
    # at [30,50) inside schedule.route does not count
    assert r["compiles_per_job"] == 1.0
    assert r["segments_per_job"] == 2.0
    assert r["ticks"] == 6


def test_phase_self_time():
    # self ns: queue 50+40, halo (80-30)+30, law 30+20, none 10+10; the
    # scan's own while and the ops outside engine programs do not count
    assert progtrace.phase_ns(OPS, 0, 1000) == {
        "queue": 90, "halo": 80, "law": 50, None: 20}
    r = progtrace.report(TRACE, PROG)
    assert r["queue_us"] == pytest.approx(90e-3 / 6)
    assert r["halo_us"] == pytest.approx(80e-3 / 6)
    assert r["law_us"] == pytest.approx(50e-3 / 6)
    assert r["phase_cover"] == pytest.approx(100.0 * 220 / 240)


def test_gaps_named_by_the_innermost_open_span():
    gaps = progtrace.named_gaps(TRACE, PROG, DEV, 0, 1000)
    # the first gap, [0,385), has its midpoint inside the lowering span
    assert gaps == [("lower_sharding_computation", 385),
                    ("chunk.window", 65), ("chunk.window", 80),
                    ("simulate", 140), ("fetch", 40)]


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _bytes(number, value):
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _int(number, value):
    return _varint(number << 3) + _varint(value)


def test_hlo_phases_from_a_hand_encoded_profile():
    ins = [_bytes(1, b"fusion.2") +
           _bytes(7, _bytes(2, b"jit(seg)/while/body/queue/add")),
           _bytes(1, b"copy.7"),
           _bytes(1, b"cond.3") +
           _bytes(7, _bytes(2, b"jit(seg)/law/halo/cond"))]
    comp = _bytes(1, b"body") + b"".join(_bytes(2, i) for i in ins)
    hlo = _bytes(1, _bytes(1, b"jit_seg") + _bytes(3, comp))
    em = (_int(1, 42) + _bytes(2, b"jit_seg(42)") +
          _bytes(5, _int(1, 7) + _bytes(6, hlo)))
    plane = (_bytes(2, b"/host:metadata") +
             _bytes(4, _int(1, 42) + _bytes(2, em)) +
             _bytes(5, _int(1, 7) + _bytes(2, _int(1, 7) +
                                           _bytes(2, b"Hlo Proto"))))
    space = _bytes(1, _bytes(2, b"/device:TPU:0")) + _bytes(1, plane)
    assert progtrace.hlo_phases(space) == {
        "jit_seg(42)": {"fusion.2": "queue", "copy.7": None,
                        "cond.3": "halo"}}


@pytest.fixture(scope="module")
def chip():
    with open(DATA + ".json") as f:
        meta = json.load(f)
    return trace.load(DATA), progtrace.load(DATA), meta


def test_chip_trace_has_the_program_spans(chip):
    tr, prog, meta = chip
    assert list(tr["ops"]) == [DEV]
    names = [n for n, *_ in prog["program"]]
    for n in ("schedule.route", "schedule.build", "slots.prepare",
              "slots.call"):
        assert n in names
    (call,) = [a for n, _, _, a in prog["program"] if n == "slots.call"]
    assert call == {"program": "run", "ticks": str(meta["steps"])}
    assert {n for n, *_ in prog["compile"]} <= set(progtrace.COMPILE)


def test_chip_trace_readings(chip):
    tr, prog, meta = chip
    r = progtrace.report(tr, prog)
    assert r["ticks"] == meta["steps"]
    assert r["segments_per_job"] == 0.0
    lo, hi = trace.window(tr)
    wall = tuple(meta["wall"])
    share = idle_share(tr, wall, meta["elapsed"], meta["steps"])
    assert (r["idle_compile_share"] + r["idle_host_share"] +
            r["idle_unattributed_share"]) == pytest.approx(share)
    assert r["idle_compile_share"] > 0 and r["idle_host_share"] > 0
    # every phase of fluid.slot_step is found, and together they hold
    # at least 90% of the engine program's op self time
    assert {"admit", "rates", "queue", "observe", "law",
            "progress"} <= set(r["phase_us"])
    assert r["phase_cover"] >= 90.0
    assert r["halo_us"] is None                     # no sharded tick
    busy_us = 1e6 * trace.busy(tr, lo, hi)[DEV] * 1e-9 / meta["steps"]
    assert sum(r["phase_us"].values()) <= busy_us
    ctx = harness.reading_context(
        types.SimpleNamespace(name="c", chips=1,
                              config={"sim": {"steps": meta["steps"]}}),
        tr, harness.CompileLog(), wall, meta["elapsed"],
        [({"points": [{}]}, None)])
    assert 50 < spec.reader("tick_us.deploy").read(ctx) < 200
    assert spec.reader("ops_per_tick.deploy").read(ctx) > 100
    names = {n for n, _ in r["idle_gaps"]}
    assert names & {"schedule.route", "schedule.build",
                    "lower_sharding_computation", "trace_to_jaxpr_dynamic",
                    "backend_compile_and_load"}


def test_counter_readers(monkeypatch):
    from repro.core import obs
    obs.reset()
    for name in ("segments_per_job.deploy", "halo_fallback_share.deploy"):
        assert spec.reader(name).read({}) is None     # no entry call yet
    monkeypatch.setattr(obs, "counters", lambda: {
        "slots.calls": 2, "slots.ticks": 20000, "chunk.segments": 30,
        "halo.fallback_ticks": 500})
    assert spec.reader("segments_per_job.deploy").read({}) == 15.0
    assert spec.reader("halo_fallback_share.deploy").read({}) == 2.5
