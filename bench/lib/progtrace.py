"""The program's own spans, JAX's compile spans and the device phases of
a JAX profile, and the reductions that read them.

``load`` reads one ``.xplane.pb`` (or ``.xplane.pb.gz``) into plain lists
on the trace clock (ns), beside what ``trace.load`` gives:

  program   (name, start, end, args) of each ``repro.<name>`` host span
            (``core/obs.py``), ``args`` its stats as strings
  compile   (name, start, end) of JAX's own trace, lowering and compile
            spans (``COMPILE``)
  ops       {device: [(phase, start, end, instruction, engine)]} every
            op of the device's ``XLA Ops`` line, ``phase`` the innermost
            ``jax.named_scope`` of ``PHASES`` in the op's HLO metadata
            (None outside them), ``engine`` whether its module holds such
            scopes (a slot engine's program)

The device events carry no metadata of their own: each op is matched to
its module by the device's ``XLA Modules`` line, and its scope comes from
the HLO protos the profiler keeps in the ``/host:metadata`` plane. Those
are read from the raw protobuf (a few lines of wire format below), since
``ProfileData`` does not expose them.

Phase times use self time: an op's duration less the ops nested inside
it, since ``while`` and ``conditional`` events enclose their bodies (a
long scan's own ``while`` event may be missing from the device line).
"""
from __future__ import annotations

import gzip
import re

from . import trace

PHASES = ("admit", "rates", "queue", "observe", "law", "progress", "halo")
COMPILE = ("trace_to_jaxpr_dynamic", "lower_sharding_computation",
           "backend_compile_and_load")
_INSTR = re.compile(r"%?([\w.\-]+)")


# -- protobuf wire format: just enough to reach the HLO op names ----------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message; values of wire type 2 stay
    memoryview slices, so skipping a large field copies nothing."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not expected")
        yield key >> 3, v


def _first(buf, number):
    return next((v for f, v in _fields(buf) if f == number), None)


def _phase(op_name: str):
    parts = [p for p in op_name.split("/") if p in PHASES]
    return parts[-1] if parts else None


def hlo_phases(raw: bytes) -> dict:
    """{module name as on the ``XLA Modules`` line: {instruction: phase}}
    from the HLO protos of the ``/host:metadata`` plane.

    XSpace.planes=1; XPlane.name=2, .event_metadata=4 (map entry
    value=2), .stat_metadata=5; XEventMetadata.name=2, .stats=5;
    XStat.metadata_id=1, .bytes_value=6; HloProto.hlo_module=1;
    HloModuleProto.computations=3; HloComputationProto.instructions=2;
    HloInstructionProto.name=1, .metadata=7; OpMetadata.op_name=2."""
    out = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1 or bytes(_first(plane, 2) or b"") != b"/host:metadata":
            continue
        hlo_id = None
        for g, entry in _fields(plane):
            if g == 5:
                md = _first(entry, 2)
                if bytes(_first(md, 2) or b"") == b"Hlo Proto":
                    hlo_id = _first(md, 1)
        for g, entry in _fields(plane):
            if g != 4:
                continue
            em = _first(entry, 2)
            name = bytes(_first(em, 2) or b"").decode()
            for h, stat in _fields(em):
                if h == 5 and _first(stat, 1) == hlo_id:
                    out[name] = _instruction_phases(_first(stat, 6))
    return out


def _instruction_phases(hlo) -> dict:
    phases = {}
    module = _first(hlo, 1)
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g != 2:
                continue
            name = meta = None
            for h, v in _fields(ins):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    meta = v
            op = _first(meta, 2) if meta is not None else None
            phases[name] = _phase(bytes(op).decode()) if op else None
    return phases


# -- loading ----------------------------------------------------------------

def load(path: str) -> dict:
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        raw = f.read()
    return read(ProfileData.from_serialized_xspace(raw), raw)


def read(pd, raw: bytes) -> dict:
    """``load`` of a parsed ``ProfileData`` and the bytes it came from."""
    phases = hlo_phases(raw)
    engine = {m for m, ph in phases.items() if any(ph.values())}
    program, compile_, ops = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            mods, evs = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
                elif line.name == "XLA Ops":
                    evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
            if evs:
                ops[plane.name] = _assign(evs, sorted(mods), phases, engine)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    n = e.name
                    if n.startswith("repro."):
                        program.append((n[len("repro."):], e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        {k: str(v) for k, v in e.stats}))
                    elif n in COMPILE:
                        compile_.append((n, e.start_ns,
                                         e.start_ns + e.duration_ns))
    return {"program": program, "compile": compile_, "ops": ops}


def _assign(evs, mods, phases, engine):
    """Each op as (phase, start, end, instruction, in an engine program):
    its module is the ``XLA Modules`` event it starts in, its phase what
    that module's HLO gives the instruction; an engine program is a
    module with phase scopes (a slot engine's scan)."""
    out, j = [], 0
    for s, e, name in sorted(evs):
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        mod = mods[j][2] if j < len(mods) and mods[j][0] <= s else None
        m = _INSTR.match(name)
        instr = m.group(1) if m else name
        out.append((phases.get(mod, {}).get(instr), s, e, instr,
                    mod in engine))
    return out


# -- reductions ---------------------------------------------------------------

def self_times(ops, lo, hi):
    """(phase, self ns, engine, instruction) of each op starting inside
    [lo, hi]: its duration less the ops nested in it."""
    evs = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [o[2] - o[1] for o in evs]
    stack = []                                   # indices of open ops
    for i, o in enumerate(evs):
        while stack and evs[stack[-1]][2] <= o[1]:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(o[2], evs[p][2]) - o[1]
        stack.append(i)
    return [(o[0], own[i], o[4], o[3]) for i, o in enumerate(evs)
            if lo <= o[1] < hi]


def _engine_ops(ops, lo, hi):
    """(phase, self ns, instruction) of the engine programs' ops. The
    scan's own ``while`` (no phase) is left out: its self time is the
    gaps between the ops of its body."""
    return [(p, ns, i) for p, ns, engine, i in self_times(ops, lo, hi)
            if engine and not (p is None and i.startswith("while"))]


def _total(pairs) -> dict:
    tot = {}
    for k, ns in pairs:
        tot[k] = tot.get(k, 0) + ns
    return tot


def phase_ns(ops, lo, hi) -> dict:
    """Self ns of the engine programs' ops per phase (None: outside every
    phase scope)."""
    return _total((p, ns) for p, ns, _ in _engine_ops(ops, lo, hi))


def _calls(prog: dict, lo, hi):
    """The ``slots.call`` spans starting inside [lo, hi]."""
    return [p for p in prog["program"] if p[0] == "slots.call" and
            lo <= p[1] < hi]


def _jobs(tr: dict, lo, hi) -> int:
    """The harness's jobs (``simulate`` spans) inside [lo, hi]."""
    return sum(1 for n, s, _ in tr["spans"] if n == "simulate" and
               lo <= s < hi)


def idle_split(tr: dict, prog: dict, device: str, lo, hi) -> dict:
    """The busiest device's idle ns inside [lo, hi], split three ways:
    under a JAX trace/lower/compile span, under a program span and not a
    compile one, and the rest (harness spans only, or none)."""
    on = trace.clip(trace.union((s, e) for _, s, e in tr["ops"][device]),
                    lo, hi)
    idle = trace.subtract([(lo, hi)], on)
    comp = trace.union((s, e) for _, s, e in prog["compile"])
    host = trace.union((s, e) for _, s, e, _ in prog["program"])
    idle_comp = trace.subtract(idle, trace.subtract(idle, comp))
    rest = trace.subtract(idle, comp)
    idle_host = trace.subtract(rest, trace.subtract(rest, host))
    total = trace.length(idle)
    c, h = trace.length(idle_comp), trace.length(idle_host)
    return {"idle": total, "compile": c, "host": h,
            "unattributed": total - c - h}


def named_gaps(tr: dict, prog: dict, device: str, lo, hi):
    """(span, ns) of each idle gap on ``device`` inside [lo, hi], named by
    the innermost span open at its midpoint: harness, program or JAX
    compile span."""
    spans = ([(n, s, e) for n, s, e in tr["spans"]] +
             [(n, s, e) for n, s, e, _ in prog["program"]] +
             list(prog["compile"]))
    on = trace.clip(trace.union((s, e) for _, s, e in tr["ops"][device]),
                    lo, hi)
    out = []
    for s, e in trace.subtract([(lo, hi)], on):
        mid = 0.5 * (s + e)
        open_ = [(a, n) for n, a, b in spans if a <= mid < b]
        out.append((max(open_)[1] if open_ else "harness", e - s))
    return out


def compiles_per_job(tr: dict, prog: dict, lo, hi):
    """``backend_compile_and_load`` spans inside a ``slots.call`` span,
    per job (harness ``simulate`` span) of the window."""
    calls = [(s, e) for _, s, e, _ in _calls(prog, lo, hi)]
    jobs = _jobs(tr, lo, hi)
    if not jobs:
        return None
    n = sum(1 for name, s, e in prog["compile"]
            if name == "backend_compile_and_load" and
            any(a <= s and e <= b for a, b in calls))
    return n / jobs


def report(tr: dict, prog: dict) -> dict:
    """The program-side readings of a traced window (the ``tracing``
    metrics of PERF.md section 3), and what backs them."""
    win = trace.window(tr)
    if win is None or not tr["ops"]:
        return {}
    lo, hi = win
    dev = trace.busiest(trace.busy(tr, lo, hi))
    calls = _calls(prog, lo, hi)
    ticks = sum(int(a.get("ticks", 0)) for *_, a in calls)
    jobs = _jobs(tr, lo, hi)
    split = idle_split(tr, prog, dev, lo, hi)
    eng_ops = _engine_ops(prog["ops"].get(dev, []), lo, hi)
    ph = _total((p, ns) for p, ns, _ in eng_ops)
    # what the scopes miss, by op kind (XLA may drop the metadata of an
    # op it rewrites, such as a collective it turns into another)
    miss = _total((re.sub(r"\.\d+$", "", i), ns)
                  for p, ns, i in eng_ops if p is None)
    eng = sum(ph.values())
    win_ns = hi - lo
    out = {
        "window_s": win_ns * 1e-9,
        "idle_share": 100.0 * split["idle"] / win_ns,
        "idle_compile_share": 100.0 * split["compile"] / win_ns,
        "idle_host_share": 100.0 * split["host"] / win_ns,
        "idle_unattributed_share": 100.0 * split["unattributed"] / win_ns,
        "compiles_per_job": compiles_per_job(tr, prog, lo, hi),
        "segments_per_job": (sum(1 for *_, a in calls
                                 if a.get("program") == "segment") / jobs
                             if jobs else None),
        "ticks": ticks,
        "engine_self_s": eng * 1e-9,
        "phase_cover": (100.0 * (eng - ph.get(None, 0)) / eng
                        if eng else None),
        "phase_us": ({p or "none": 1e-3 * ns / ticks
                      for p, ns in sorted(ph.items(), key=lambda kv: -kv[1])}
                     if ticks else {}),
        "unscoped_us": ({k: 1e-3 * ns / ticks for k, ns in
                         sorted(miss.items(), key=lambda kv: -kv[1])[:8]}
                        if ticks else {}),
        "idle_gaps": [[n, t * 1e-9] for n, t in
                      sorted(named_gaps(tr, prog, dev, lo, hi),
                             key=lambda g: -g[1])[:12]],
    }
    for p in ("queue", "law", "halo"):
        out[p + "_us"] = out["phase_us"].get(p)
    return out


if __name__ == "__main__":
    import json
    import sys
    path = sys.argv[1]
    print(json.dumps(report(trace.load(path), load(path)), indent=1))
