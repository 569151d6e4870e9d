"""Reading a JAX profiler trace into the intervals the metrics use.

``load`` turns one ``.xplane.pb`` into plain lists on one clock (ns):

  ops[device]     (name, start, end) of every operation on the device's
                  ``XLA Ops`` line
  spans           (name, start, end) of the harness's own
                  ``TraceAnnotation`` spans (names ``bench.<what>``)

The reductions below work on those lists only, so a test can feed them
a recorded trace or hand-made intervals alike.
"""
from __future__ import annotations

import glob
import gzip
import os
import re

COLLECTIVE = re.compile(r"all-to-all|all-reduce|all-gather|collective-permute"
                        r"|reduce-scatter|psum", re.I)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """The intervals of the ``.xplane.pb`` (or gzipped ``.xplane.pb.gz``)
    at ``path``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name[len("bench."):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"ops": {k: v for k, v in ops.items() if v}, "spans": spans}


def union(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Union ``a`` minus union ``b`` (both disjoint and sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window(tr: dict):
    """The traced window: from the first harness span's start to the last
    one's end (the window's work, host and device, lies inside)."""
    if not tr["spans"]:
        return None
    return (min(s for _, s, _ in tr["spans"]),
            max(e for _, _, e in tr["spans"]))


def busy(tr: dict, lo, hi) -> dict:
    """Busy ns of each device inside [lo, hi]: the union of its ops."""
    return {d: length(clip(union((s, e) for _, s, e in ev), lo, hi))
            for d, ev in tr["ops"].items()}


def busiest(tr: dict, lo, hi):
    b = busy(tr, lo, hi)
    return max(b, key=b.get) if b else None


def idle_gaps(tr: dict, device: str, lo, hi):
    """(span name, ns) of each gap between ops on ``device`` inside
    [lo, hi], named by the harness span its midpoint falls in."""
    on = clip(union((s, e) for _, s, e in tr["ops"][device]), lo, hi)
    gaps = subtract([(lo, hi)], on)
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        names = [n for n, a, b in tr["spans"] if a <= mid < b]
        out.append((names[-1] if names else "harness", e - s))
    return out


def top_ops(tr: dict, device: str, lo, hi, n=10):
    """The ``n`` op names with the most device time inside [lo, hi]."""
    tot = {}
    for name, s, e in tr["ops"][device]:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = re.sub(r"\.\d+$", "", name)
            tot[key] = tot.get(key, 0) + d
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def exposed_collective_share(tr: dict, lo, hi):
    """Mean over devices of the share of [lo, hi] in which a collective
    runs with no other operation running beside it; None if the trace
    holds no collective."""
    shares = []
    for d, ev in tr["ops"].items():
        coll = union((s, e) for n, s, e in ev if COLLECTIVE.search(n))
        if not coll:
            continue
        comp = union((s, e) for n, s, e in ev if not COLLECTIVE.search(n))
        shares.append(length(subtract(clip(coll, lo, hi), comp)) / (hi - lo))
    return sum(shares) / len(shares) if shares else None
