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

import numpy as np

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
    """Sorted, disjoint union of (start, end) pairs, as an [n, 2] array
    (a window traces tens of millions of ops)."""
    if not isinstance(intervals, np.ndarray):
        intervals = list(intervals)
    a = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0], kind="stable")]
    end = np.maximum.accumulate(a[:, 1])
    first = np.r_[True, a[1:, 0] > end[:-1]]
    return np.stack([a[first, 0], end[np.r_[first[1:], True]]], 1)


def clip(intervals, lo, hi):
    a = np.asarray(intervals, np.float64).reshape(-1, 2).clip(lo, hi)
    return a[a[:, 1] > a[:, 0]]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def op_intervals(events):
    """The ``(start, end)`` of ``(name, start, end)`` events, as an
    [n, 2] array."""
    return np.fromiter((t for _, s, e in events for t in (s, e)),
                       np.float64, 2 * len(events)).reshape(-1, 2)


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


def loop_ticks(tr: dict, device: str, lo, hi) -> int:
    """Iterations of the tick loop that ``device`` ran inside [lo, hi]:
    the median count of the op names that ran there more than once (the
    loop body's ops run once a tick; a tick cut in two counts once)."""
    counts = {}
    for name, s, e in tr["ops"][device]:
        if s >= lo and e <= hi:
            counts[name] = counts.get(name, 0) + 1
    many = sorted(c for c in counts.values() if c > 1)
    return many[(len(many) - 1) // 2] if many else 0


def busy(tr: dict, lo, hi) -> dict:
    """Busy ns of each device inside [lo, hi]: the union of its ops."""
    out = {}
    for d, ev in tr["ops"].items():
        on = clip(union(op_intervals(ev)), lo, hi)
        out[d] = float((on[:, 1] - on[:, 0]).sum())
    return out


def busiest(busy_ns: dict):
    """The device of ``busy``'s result that was busy longest."""
    return max(busy_ns, key=busy_ns.get) if busy_ns else None


def idle_gaps(tr: dict, device: str, lo, hi):
    """(span name, ns) of each gap between ops on ``device`` inside
    [lo, hi], named by the harness span its midpoint falls in."""
    on = clip(union(op_intervals(tr["ops"][device])), lo, hi)
    gs, ge = np.r_[lo, on[:, 1]], np.r_[on[:, 0], hi]
    keep = ge > gs
    gs, ge = gs[keep], ge[keep]
    mid = 0.5 * (gs + ge)
    name = np.full(len(mid), -1)
    for i, (_, a, b) in enumerate(tr["spans"]):
        name[(a <= mid) & (mid < b)] = i
    names = [n for n, _, _ in tr["spans"]] + ["harness"]
    return [(names[i], g) for i, g in zip(name.tolist(), (ge - gs).tolist())]


def top_ops(tr: dict, device: str, lo, hi, n=10):
    """The ``n`` op names with the most device time inside [lo, hi]."""
    per = {}
    for name, s, e in tr["ops"][device]:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            per[name] = per.get(name, 0) + d
    tot = {}
    for name, d in per.items():
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
        if not len(coll):
            continue
        comp = union((s, e) for n, s, e in ev if not COLLECTIVE.search(n))
        shares.append(length(subtract(clip(coll, lo, hi), comp)) / (hi - lo))
    return sum(shares) / len(shares) if shares else None
