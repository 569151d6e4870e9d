"""The benchmark's own description of a configuration's fabric.

From the ``fabric`` group of a configuration file, the fabric kind's
file ``bench/fabrics/<kind>.py`` builds, without the program: host
count, host groups (rack or edge switch) and the load capacity that the
traffic generator needs, and for the plain reference the queues
(capacity, buffer, owning switch, link class) and each flow's path with
its per-hop forward delays and round-trip time.

A kind may also give ``phase_s``, [Q] seconds, NaN where it declares
none: each queue's phase, which a link process with ``"t0_s":
"fabric"`` takes (``reference.build_links``), as a circuit's slot in a
reconfigurable fabric's week.

Queue numbering and ECMP choice follow the published fabrics and the
hash the program documents (a splitmix64 finalizer over seed, source,
destination and flow index, taken modulo the pair's equal-cost path
count, paths in lexicographic link order), so that the reference routes
each flow over the same links as the deployment it checks.
"""
from __future__ import annotations

import os

import numpy as np

from .spec import BENCH, load_module

GBPS = 1e9 / 8.0
US = 1e-6
HOST, TOR, AGG, CORE = 0, 1, 2, 3
TIERS = {"HOST": HOST, "TOR": TOR, "AGG": AGG, "CORE": CORE}


def ecmp_hash(src, dst, flow_id, seed) -> np.ndarray:
    def mix(x):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xbf58476d1ce4e5b9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94d049bb133111eb)
        return x ^ (x >> np.uint64(31))

    with np.errstate(over="ignore"):
        h = mix(np.asarray(seed, np.uint64) ^ np.uint64(0x9e3779b97f4a7c15))
        h = mix(h ^ np.asarray(src, np.uint64))
        h = mix(h ^ np.asarray(dst, np.uint64))
        h = mix(h ^ np.asarray(flow_id, np.uint64))
    return h


def describe(fabric_cfg: dict, bench_dir: str = BENCH):
    """The description of a configuration's ``fabric`` group:
    ``<bench_dir>/fabrics/<kind>.py``'s ``Fabric`` (``bench_dir`` the
    cell's). A later fabric kind is added as a file alone."""
    mod = load_module(os.path.join(bench_dir, "fabrics",
                                   fabric_cfg["kind"] + ".py"))
    return mod.Fabric(fabric_cfg)
