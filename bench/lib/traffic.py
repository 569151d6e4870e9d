"""The benchmark's one traffic generator.

A traffic mix is a JSON file under ``bench/traffic/``; this module reads
its parameters and draws, from a seed, the flow arrays of one scenario:
source host, destination host, size in bytes and start time in seconds,
in groups that each carry their own ECMP seed (the program hashes a
flow's path from its index inside the group and that seed).

The arithmetic is a copy of the program's ``core/workload.py`` as of the
commit that added the benchmark (web-search CDF, Poisson arrivals,
rotating incast bursts), kept here so that later changes to the program
cannot change the yardstick. ``tests/test_traffic.py`` holds the copy to
the program's arrays. Arrivals are a Poisson process, as the program
draws them, so the number of flows varies from scenario to scenario.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

KB, MB = 1e3, 1e6
GBPS = 1e9 / 8.0

# (size_bytes, cdf) anchors of the web-search flow-size distribution
# (Alizadeh et al., DCTCP, SIGCOMM 2010), as core/workload.py has them
WEBSEARCH_CDF = np.array([
    (6 * KB, 0.00), (10 * KB, 0.15), (13 * KB, 0.20), (19 * KB, 0.30),
    (33 * KB, 0.40), (53 * KB, 0.53), (133 * KB, 0.60), (667 * KB, 0.70),
    (1.333 * MB, 0.80), (4 * MB, 0.90), (10 * MB, 0.97), (30 * MB, 1.00),
], dtype=np.float64)


def websearch_mean() -> float:
    s, c = WEBSEARCH_CDF[:, 0], WEBSEARCH_CDF[:, 1]
    return float(np.sum(0.5 * (s[1:] + s[:-1]) * np.diff(c)))


def websearch_sample(rng: np.random.Generator, n: int) -> np.ndarray:
    """Inverse-CDF draw, log-linear between anchors."""
    u = rng.uniform(0.0, 1.0, size=n)
    s, c = WEBSEARCH_CDF[:, 0], WEBSEARCH_CDF[:, 1]
    return np.exp(np.interp(u, c, np.log(s))).astype(np.float64)


def poisson_websearch(fab: dict, load: float, duration: float,
                      seed: int) -> dict:
    """Web-search flows arriving at ``load`` times the fabric's load
    capacity, between distinct hosts of different groups (racks or edge
    switches), as ``core.workload.poisson_websearch`` draws them."""
    rng = np.random.default_rng(seed)
    lam = load * fab["load_capacity"] / websearch_mean()   # flows per second
    n = max(int(lam * duration * 1.2) + 16, 16)
    starts = np.cumsum(rng.exponential(1.0 / lam, size=n))
    starts = starts[starts < duration]
    n = len(starts)
    sizes = websearch_sample(rng, n)
    nh = fab["n_hosts"]
    src = rng.integers(0, nh, size=n)
    dst = rng.integers(0, nh, size=n)
    grp = fab["group"]
    for _ in range(8):                  # re-draw same-group destinations
        same = grp[src] == grp[dst]
        if not same.any():
            break
        dst[same] = rng.integers(0, nh, size=int(same.sum()))
    dst = np.where(dst == src, (dst + 1) % nh, dst)
    return dict(src=src, dst=dst, size=sizes, start=starts, ecmp_seed=seed)


def incast_burst(fab: dict, fan_in: int, req_bytes: float, n_bursts: int,
                 period: float, seed: int, start: float = 0.0) -> dict:
    """Synchronized bursts: burst k fires at ``start + k * period``, and
    ``fan_in`` senders outside the victim's group each send ``req_bytes``
    to a victim that rotates round-robin over the hosts, as
    ``core.workload.incast_burst`` draws them."""
    rng = np.random.default_rng(seed)
    grp = fab["group"]
    nh = fab["n_hosts"]
    src_l, dst_l, sz_l, st_l = [], [], [], []
    for k in range(n_bursts):
        victim = int((k * max(nh // max(n_bursts, 1), 1)) % nh)
        others = np.nonzero(grp != grp[victim])[0]
        src_l.append(rng.choice(others, size=fan_in,
                                replace=fan_in > len(others)))
        dst_l.append(np.full(fan_in, victim))
        sz_l.append(np.full(fan_in, req_bytes))
        st_l.append(np.full(fan_in, start + k * period))
    return dict(src=np.concatenate(src_l).astype(np.int64),
                dst=np.concatenate(dst_l).astype(np.int64),
                size=np.concatenate(sz_l), start=np.concatenate(st_l),
                ecmp_seed=seed)


def scenario(mix: dict, fab: dict, seed: int) -> List[Dict]:
    """The flow groups of one scenario of ``mix`` drawn from ``seed``.

    Each entry of ``mix["components"]`` is one generator call; component
    ``i`` draws from ``seed + i``, the program's convention for a
    web-search trace with incast bursts on top (``fabric16_scenario``)."""
    out = []
    for i, c in enumerate(mix["components"]):
        s = int(seed) + i
        if c["generator"] == "poisson_websearch":
            out.append(poisson_websearch(fab, c["load"], mix["duration_s"],
                                         s))
        elif c["generator"] == "incast_burst":
            n_b = int(c["n_bursts"])
            out.append(incast_burst(
                fab, int(c["fan_in"]), float(c["req_bytes"]), n_b,
                mix["duration_s"] / n_b, s, start=float(c["start_s"])))
        else:
            raise ValueError(f"unknown generator {c['generator']!r}")
    return out


def at_load(mix: dict, load: float) -> dict:
    """``mix`` with ``load`` in place of its ``poisson_websearch``
    components' load (a load-sweep grid's point)."""
    comps = [dict(c, load=load) if c["generator"] == "poisson_websearch"
             else c for c in mix["components"]]
    return dict(mix, components=comps)


def scenario_seeds(seed: int, job: int, n: int) -> np.ndarray:
    """The ``n`` traffic seeds of job ``job`` of the stream a run's
    ``--seed`` gives (any non-negative integer, also beyond 32 bits).
    Each is below 2**31 so that ``seed + i`` of a scenario's components
    stays a valid seed for both numpy and the program's ECMP hash."""
    ss = np.random.SeedSequence([int(seed), int(job)])
    out = ss.generate_state(n, dtype=np.uint32) >> np.uint32(1)
    return out.astype(np.int64)
