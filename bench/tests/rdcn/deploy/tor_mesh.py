"""The program's build of the test fabric kind ``tor_mesh``
(``fabrics/tor_mesh.py`` beside it): ``build(f, links)`` and the
circuits' phases in the program's queue order."""
import numpy as np

from repro.core import FabricBuilder, compile_routes
from repro.core.fabric import TOR


def build(f: dict, links: dict):
    R, H = f["tors"], f["hosts_per_tor"]
    b = FabricBuilder("tor_mesh", dt_alpha=links["dt_alpha"])
    hosts = [[b.add_host() for _ in range(H)] for _ in range(R)]
    tors = [b.add_switch(TOR, links["switch_buffer"]) for _ in range(R)]
    for i in range(R):
        for j in range(R):
            if j != i:
                b.add_link(tors[i], tors[j], links["fabric_bw"],
                           links["d_fabric"], queued=True,
                           buffer=links["buffer_per_port"])
    for r in range(R):
        for h in range(H):
            b.add_link(tors[r], hosts[r][h], links["host_bw"],
                       links["d_host"], queued=True,
                       buffer=links["buffer_per_port"])
    for r in range(R):
        for h in range(H):
            b.add_link(hosts[r][h], tors[r], links["host_bw"],
                       links["d_host"], queued=False)
    routes = compile_routes(b.build())
    return routes, routes


def phases(f: dict, fabric) -> np.ndarray:
    """[Q] seconds: the ToR pair ``i -> j`` is on the circuit in
    matching ``(j - i - 1) mod R``; NaN for the host downlinks."""
    ql = fabric.queued_links()
    src = fabric.link_src[ql] - fabric.n_hosts
    dst = fabric.link_dst[ql] - fabric.n_hosts
    pair = (fabric.tier[fabric.link_src[ql]] == TOR) & \
        (fabric.tier[fabric.link_dst[ql]] == TOR)
    slot = (dst - src - 1) % f["tors"] * (f["circuit_slot_us"] * 1e-6)
    return np.where(pair, slot, np.nan)
