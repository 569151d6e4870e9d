"""A test fabric kind: ToRs joined pairwise by circuits, as the
benchmark describes it. Copied with ``deploy/tor_mesh.py`` into a copy
of ``bench/`` by the tests, never into the benchmark itself."""
import numpy as np

from bench.lib.fabrics import GBPS, HOST, TOR, US


class Fabric:
    """R ToRs of H hosts; one queue from every ToR to every other,
    ``i*(R-1) + (j if j < i else j-1)``, then host downlinks
    ``R*(R-1) + r*H + h``. The queue ``i -> j`` is on the circuit in
    matching ``(j - i - 1) mod R`` of the week's R-1, each
    ``circuit_slot_us`` long: that is its ``phase_s``."""

    def __init__(self, c: dict):
        R = self.R = c["tors"]
        H = self.H = c["hosts_per_tor"]
        self.host_bw = c["host_gbps"] * GBPS
        self.fabric_bw = c["fabric_gbps"] * GBPS
        self.d_host, self.d_fabric = c["d_host_us"] * US, c["d_fabric_us"] * US
        self.buffer_per_port = c["buffer_per_port"]
        self.switch_buffer, self.dt_alpha = c["switch_buffer"], c["dt_alpha"]
        self.n_hosts = R * H
        self.group = np.arange(self.n_hosts) // H
        self.load_capacity = self.n_hosts * self.host_bw
        P = R * (R - 1)
        self.Q = P + R * H
        self.bandwidth = np.r_[np.full(P, self.fabric_bw),
                               np.full(R * H, self.host_bw)]
        src = np.repeat(np.arange(R), R - 1)
        dst = np.array([j for i in range(R) for j in range(R) if j != i])
        self.switch_of_queue = np.r_[src, np.repeat(np.arange(R), H)]
        self.n_switches = R
        self.link_class = np.r_[np.tile([[TOR, TOR]], (P, 1)),
                                np.tile([[TOR, HOST]], (R * H, 1))]
        self.phase_s = np.r_[(dst - src - 1) % R * (c["circuit_slot_us"] * US),
                             np.full(R * H, np.nan)]
        self.hops = 2

    def route(self, src, dst, seed):
        R, H = self.R, self.H
        r1, r2 = src // H, dst // H
        host_q = R * (R - 1) + dst
        same = r1 == r2
        pair_q = r1 * (R - 1) + np.where(r2 < r1, r2, r2 - 1)
        path = np.full((len(src), 2), self.Q, np.int64)
        path[:, 0] = np.where(same, host_q, pair_q)
        path[:, 1] = np.where(same, self.Q, host_q)
        dh, df = self.d_host, self.d_fabric
        tf = np.zeros((len(src), 2))
        tf[:, 0] = dh
        tf[:, 1] = np.where(same, 0.0, dh + df)
        rtt = np.where(same, 2.0 * (dh + dh), 2.0 * (dh + df + dh))
        return path, tf, rtt
