"""The leaf-spine fabric as the benchmark describes it (the PowerTCP
paper's evaluation fabric is 8 racks x 32 hosts under 2 spines)."""
import numpy as np

from bench.lib.fabrics import AGG, GBPS, HOST, TOR, US, ecmp_hash


class Fabric:
    """R racks of H hosts under S spines. Queues: ToR uplinks
    ``up[r, s] = r*S + s``, spine downlinks ``R*S + s*R + r``, host
    downlinks ``2*R*S + r*H + h``."""

    def __init__(self, c: dict):
        self.R, self.H, self.S = c["racks"], c["hosts_per_rack"], c["spines"]
        self.host_bw = c["host_gbps"] * GBPS
        self.fabric_bw = c["fabric_gbps"] * GBPS
        self.d_host, self.d_fabric = c["d_host_us"] * US, c["d_fabric_us"] * US
        self.buffer_per_port = c["buffer_per_port"]
        self.switch_buffer, self.dt_alpha = c["switch_buffer"], c["dt_alpha"]
        R, H, S = self.R, self.H, self.S
        self.n_hosts = R * H
        self.group = np.arange(self.n_hosts) // H
        self.load_capacity = R * S * self.fabric_bw
        self.Q = 2 * R * S + R * H
        bw = np.r_[np.full(2 * R * S, self.fabric_bw),
                   np.full(R * H, self.host_bw)]
        # switch ids: ToRs 0..R-1, spines R..R+S-1
        up_sw = np.repeat(np.arange(R), S)
        down_sw = R + np.repeat(np.arange(S), R)
        host_sw = np.repeat(np.arange(R), H)
        self.switch_of_queue = np.r_[up_sw, down_sw, host_sw]
        self.n_switches = R + S
        self.link_class = np.r_[np.tile([[TOR, AGG]], (R * S, 1)),
                                np.tile([[AGG, TOR]], (R * S, 1)),
                                np.tile([[TOR, HOST]], (R * H, 1))]
        self.bandwidth = bw
        self.hops = 3

    def route(self, src, dst, seed):
        """(path [n, hops], tf seconds [n, hops], rtt seconds [n]) of the
        flows ``src -> dst`` made by one routing call with ECMP seed
        ``seed``; unused hops hold queue id ``Q``."""
        R, H, S = self.R, self.H, self.S
        n = len(src)
        r1, r2, h2 = src // H, dst // H, dst % H
        spine = (ecmp_hash(src, dst, np.arange(n), seed)
                 % np.uint64(S)).astype(np.int64)
        host_q = 2 * R * S + r2 * H + h2
        same = r1 == r2
        path = np.full((n, self.hops), self.Q, np.int64)
        path[:, 0] = np.where(same, host_q, r1 * S + spine)
        path[:, 1] = np.where(same, self.Q, R * S + spine * R + r2)
        path[:, 2] = np.where(same, self.Q, host_q)
        dh, df = self.d_host, self.d_fabric
        cross = [dh, dh + df, dh + df + df]
        tf = np.zeros((n, self.hops))
        tf[:, 0] = dh
        tf[:, 1] = np.where(same, 0.0, cross[1])
        tf[:, 2] = np.where(same, 0.0, cross[2])
        rtt = np.where(same, 2.0 * (dh + dh),
                       2.0 * (dh + df + df + dh))
        return path, tf, rtt
