"""The k-ary fat-tree as the benchmark describes it."""
import numpy as np

from bench.lib.fabrics import AGG, CORE, GBPS, HOST, TOR, US, ecmp_hash


class Fabric:
    """k-ary fat-tree (Al-Fares et al., SIGCOMM 2008): k pods of k/2 edge
    and k/2 aggregation switches, (k/2)^2 cores, k^3/4 hosts. Queue
    blocks in order: edge->agg, agg->core, core->agg, agg->edge,
    edge->host."""

    def __init__(self, c: dict):
        k = self.k = c["k"]
        h = self.half = k // 2
        self.host_bw = c["host_gbps"] * GBPS
        self.fabric_bw = c["fabric_gbps"] * GBPS
        self.d_host, self.d_fabric = c["d_host_us"] * US, c["d_fabric_us"] * US
        self.buffer_per_port = c["buffer_per_port"]
        self.switch_buffer, self.dt_alpha = c["switch_buffer"], c["dt_alpha"]
        self.n_hosts = k * h * h
        self.group = np.arange(self.n_hosts) // h           # edge switch
        self.load_capacity = min(k * h * h * self.fabric_bw,
                                 self.n_hosts * self.host_bw)
        B = k * h * h                                        # block size
        self.Q = 5 * B
        self.bandwidth = np.r_[np.full(4 * B, self.fabric_bw),
                               np.full(B, self.host_bw)]
        edge = np.arange(k * h)                    # switch ids: edges,
        agg = k * h + np.arange(k * h)             # aggs, then cores
        core = 2 * k * h + np.arange(h * h)
        self.switch_of_queue = np.r_[
            np.repeat(edge, h),                    # edge(pod,e) -> agg a
            np.repeat(agg, h),                     # agg(pod,a) -> core j
            np.repeat(core, k),                    # core c -> pod
            np.repeat(agg, h),                     # agg(pod,a) -> edge e
            np.repeat(edge, h)]                    # edge -> its hosts
        self.n_switches = 2 * k * h + h * h
        cls = [(TOR, AGG), (AGG, CORE), (CORE, AGG), (AGG, TOR), (TOR, HOST)]
        self.link_class = np.concatenate(
            [np.tile([c_], (B, 1)) for c_ in cls])
        self.hops = 5

    def route(self, src, dst, seed):
        k, h = self.k, self.half
        B = k * h * h
        n = len(src)
        ps, es = src // (h * h), (src // h) % h
        pd, ed = dst // (h * h), (dst // h) % h
        same_edge = (ps == pd) & (es == ed)
        same_pod = (ps == pd) & ~same_edge
        npaths = np.where(same_edge, 1, np.where(same_pod, h, h * h))
        choice = (ecmp_hash(src, dst, np.arange(n), seed)
                  % npaths.astype(np.uint64)).astype(np.int64)
        a = np.where(same_pod, choice, choice // h)
        j = choice % h
        c = a * h + j
        e2a = (ps * h + es) * h + a
        a2c = B + (ps * h + a) * h + j
        c2a = 2 * B + c * k + pd
        a2e = 3 * B + (pd * h + a) * h + ed
        e2h = 4 * B + dst
        Q = self.Q
        path = np.full((n, 5), Q, np.int64)
        path[:, 0] = np.where(same_edge, e2h, e2a)
        path[:, 1] = np.where(same_edge, Q, np.where(same_pod, a2e, a2c))
        path[:, 2] = np.where(same_edge, Q, np.where(same_pod, e2h, c2a))
        path[:, 3] = np.where(same_edge | same_pod, Q, a2e)
        path[:, 4] = np.where(same_edge | same_pod, Q, e2h)
        dh, df = self.d_host, self.d_fabric
        cum = [dh]
        for _ in range(4):
            cum.append(cum[-1] + df)
        tf = np.zeros((n, 5))
        tf[:, 0] = dh
        tf[:, 1] = np.where(same_edge, 0.0, cum[1])
        tf[:, 2] = np.where(same_edge, 0.0, cum[2])
        tf[:, 3] = np.where(same_edge | same_pod, 0.0, cum[3])
        tf[:, 4] = np.where(same_edge | same_pod, 0.0, cum[4])
        last = np.where(same_edge, dh, np.where(same_pod, cum[2], cum[4]))
        rtt = 2.0 * (last + dh)
        return path, tf, rtt
