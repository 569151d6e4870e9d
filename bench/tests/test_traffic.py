"""The benchmark's copied generators and its own routing against the
program's, for fixed seeds."""
import json
import os

import numpy as np
import pytest

from bench.lib import fabrics, program, reference, traffic
from bench.lib.spec import BENCH

LS = dict(kind="leaf_spine", racks=8, hosts_per_rack=32, spines=2,
          host_gbps=25, fabric_gbps=100, d_host_us=1, d_fabric_us=5,
          buffer_per_port=6e6, switch_buffer=24e6, dt_alpha=1.0)
FT = dict(kind="fat_tree", k=8, host_gbps=25, fabric_gbps=100, d_host_us=1,
          d_fabric_us=5, buffer_per_port=6e6, switch_buffer=24e6,
          dt_alpha=1.0)


def _fab(desc):
    return dict(n_hosts=desc.n_hosts, group=desc.group,
                load_capacity=desc.load_capacity)


def _program_fabric(cfg):
    from repro.core import LeafSpine, fat_tree
    if cfg["kind"] == "leaf_spine":
        return LeafSpine(racks=8, hosts_per_rack=32, spines=2)
    return fat_tree(cfg["k"])


def test_websearch_cdf_is_the_programs():
    from repro.core import WEBSEARCH_CDF, websearch_mean
    assert np.array_equal(traffic.WEBSEARCH_CDF, WEBSEARCH_CDF)
    assert traffic.websearch_mean() == websearch_mean()


@pytest.mark.parametrize("cfg", [LS, FT], ids=["leaf_spine", "fat_tree"])
@pytest.mark.parametrize("seed", [1, 7, 2**31 - 5])
def test_poisson_websearch_reproduces_the_program(cfg, seed):
    from repro.core import poisson_websearch
    desc = fabrics.describe(cfg)
    g = traffic.poisson_websearch(_fab(desc), 0.6, 0.002, seed)
    fl = poisson_websearch(_program_fabric(cfg), 0.6, 0.002, 1e-6, seed=seed)
    assert np.array_equal(np.float32(g["size"]), np.asarray(fl.size))
    assert np.array_equal(np.float32(g["start"]), np.asarray(fl.start))
    path, tf, rtt = desc.route(g["src"], g["dst"], g["ecmp_seed"])
    assert np.array_equal(path, np.asarray(fl.path))
    assert np.array_equal(np.round(tf / 1e-6), np.asarray(fl.tf_steps))
    assert np.array_equal(np.float32(rtt), np.asarray(fl.tau))


@pytest.mark.parametrize("seed", [5, 123456])
def test_incast_burst_reproduces_the_program(seed):
    from repro.core import incast_burst
    desc = fabrics.describe(FT)
    g = traffic.incast_burst(_fab(desc), 16, 1.5e5, 8, 1e-3, seed,
                             start=1e-4)
    fl, _ = incast_burst(_program_fabric(FT), fan_in=16, req_bytes=1.5e5,
                         n_bursts=8, period=1e-3, sim_dt=1e-6, seed=seed,
                         start=1e-4)
    assert np.array_equal(np.float32(g["start"]), np.asarray(fl.start))
    path, _, _ = desc.route(g["src"], g["dst"], g["ecmp_seed"])
    assert np.array_equal(path, np.asarray(fl.path))


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_websearch60_is_the_programs_paper_workload():
    """The whole timed mix, 30 ms at 60% on the paper's leaf-spine, is
    the program's ``poisson_websearch`` of the same seed."""
    from repro.core import poisson_websearch
    desc = fabrics.describe(LS)
    (g,) = traffic.scenario(_mix("websearch60"), _fab(desc), 99)
    fl = poisson_websearch(_program_fabric(LS), 0.6, 0.03, 1e-6, seed=99)
    assert np.array_equal(np.float32(g["start"]), np.asarray(fl.start))
    assert np.array_equal(np.float32(g["size"]), np.asarray(fl.size))
    path, _, _ = desc.route(g["src"], g["dst"], g["ecmp_seed"])
    assert np.array_equal(path, np.asarray(fl.path))


def test_websearch60_incast_is_the_programs_fabric16_scenario():
    """The fat-tree's mix (85 ms of web-search plus 64 incasts) routed
    and scheduled by the benchmark is the program's headline sharded
    scenario of the same seed."""
    from benchmarks.fabric_fct import fabric16_scenario
    with open(os.path.join(BENCH, "configs", "fattree16.json")) as f:
        cfg = json.load(f)
    dep = program.deploy(cfg)
    desc = fabrics.describe(cfg["fabric"])
    groups = traffic.scenario(_mix("websearch60_incast"), _fab(desc), 5)
    mine = program.schedule(dep, groups, 1e-6)
    _, theirs = fabric16_scenario(seed=5)
    for k in ("start", "size", "path"):
        assert np.array_equal(np.asarray(getattr(mine, k)),
                              np.asarray(getattr(theirs, k))), k


def test_reference_links_match_the_programs_topology_and_impairments():
    from repro.core import LinkProcess, fabric_impairments, fat_tree, netem
    from repro.core.fabric import AGG, CORE
    from repro.core.impair import link_bw_at, link_loss_at
    desc = fabrics.describe(FT)
    imp = {"rules": [{"links": ["AGG", "CORE"], "kind": "oscillate",
                      "bw_lo_gbps": 40, "period_s": 5e-4, "seed": 7}],
           "default": {"kind": "const", "loss": 0.002, "random_loss": True,
                       "seed": 13}}
    L = reference.build_links(desc, imp)
    ft = fat_tree(8)
    topo = ft.topology()
    assert np.array_equal(np.asarray(L.bw), np.asarray(topo.bandwidth))
    assert np.array_equal(np.asarray(L.sw), np.asarray(topo.switch_of_queue))
    p = fabric_impairments(ft, rules={(AGG, CORE): LinkProcess(
        kind="oscillate", bw_lo=40e9 / 8, period=5e-4, seed=7)},
        default=netem(loss=0.002, seed=13))
    for t in (0.0, 1.3e-4, 2.51e-4, 7.77e-4):
        bw, keep = reference.link_state(np.float32(t), L)
        np.testing.assert_allclose(np.asarray(bw),
                                   np.asarray(link_bw_at(t, p)), rtol=1e-6)
        assert np.array_equal(np.asarray(keep),
                              1.0 - np.asarray(link_loss_at(t, p)))
