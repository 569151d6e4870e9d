"""The whole-trace slot engine's program cache (``fluid._slot_program``).

``simulate_slots`` takes the schedule and the [N]-axis ``LawConfig``
leaves as arguments of a cached program, padded to 2**k - 1 for the bit
length k of max(N, S): schedules of one deployment whose flow counts
fall in one bucket reuse one compiled program. Every result must be
bit-identical to the chunk-streamed path (bit-identical to the
single-shot run by contract, and not cached); the fused backend, which
that path rejects, is held to the fused padded engine as in
test_slot_engine.py.
"""
import numpy as np
import pytest

import jax

from repro.core import (GBPS, LeafSpine, SimConfig, default_law_config,
                        make_schedule, obs, schedule_as_flows, simulate,
                        simulate_slots)
from repro.core import fluid

DT = 1e-6
CFG = SimConfig(dt=DT, steps=1024, hist=256, update_period=2e-6)
SIZES = (20, 23, 27)          # three flow counts, one bucket (Np = 31)


@pytest.fixture(autouse=True)
def _cold():
    fluid._slot_programs.clear()
    obs.reset()
    yield
    fluid._slot_programs.clear()
    obs.reset()


def _fabric(host_gbps=25):
    return LeafSpine(racks=2, hosts_per_rack=4, spines=1,
                     host_bw=host_gbps * GBPS)


def _sched(fab, n, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, 8, n)
    dst = (src + 4 + rng.integers(0, 4, n)) % 8      # always the other rack
    sizes = rng.uniform(2e4, 1.5e5, n)
    starts = rng.uniform(0.0, 3e-4, n)
    return make_schedule(fab.make_flows(src, dst, sizes, starts, DT,
                                        seed=seed))


def _lcfg(sched):
    return default_law_config(schedule_as_flows(sched), expected_flows=4.0)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_bitwise(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        assert np.array_equal(x, y, equal_nan=True)


@pytest.mark.parametrize("law", ["powertcp", "hpcc", "fncc"])
def test_one_bucket_one_program_and_the_chunk_stream_bits(law):
    fab = _fabric()
    topo = fab.topology()
    for i, n in enumerate(SIZES):
        sched = _sched(fab, n, seed=i)
        lcfg = _lcfg(sched)
        got = simulate_slots(topo, sched, law, 8, lcfg, CFG)
        c = obs.counters()
        assert c["slots.program_lookups"] == i + 1
        assert c["slots.program_misses"] == 1
        ref = simulate_slots(topo, sched, law, 8, lcfg, CFG, chunk=n)
        _assert_bitwise(got, ref)
        st = got[0]
        assert st.fct.shape == (n,)
        assert np.isfinite(np.asarray(st.fct)).all()
        assert (np.asarray(st.slot_flow) == n).all()     # every slot free
    assert len(fluid._slot_programs) == 1


def test_fused_backend_shares_one_program():
    fab = _fabric()
    topo = fab.topology()
    for i, n in enumerate(SIZES):
        sched = _sched(fab, n, seed=10 + i)
        lcfg = _lcfg(sched)
        st_p, rec_p = simulate(topo, schedule_as_flows(sched), "powertcp",
                               lcfg, CFG, backend="fused")
        st_s, rec_s = simulate_slots(topo, sched, "powertcp", 32, lcfg, CFG,
                                     backend="fused")
        assert obs.counters()["slots.program_misses"] == 1
        assert st_s.fct.shape == (n,)
        np.testing.assert_allclose(np.asarray(st_s.fct),
                                   np.asarray(st_p.fct), rtol=1e-5,
                                   atol=2e-6)
        np.testing.assert_allclose(np.asarray(rec_s.q), np.asarray(rec_p.q),
                                   rtol=1e-4, atol=10.0)
        assert (np.asarray(st_s.slot_flow) == n).all()
    assert obs.counters()["slots.program_lookups"] == len(SIZES)


def test_free_slots_read_n_while_flows_are_in_flight():
    fab = _fabric()
    topo = fab.topology()
    sched = _sched(fab, 23, seed=4)
    cfg = CFG._replace(steps=150)
    st, _ = simulate_slots(topo, sched, "powertcp", 30, _lcfg(sched), cfg,
                           record=False)
    flow = np.asarray(st.slot_flow)
    assert st.fct.shape == (23,)
    assert (flow < 23).any() and (flow == 23).any()
    assert ((flow < 23) | (flow == 23)).all()


def test_equal_shapes_other_bandwidth_is_another_program():
    slow, fast = _fabric(10), _fabric(25)
    sched = _sched(fast, 23, seed=7)
    lcfg = _lcfg(sched)
    runs = []
    for fab in (slow, fast):
        topo = fab.topology()
        got = simulate_slots(topo, sched, "powertcp", 8, lcfg, CFG)
        ref = simulate_slots(topo, sched, "powertcp", 8, lcfg, CFG, chunk=23)
        _assert_bitwise(got, ref)
        runs.append(np.asarray(got[0].fct))
    assert obs.counters()["slots.program_misses"] == 2
    assert not np.array_equal(runs[0], runs[1], equal_nan=True)


def test_the_cache_keeps_its_bound(monkeypatch):
    monkeypatch.setattr(fluid, "_SLOT_PROGRAMS_MAX", 2)
    fab = _fabric()
    topo = fab.topology()
    sched = _sched(fab, 20, seed=1)
    lcfg = _lcfg(sched)
    cfg = CFG._replace(steps=20)

    def run(slots):
        simulate_slots(topo, sched, "powertcp", slots, lcfg, cfg,
                       record=False)
        return obs.counters()["slots.program_misses"]

    assert [run(s) for s in (4, 5, 6)] == [1, 2, 3]
    assert len(fluid._slot_programs) == 2
    assert run(6) == 3                  # the newest stays
    assert run(4) == 4                  # the oldest was dropped
    assert len(fluid._slot_programs) == 2


def test_padding_is_inert_and_repeats_the_last_flow():
    fab = _fabric()
    sched = _sched(fab, 23, seed=2)
    lcfg = _lcfg(sched)
    sim = fluid.SlotSim(fab.topology(), sched, fluid.get_law("powertcp"),
                        lcfg, CFG, 8)
    _, (sched_p, flow, n) = fluid._slot_program(sim, None, False)
    assert int(n) == 23
    start = np.asarray(sched_p.start)
    assert start.shape == (31,) and np.isinf(start[23:]).all()
    assert np.array_equal(start[:23], np.asarray(sched.start))
    assert len(flow) == 3                        # beta, tau, host_bw
    for a, real in zip(flow, (lcfg.beta, lcfg.tau, lcfg.host_bw)):
        real = np.asarray(real)
        assert a.shape == (31,)
        assert np.array_equal(a[:23], real)
        assert (a[23:] == real[-1]).all()
