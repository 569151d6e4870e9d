"""Circuit schedules: per-link day/night link processes, each queue at
its own phase, in the program and in the plain reference.

A test fabric kind, ``tor_mesh`` (``bench/tests/rdcn/``: 4 ToRs of 4
hosts, one circuit queue per ordered ToR pair, 3 matchings of a 225 us
day and a 20 us night), is placed with its deployment and mixes in a
copy of ``bench/``, where the harness finds it by name. The program's
and the reference's link capacities agree tick by tick; PowerTCP
through the ``simulate_slots`` and ``run_sweep`` entries reads inside
``ls256-ws60``'s limits; circuits put at the wrong phase, and the
bfloat16 control, do not; reTCP (``bench/laws/retcp.py``) agrees with
the program's ``retcp`` where every circuit shares one phase, and where
each flow's circuit is handed to the program as ``LawConfig.sched``
leaves of a flow axis."""
import json
import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench.lib import check, fabrics, harness, program, reference, spec
from bench.tests import readings, small

CONFIG = "tormesh4"
CELLS = {"tormesh-ws": "tormesh_ws", "tormesh-grid": "tormesh_grid"}
SEED = 2**40 + 21


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    """A copy of ``bench/`` with the test fabric kind and its files,
    ``ls256-ws60``'s limits for each test cell, and a law of its own."""
    bench = tmp_path_factory.mktemp("tree") / "bench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copytree(bench / "tests" / "rdcn", bench, dirs_exist_ok=True)
    for name in CELLS:
        shutil.copy(bench / "checks" / "ls256-ws60.json",
                    bench / "checks" / f"{name}.json")
    shutil.copy(bench / "laws" / "retcp.py", bench / "laws" / "copied.py")
    return str(bench)


def _config(bench_dir, **rule):
    with open(os.path.join(bench_dir, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg["impairments"]["rules"][0].update(rule)
    return cfg


def _cell(bench_dir, name, cfg=None):
    with open(os.path.join(small.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    work = dict(name=name, config=CONFIG, traffic=CELLS[name], chips=1,
                why="test")
    return spec.Cell(name, work, cfg or _config(bench_dir), bench, bench_dir)


def test_program_and_reference_links_agree_every_tick(bench_dir):
    from repro.core.impair import link_bw_at, link_loss_at
    cfg = _config(bench_dir)
    desc = fabrics.describe(cfg["fabric"], bench_dir)
    dep = program.deploy(cfg, bench_dir)
    L = reference.build_links(desc, cfg["impairments"])
    assert np.array_equal(np.asarray(L.bw), np.asarray(dep.topo.bandwidth))
    assert np.array_equal(np.asarray(L.sw),
                          np.asarray(dep.topo.switch_of_queue))
    src = np.repeat(np.arange(desc.n_hosts), desc.n_hosts)
    dst = np.tile(np.arange(desc.n_hosts), desc.n_hosts)
    src, dst = src[src != dst], dst[src != dst]
    path, _, rtt = desc.route(src, dst, 3)
    fl = dep.fabric.make_flows(src, dst, np.ones(len(src)),
                               np.zeros(len(src)), 1e-6, seed=3)
    assert np.array_equal(path, np.asarray(fl.path))
    assert np.array_equal(np.float32(rtt), np.asarray(fl.tau))
    dt = cfg["sim"]["dt"]
    n = round(2 * cfg["impairments"]["rules"][0]["period_s"] / dt)
    t = jnp.arange(n).astype(jnp.float32) * jnp.float32(dt)   # 2 weeks
    bw_p = jax.vmap(lambda x: link_bw_at(x, dep.impair))(t)
    keep_p = jax.vmap(lambda x: 1.0 - link_loss_at(x, dep.impair))(t)
    bw_r, keep_r = jax.vmap(lambda x: reference.link_state(x, L))(t)
    assert np.array_equal(np.asarray(bw_p), np.asarray(bw_r))
    assert np.array_equal(np.asarray(keep_p), np.asarray(keep_r))
    # each circuit is up for one day a week, and no two pairs of a ToR
    # at once
    up = np.asarray(bw_r)[:, :int(L.sched.sum())] > 25e9 / 8
    assert (up.sum(axis=0) == 2 * 225).all()
    for i in range(4):
        assert up[:, 3 * i:3 * i + 3].sum(axis=1).max() == 1


def _find(what, bench_dir):
    cfg = _config(bench_dir)
    if what == "fabric":
        return fabrics.describe(cfg["fabric"], bench_dir)
    if what == "deploy":
        return program.deploy(cfg, bench_dir)
    return reference.law_rule("copied", bench_dir)


@pytest.mark.parametrize("what", ["fabric", "deploy", "law"])
def test_plugins_are_found_in_the_cells_tree(bench_dir, what):
    """A fabric kind, its deployment and a law that only the copy holds
    are found through the cell's directory, and not in the tree."""
    assert _find(what, bench_dir) is not None
    with pytest.raises(FileNotFoundError):
        _find(what, spec.BENCH)


@pytest.mark.parametrize("side", ["reference", "program"])
def test_fabric_phases_from_a_kind_without_them_are_refused(side):
    with open(os.path.join(spec.BENCH, "configs", "leafspine256.json")) as f:
        cfg = json.load(f)
    cfg["impairments"] = {"rules": [dict(
        links=["TOR", "AGG"], kind="schedule", period_s=7.35e-4,
        up_s=2.25e-4, bw_lo_gbps=25, t0_s="fabric")]}
    with pytest.raises(ValueError, match="phase"):
        if side == "reference":
            reference.build_links(fabrics.describe(cfg["fabric"]),
                                  cfg["impairments"])
        else:
            program.deploy(cfg)


@pytest.mark.parametrize("name", list(CELLS))
def test_staggered_circuits_are_correct(bench_dir, name):
    line = small.run(_cell(bench_dir, name), SEED)
    assert line["correct"] is True, line["check"]


def _phases_at_zero(t0, up):
    return jnp.zeros_like(t0)


def _phases_a_day_late(t0, up):
    return t0 + up


@pytest.mark.parametrize("name", list(CELLS))
@pytest.mark.parametrize("shift", [_phases_at_zero, _phases_a_day_late])
def test_circuits_at_the_wrong_phase_are_not_correct(monkeypatch, bench_dir,
                                                     name, shift):
    """The program's processes compiled with every circuit up at once, or
    a day late."""
    real = program.deploy

    def deploy(cfg, bench_dir=spec.BENCH):
        dep = real(cfg, bench_dir)
        p = dep.impair
        return dep._replace(impair=p._replace(t0=shift(p.t0, p.up)))

    monkeypatch.setattr(program, "deploy", deploy)
    assert small.run(_cell(bench_dir, name), SEED)["correct"] is False


def test_control_is_refused_on_the_schedule(bench_dir):
    c = _cell(bench_dir, "tormesh-ws")
    with open(os.devnull, "w") as out:
        rows = list(readings.readings(c, [SEED, 5], control=True,
                                      require_tpu=False, out=out))
    assert {r["kind"] for r in rows} == {"program", "control"}
    for r in rows:
        within = all(r[k] <= lim for k, lim in c.check.items())
        assert within == (r["kind"] == "program"), r


def _retcp_gaps(bench_dir, seed, t0_s, per_flow):
    """The program's ``retcp`` against ``bench/laws/retcp.py``: with one
    ``LawConfig.sched`` for the scenario, or with ``sched`` leaves of a
    flow axis, each flow's circuit (the slot engine gathers such leaves
    per slot)."""
    from repro.core import (ScheduleParams, default_law_config,
                            schedule_as_flows, simulate_slots)
    cfg = _config(bench_dir, t0_s=t0_s)
    cfg["law"] = "retcp"
    c = _cell(bench_dir, "tormesh-ws", cfg)
    desc = fabrics.describe(cfg["fabric"], bench_dir)
    fab = dict(n_hosts=desc.n_hosts, group=desc.group,
               load_capacity=desc.load_capacity)
    dep = program.deploy(cfg, bench_dir)
    (pt,) = harness.make_job(c, fab, seed, 0)["points"]
    assert pt["law"] == "retcp"
    sched = program.schedule(dep, pt["groups"], dep.sim.dt)
    rule = cfg["impairments"]["rules"][0]
    n = sched.start.shape[0] if per_flow else ()
    t0 = (np.asarray(dep.impair.t0)[np.asarray(sched.path)[:, 0]]
          if per_flow else t0_s)
    sp = ScheduleParams(
        day=jnp.full(n, rule["up_s"], jnp.float32),
        night=jnp.full(n, rule["period_s"] - rule["up_s"], jnp.float32),
        week=jnp.full(n, rule["period_s"], jnp.float32),
        t0=jnp.asarray(t0, jnp.float32),
        circuit_bw=jnp.full(n, cfg["fabric"]["fabric_gbps"] * fabrics.GBPS,
                            jnp.float32),
        packet_bw=jnp.full(n, rule["bw_lo_gbps"] * fabrics.GBPS, jnp.float32))
    lcfg = default_law_config(schedule_as_flows(sched),
                              **program.law_kwargs(cfg))
    lcfg = lcfg._replace(sched=sp, retcp_prebuffer=6e-4)
    st, _ = simulate_slots(dep.topo, sched, "retcp", cfg["slots"], lcfg,
                           dep.sim, record=False, backend="reference",
                           impair=dep.impair)
    fl, ref = check.reference_run(c, desc, pt)
    assert np.isfinite(ref).sum() > 20
    assert check.pool_peak(fl, np.asarray(st.fct), dep.sim.dt) <= cfg["slots"]
    return check.against(np.asarray(st.fct), ref), c.check


@pytest.mark.parametrize("seed", [SEED, 6])
def test_retcp_agrees_with_the_programs_on_a_shared_phase(bench_dir, seed):
    g, lim = _retcp_gaps(bench_dir, seed, 0.0, per_flow=False)
    assert all(v <= lim[k + ".powertcp"] for k, v in g.items()), g


@pytest.mark.parametrize("seed", [SEED, 6])
def test_retcp_agrees_with_flow_axis_schedules_on_staggered_circuits(
        bench_dir, seed):
    g, lim = _retcp_gaps(bench_dir, seed, "fabric", per_flow=True)
    assert all(v <= lim[k + ".powertcp"] for k, v in g.items()), g
