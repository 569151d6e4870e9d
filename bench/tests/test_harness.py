"""The harness finds what is added as files alone, refuses to run
without a chip, expands grid jobs, draws the checked points, and the
control comes out as not correct."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench.lib import (check, fabrics, harness, program, reference,
                       spec, traffic)
from bench.tests import readings, small

ROOT = small.ROOT


def test_added_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    (bench / "traffic" / "websearch30.json").write_text(json.dumps(dict(
        kind="scenario", duration_s=0.001,
        components=[dict(generator="poisson_websearch", load=0.3)])))
    (bench / "checks" / "ls256-ws30.json").write_text(json.dumps({
        "fct_gap_max.powertcp": 0.1}))
    (bench / "metrics" / "answer.deploy.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["workloads"].append(dict(name="ls256-ws30", config="leafspine256",
                               traffic="websearch30", chips=1, why="t"))
    b["end_to_end"][0]["workloads"].append("ls256-ws30")
    b["per_layer"].append(dict(name="answer.deploy", unit="ops",
                               better="lower", source="program_counter",
                               layer="host", moves="sim_ticks_per_s",
                               workloads=["ls256-ws30"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    shutil.copytree(os.path.join(ROOT, "bench", "configs"),
                    tmp_path / "bench" / "configs", dirs_exist_ok=True)
    cell = spec.Cell.named(str(tmp_path), "ls256-ws30", bench_dir=str(bench))
    assert cell.traffic["components"][0]["load"] == 0.3
    assert cell.check["fct_gap_max.powertcp"] == 0.1
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == ["answer.deploy"]
    assert cell.load_module("metrics", "answer.deploy").read({}) == 42.0
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "sim_ticks_per_s", "setup_s"]
    entry = cell.load_module("entries", cell.config["entry"])
    assert callable(entry.run)


def _fab(cell):
    d = fabrics.describe(cell.config["fabric"])
    return dict(n_hosts=d.n_hosts, group=d.group,
                load_capacity=d.load_capacity)


def test_grid_job_is_laws_by_loads_by_seeds():
    c = spec.Cell.named(ROOT, "ls256-fig7-sweep")
    grid = c.traffic["grid"]
    grid["seeds_per_load"] = 2          # in memory: two seeds to order
    fab = _fab(c)
    seed, j = 2**40 + 11, 3
    pts = harness.make_job(c, fab, seed, j)["points"]
    n = grid["seeds_per_load"]
    seeds = traffic.scenario_seeds(seed, j, len(grid["loads"]) * n)
    want = [(law, load, int(s)) for law in grid["laws"]
            for load, s in zip([x for x in grid["loads"] for _ in range(n)],
                               seeds)]
    assert [(p["law"], p["load"], p["scenario"]) for p in pts] == want
    for p in pts[:len(seeds)]:          # the first law's: each load drawn
        (g,) = p["groups"]
        ref = traffic.poisson_websearch(fab, p["load"], c.traffic["duration_s"],
                                        p["scenario"])
        for k in ("src", "dst", "size", "start"):
            assert np.array_equal(g[k], ref[k]), k
    by_law = {}
    for p in pts:
        by_law.setdefault(p["law"], []).append(p)
    first = by_law[grid["laws"][0]]
    for law in grid["laws"][1:]:
        for a, b in zip(first, by_law[law]):
            assert a["scenario"] == b["scenario"]
            assert all(np.array_equal(a["groups"][0][k], b["groups"][0][k])
                       for k in ("src", "dst", "size", "start"))


def _job_hash(job) -> str:
    h = hashlib.sha256()
    for p in job["points"]:
        h.update(json.dumps([p["law"], p["scenario"]]).encode())
        for g in p["groups"]:
            for k in sorted(g):
                v = g[k]
                if hasattr(v, "tobytes"):
                    h.update(k.encode())
                    h.update(str(v.dtype).encode())
                    h.update(v.tobytes())
                else:
                    h.update(json.dumps([k, v]).encode())
    return h.hexdigest()


# jobs of seed 2**40 + 7 as the harness drew them before grid jobs
PARENT_JOBS = {
    ("ls256-ws60", 0):
        "44b4fdd58a754b777937513e898065eaeecdcd2ca44b09efd709ebf155078709",
    ("ls256-ws60", harness.WARM):
        "b7b8d4a5c84bfbd3f59c4835a0b2b1001b8fae61d0e237dd39f3f9b8dc84c6f0",
    ("ft16-degraded", 0):
        "f00abea53d5bde38e2557984d84c38ad69a8d686864c1928db0caec2749c4c2b",
    ("ft16-degraded", harness.WARM):
        "783ef6d365a81fe3b889cb902b6f0d6b852ea543cc673c5cae8dc0cf9db1e22f",
}


@pytest.mark.parametrize("name, j", list(PARENT_JOBS))
def test_one_point_jobs_are_unchanged(name, j):
    c = spec.Cell.named(ROOT, name)
    job = harness.make_job(c, _fab(c), 2**40 + 7, j)
    assert [sorted(p) for p in job["points"]] == [["groups", "law",
                                                    "scenario"]]
    assert _job_hash(job) == PARENT_JOBS[(name, j)]


def _leaves_hash(nt, fields) -> str:
    h = hashlib.sha256()
    for k in fields:
        a = np.asarray(getattr(nt, k))
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# the reference's Links (the fields it had before circuit schedules) and
# the program's ImpairmentParams of each configuration, and the
# reference FCTs of the first point of each law of job 0 of seed
# 2**40 + 7 at each cell's CPU cut, as the harness built them before
# circuit schedules
PARENT_LINK_FIELDS = ("bw", "buf", "sw", "osc", "bw_lo", "period", "loss",
                      "loss_random", "seed")
LS_LINKS = "a74cc1cf23ac3763bd68379f0c75fe9898b5724b673c1a4549044036d823d582"
PARENT_DEPLOYMENTS = {
    "ls256-ws60": (LS_LINKS, None),
    "ft16-degraded": (
        "06b081042c0eb8e41e04c293d4fa7f6d2936c07ed68cb22ed8d614b1ca12a6c1",
        "47f136b2cf022aabcfb299f7b241e1c044facc179f04784c581fdc0a2f25a343"),
    "ls256-fig7-sweep": (LS_LINKS, None),
}
PARENT_REFERENCE_FCTS = {
    "ls256-ws60": [
        ("powertcp", "06593e04e1406867e099c4e49dadb787"
                     "f891d13a2ed74f24059c6388d8d7536e")],
    "ft16-degraded": [
        ("powertcp", "3e93a047e9241d743d9f3976a99a2c36"
                     "c78cc21ef42c22d8e577f001a503bf54")],
    "ls256-fig7-sweep": [
        ("powertcp", "ad2a2947327e5744f893d9c84027b713"
                     "e8b3024685002db71370cd057ef862e2"),
        ("hpcc", "b62c1233b2151611ced89385c167611f"
                 "09abee51c3e72444836d98991a4ace29"),
        ("timely", "fb9c8759e6ff93b84bc052379773c2be"
                   "cf8c98cafab4af0f303c5719a973b544")],
}


@pytest.mark.parametrize("name", list(PARENT_DEPLOYMENTS))
def test_deployments_are_unchanged(name):
    c = spec.Cell.named(ROOT, name)
    links = reference.build_links(fabrics.describe(c.config["fabric"]),
                                  c.config.get("impairments"))
    impair = program.deploy(c.config).impair
    want_links, want_impair = PARENT_DEPLOYMENTS[name]
    assert _leaves_hash(links, PARENT_LINK_FIELDS) == want_links
    assert not np.asarray(links.sched).any()
    assert not np.asarray(links.up).any() and not np.asarray(links.t0).any()
    assert (None if impair is None
            else _leaves_hash(impair, impair._fields)) == want_impair


@pytest.mark.parametrize("name", list(PARENT_REFERENCE_FCTS))
def test_reference_fcts_are_unchanged(name):
    c = small.cell(name)
    desc = fabrics.describe(c.config["fabric"])
    job = harness.make_job(c, _fab(c), 2**40 + 7, 0)
    firsts = {}
    for p in job["points"]:
        firsts.setdefault(p["law"], p)
    got = []
    for law, p in firsts.items():
        _, fct = check.reference_run(c, desc, p)
        got.append((law, hashlib.sha256(
            np.asarray(fct, np.float32).tobytes()).hexdigest()))
    assert got == PARENT_REFERENCE_FCTS[name]


def test_sample_draws_one_point_per_law_of_a_grid_job():
    laws = ["powertcp", "hpcc", "timely"]
    jobs = [({"points": [dict(law=law, scenario=10 * i + k) for law in laws
                         for k in range(6)]}, [f"fct{i}.{n}" for n in range(18)])
            for i in range(4)]
    for seed in (1, 2**40 + 3):
        got = check.sample(jobs, np.random.default_rng([seed, 1]))
        rng = np.random.default_rng([seed, 1])
        job, fcts = jobs[int(rng.integers(4))]
        want = [(job["points"][6 * li + k], fcts[6 * li + k])
                for li, k in enumerate(int(rng.integers(6)) for _ in laws)]
        assert got == want
        assert [p["law"] for p, _ in got] == laws


def test_sample_keeps_the_draw_of_one_point_jobs():
    jobs = [({"points": [dict(law="powertcp", scenario=i)]}, [f"fct{i}"])
            for i in range(5)]
    for seed in (1, 2**40 + 3):
        i = int(np.random.default_rng([seed, 1]).integers(5))
        got = check.sample(jobs, np.random.default_rng([seed, 1]))
        assert got == [(jobs[i][0]["points"][0], f"fct{i}")]


def _run_py(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ls256-ws60",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run_py(ROOT, env)
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    r = _run_py(str(tmp_path), dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "{" not in r.stdout


@pytest.mark.parametrize("name", small.names())
def test_control_is_not_correct_and_program_is(name):
    c = small.cell(name)
    rows = list(readings.readings(c, [3, 4], control=True,
                                  require_tpu=False, out=open(os.devnull,
                                                              "w")))
    for r in rows:
        within = all(r[k] <= lim for k, lim in c.check.items())
        assert within == (r["kind"] == "program"), r
