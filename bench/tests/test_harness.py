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

from bench.lib import check, fabrics, harness, spec, traffic
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
