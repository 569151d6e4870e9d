"""The harness finds what is added as files alone, refuses to run
without a chip, and the control comes out as not correct."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.lib import spec
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
