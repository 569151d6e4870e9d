"""The benchmark's cells cut to sizes a CPU test can hold: each cell of
``BENCHMARK.json`` with its files, and the cut ``bench/cuts/<cell>.json``
merged into its configuration and traffic mix in memory (the horizon,
the trace and, for the fat-tree, the fabric shortened)."""
import io
import json
import os

from bench.lib import harness, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def merge(base: dict, cut: dict):
    for k, v in cut.items():
        if isinstance(v, dict):
            merge(base[k], v)
        else:
            base[k] = v


def cell(name):
    c = spec.Cell.named(ROOT, name)
    cut = c.load_json("cuts", name)
    merge(c.config, cut.get("config", {}))
    merge(c.traffic, cut.get("traffic", {}))
    return c


def entry_name(c):
    return c.traffic.get("entry", c.config["entry"])


def run(c, seed=20260101):
    """One run of ``c`` without the look for a chip; its result line."""
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(c, seed, 0.0, False, os.path.join(ROOT, ".bench_tree"),
                     require_tpu=False, out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])
