"""Finding a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
configuration entry names its file. A traffic mix ``<mix>`` is
``bench/traffic/<mix>.json``; a per-layer metric ``<metric>`` is read by
``bench/metrics/<metric>.py`` or, where there is none, by the file of
the name's first part (``idle_share.deploy``, and any later
``idle_share.<kind>``, by ``bench/metrics/idle_share.py``); a program
entry ``<entry>`` (named in the configuration file) is driven by
``bench/entries/<entry>.py``; the limits of a cell's correctness check
are ``bench/checks/<cell>.json``. The reference's laws
(``bench/laws/``), fabric kinds (``bench/fabrics/``, with the program's
constructors in ``bench/deploy/``), the faults of each entry
(``bench/faults/``) and each cell's CPU cut (``bench/cuts/``) are found
the same way. A later cell, mix, metric, law, fabric kind or entry is
added as files and entries alone.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Cell:
    """One workload with its configuration, its traffic mix, its check
    limits and the metrics ``BENCHMARK.json`` lists for it."""

    def __init__(self, name: str, workload: dict, config: dict,
                 bench: dict, bench_dir: str = BENCH):
        self.name, self.entry, self.config = name, workload, config
        self.bench, self.dir = bench, bench_dir
        self.traffic = self.load_json("traffic", workload["traffic"])
        self.check = self.load_json("checks", name)
        self.chips = int(workload["chips"])

    @classmethod
    def named(cls, root: str, name: str, bench_dir: str = BENCH) -> "Cell":
        """The workload ``name`` of ``<root>/BENCHMARK.json``."""
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{sorted(cells)}")
        cfg = {c["name"]: c for c in bench["configs"]}[cells[name]["config"]]
        with open(os.path.join(root, cfg["file"])) as f:
            config = json.load(f)
        return cls(name, cells[name], config, bench, bench_dir)

    def metrics(self, kind: str):
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those that list it under ``workloads``, or list no cells."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]

    def load_json(self, kind: str, name: str) -> dict:
        with open(os.path.join(self.dir, kind, name + ".json")) as f:
            return json.load(f)

    def load_module(self, kind: str, name: str):
        return load_module(os.path.join(self.dir, kind, name + ".py"))

    def reader(self, metric: str):
        return reader(metric, self.dir)


def reader(metric: str, bench_dir: str = BENCH):
    """The module whose ``read(ctx)`` gives per-layer ``metric``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    if not os.path.exists(path):
        path = os.path.join(bench_dir, "metrics", metric.split(".")[0] + ".py")
    return load_module(path)


def load_module(path: str):
    """A ``.py`` file as a module (its name may hold dots)."""
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        "bench_plugin_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
