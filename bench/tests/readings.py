#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (``bench/checks``).

    python bench/tests/readings.py --workload <cell> --seeds 1 2 3 ... [--control]

For each seed it draws the job a run with that seed starts its window
with, runs it through the program's entry once, samples its points as
the check does, and compares each with the float32 reference: the
program's readings, whose largest over a dozen seeds is a limit's lower
reading. With ``--control`` it also compares the reference computed in
bfloat16 (the clock kept in float32) with the float32 one at the same
points: the control, whose smallest reading is the upper one. One JSON
line per seed and kind; everything runs in this one process. Needs the
cell's chips, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def readings(cell, seeds, control: bool, require_tpu=True, out=sys.stdout):
    """Yield one dict per seed and kind (``program``/``control``) with
    the worst of ``check.gaps`` per law over the sampled points."""
    from bench.lib import check, fabrics, harness, program
    if require_tpu:
        harness.devices(cell.chips)
    harness.window_cache(harness.use_compile_cache(ROOT))
    cfg = cell.config
    desc = fabrics.describe(cfg["fabric"], cell.dir)
    fab = dict(n_hosts=desc.n_hosts, group=desc.group,
               load_capacity=desc.load_capacity)
    dep = program.deploy(cfg, cell.dir)
    entry = cell.load_module("entries", cell.traffic.get("entry",
                                                         cfg["entry"]))
    none = lambda _: contextlib.nullcontext()
    for seed in seeds:
        job = harness.make_job(cell, fab, seed, 0)
        t0 = time.perf_counter()
        fcts = entry.run(dep, cfg, job, none)
        run_s = time.perf_counter() - t0
        rng = np.random.default_rng([seed, 1])
        kinds = {"program": [], "control": []}
        for point, fct in check.sample([(job, fcts)], rng):
            _, ref = check.reference_run(cell, desc, point)
            kinds["program"].append((point["law"], check.against(fct, ref)))
            if control:
                _, ctl = check.reference_run(cell, desc, point, "bfloat16")
                kinds["control"].append((point["law"],
                                         check.against(ctl, ref)))
        for kind, gs in kinds.items():
            if gs:
                row = check.per_law(gs)
                row.update(cell=cell.name, seed=seed, kind=kind,
                           run_s=run_s)
                print(json.dumps(row), file=out, flush=True)
                yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args(argv)
    from bench.lib import harness, spec
    cell = spec.Cell.named(ROOT, a.workload)
    try:
        for _ in readings(cell, a.seeds, a.control):
            pass
    except harness.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
