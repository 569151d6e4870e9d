#!/usr/bin/env python3
"""Record, on the chip, the small trace that ``test_trace_metrics.py``
reads: one traced run of a cell cut to a few hundred ticks.

    python bench/tests/record_trace.py --workload ls256-ws60 --steps 300

It writes ``bench/tests/data/<cell>.xplane.pb.gz`` (the profile) and
``bench/tests/data/<cell>.xplane.pb.gz.json`` (the window's wall-clock
bounds and seconds, the compile spans inside it and the laws of each
point), and prints the run's result line. Needs the cell's chips.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    from bench.lib import harness
    from bench.tests import small
    c = small.cell(a.workload)
    c.config["sim"]["steps"] = a.steps
    c.traffic["duration_s"] = a.steps * c.config["sim"]["dt"] / 2
    out = os.path.join(ROOT, "bench", "tests", "data")
    os.makedirs(out, exist_ok=True)
    try:
        harness.devices(c.chips)
    except harness.NoChip as e:
        print(f"record_trace: {e}", file=sys.stderr)
        return 2
    return harness.run(c, a.seed, 0.0, True, ROOT, keep_trace=os.path.join(
        out, a.workload + ".xplane.pb.gz"))


if __name__ == "__main__":
    sys.exit(main())
