#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes, metrics and correctness limits
are files found by the names in ``BENCHMARK.json`` (see
``bench/lib/spec.py``). The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, last, ``check``: each number compared with the plain
reference beside its limit. The same numbers end standard error.

Without as many TPU chips as the cell asks for, the run exits 2 and
prints no result; it never falls back to the CPU.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()      # set-up is timed from the process's start

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    from bench.lib import harness, spec
    cell = spec.Cell.named(ROOT, a.workload)
    try:
        harness.devices(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    return harness.run(cell, a.seed % 2**63, a.seconds, bool(a.trace), ROOT,
                       t_start=T0)


if __name__ == "__main__":
    sys.exit(main())
