"""The ``run_sweep`` entry against the ``simulate_slots`` entry: every
point of a grid job, run in one batched sweep call, gives the FCTs that
a call of its own gives (the program's batch == serial contract), at
the CPU cut of ``ls256-fig7-sweep``."""
import contextlib

import numpy as np

from bench.lib import fabrics, harness, program, spec
from bench.tests import small


def test_each_point_is_its_serial_run():
    c = small.cell("ls256-fig7-sweep")
    cfg = c.config
    d = fabrics.describe(cfg["fabric"])
    fab = dict(n_hosts=d.n_hosts, group=d.group,
               load_capacity=d.load_capacity)
    dep = program.deploy(cfg)
    job = harness.make_job(c, fab, 2**40 + 5, 0)
    none = lambda _: contextlib.nullcontext()
    batched = c.load_module("entries", "run_sweep").run(dep, cfg, job, none)
    serial = c.load_module("entries", "simulate_slots")
    assert len(batched) == len(job["points"]) == 9
    for p, fct in zip(job["points"], batched):
        (one,) = serial.run(dep, cfg, dict(points=[p]), none)
        n = len(one)
        assert np.isfinite(one).sum() > 0
        np.testing.assert_allclose(fct[:n], one, rtol=1e-6)
        assert not np.isfinite(fct[n:]).any()


def test_a_job_that_is_no_grid_is_refused():
    pts = [dict(law=law, load=0.6, scenario=s) for law in ("a", "b")
           for s in (1, 2)]
    entry = spec.load_module(spec.BENCH + "/entries/run_sweep.py")
    assert entry.lanes(dict(points=pts)) == pts[:2]
    import pytest
    with pytest.raises(ValueError):
        entry.lanes(dict(points=pts[:3] + [dict(pts[3], scenario=9)]))
