"""Each fault the timed path can have, planted underneath a whole run
(without the look for a chip), makes ``correct`` come out false; the
sound run of the same cell comes out true. Every cell of
``BENCHMARK.json`` is run under each fault its entry lists in
``bench/faults/<entry>.py``."""
import os

import pytest

from bench.lib import spec
from bench.tests import small


def _faults(name):
    entry = small.entry_name(small.cell(name))
    mod = spec.load_module(os.path.join(spec.BENCH, "faults", entry + ".py"))
    return mod.FAULTS


CASES = [pytest.param(name, f, id=f"{name}-{f.__name__}")
         for name in small.names() for f in _faults(name)]


@pytest.mark.parametrize("name", small.names())
def test_sound_run_is_correct(name):
    assert small.run(small.cell(name))["correct"] is True


@pytest.mark.parametrize("name, fault", CASES)
def test_fault_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    assert small.run(small.cell(name))["correct"] is False
