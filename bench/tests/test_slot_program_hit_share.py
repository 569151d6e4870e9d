"""The reader of ``slot_program_hit_share.deploy`` on hand-made counters."""
from bench.lib import spec

NAME = "slot_program_hit_share.deploy"


def test_reads_nothing_without_the_counters(monkeypatch):
    from repro.core import obs
    monkeypatch.setattr(obs, "counters", lambda: {})
    assert spec.reader(NAME).read({}) is None
    monkeypatch.setattr(obs, "counters", lambda: {
        "slots.calls": 2, "slots.ticks": 80000})     # a program without them
    assert spec.reader(NAME).read({}) is None


def test_hits_over_lookups(monkeypatch):
    from repro.core import obs
    monkeypatch.setattr(obs, "counters", lambda: {
        "slots.calls": 2, "slots.ticks": 80000,
        "slots.program_lookups": 2, "slots.program_misses": 1})
    assert spec.reader(NAME).read({}) == 50.0
    monkeypatch.setattr(obs, "counters", lambda: {
        "slots.program_lookups": 8, "slots.program_misses": 2})
    assert spec.reader(NAME).read({}) == 75.0
