"""Spans and counters of a compiled program's Monte-Carlo estimate: the
planner, the resident executor and its RowClones, and the staged bytes."""
from __future__ import annotations

import pytest

from repro import tracing
from repro.core import charz
from repro.core.bankarray import BankArray
from repro.core.policy import ResidentPolicy

ROW_BITS = 256
KW = dict(trials=48, groups=2, row_bits=ROW_BITS, seed=11,
          resident=ResidentPolicy.SCHEDULED)


def _estimate(monkeypatch, traced: bool, **over):
    """-> (estimate, snapshot or None, the ISAs of the arrays it built)."""
    made = []

    class Recording(BankArray):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(charz, "BankArray", Recording)
    if traced:
        tracing.enable()
    tracing.reset()
    try:
        value = charz.mc_program_success("add4", **(KW | over))
        snap = tracing.snapshot() if traced else None
    finally:
        tracing.disable()
        tracing.reset()
    isas = [isa for arr in made for isa in arr._isas.values()]
    return value, snap, isas


@pytest.fixture
def traced_add4(monkeypatch):
    return _estimate(monkeypatch, True)


def test_schedule_and_exec_once_per_group(traced_add4):
    _value, snap, _isas = traced_add4
    calls = {k: v["calls"] for k, v in snap["spans"].items()}
    assert calls["charz.estimate"] == 1
    assert calls["compiler.schedule"] == KW["groups"]
    assert calls["resident.exec"] == KW["groups"]
    assert calls["charz.chip"] >= 1


def test_rowclone_calls_are_the_isa_rowclones(traced_add4):
    _value, snap, isas = traced_add4
    clones = sum(isa.stats.rowclones for isa in isas)
    assert clones > 0
    assert snap["spans"]["resident.rowclone"]["calls"] == clones
    assert clones == sum(isa.sim.log.counts.get("RC", 0) for isa in isas)


def test_h2d_bytes_are_the_staged_rows(traced_add4):
    _value, snap, isas = traced_add4
    rows = sum(isa.stats.writes for isa in isas)
    assert rows > 0
    assert rows == sum(isa.sim.log.counts.get("WR", 0) for isa in isas)
    assert snap["counters"]["resident.h2d_bytes"] == rows * ROW_BITS // 8


def test_trials_counter_is_the_trials_asked_for(traced_add4):
    _value, snap, _isas = traced_add4
    assert snap["counters"]["charz.trials"] == KW["trials"]


@pytest.mark.parametrize("policy", [ResidentPolicy.SCHEDULED,
                                    ResidentPolicy.GREEDY])
def test_tracing_leaves_program_estimate_identical(monkeypatch, policy):
    off, _snap, isas_off = _estimate(monkeypatch, False, resident=policy)
    on, snap, isas_on = _estimate(monkeypatch, True, resident=policy)
    assert on == off
    for a, b in zip(isas_on, isas_off, strict=True):
        assert a.stats == b.stats
        assert a.sim.log.counts == b.sim.log.counts
        assert a.sim.log.events == b.sim.log.events
    assert snap["spans"]["resident.exec"]["calls"] == KW["groups"]
