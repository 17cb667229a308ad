"""RowClone's noisy copy, bit for bit against the float formula it replaced.

``BankSim.rowclone`` reads each copy's flips off the generator's raw words
and flips only the hit cells.  The oracle below is the float formula:
``rng.random(shape, dtype) < p`` over the restored source, then a whole-row
``np.where``.  Both must leave the same cells, log and generator counter.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro import tracing
from repro.core import charz
from repro.core.device import ENERGY_PJ, VIOLATED_TRP_NS
from repro.core.policy import ResidentPolicy
from repro.core.simulator import BankSim, _uniform_hits

ROW_BITS = 256
P_VALUES = (2e-6, 1e-3, 0.05, 0.5, 1.0)


def oracle_rowclone(self, sub, src, dst):
    """The float-draw RowClone: one uniform a cell, a whole-row rebuild."""
    isrc, idst = self._map_rows(sub, [src, dst])
    arr = self._cells(sub)
    restored = (arr[:, isrc] > 0.5).astype(np.float32)
    copied = restored
    if self.error_model == "analog" and self.rowclone_fail_p > 0.0:
        rng = self._rng()
        flip = rng.random(restored.shape,
                          dtype=self._noise_dtype) < self.rowclone_fail_p
        copied = np.where(flip, 1.0 - restored, restored)
    arr[:, idst] = copied
    arr[:, isrc] = restored
    t = self.timings
    self.log.add("RC", t.tRAS + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                 2 * ENERGY_PJ["act"] + 2 * ENERGY_PJ["pre"],
                 bank=self.bank, sub=sub)


def _sim(trials, p, p_type=float, *, seed=3, error_model="analog",
         row_bits=ROW_BITS):
    sim = BankSim(row_bits=row_bits, seed=seed, error_model=error_model,
                  trials=trials, rowclone_fail_p=p)
    sim.rowclone_fail_p = p_type(p)
    return sim


def _stage(sim, seed):
    """Rows 1-3 hold analog voltages (not just 0/1), so the restore's
    threshold is exercised too."""
    rng = np.random.default_rng(seed)
    idx = sim._map_rows(0, [1, 2, 3])
    cells = sim._cells(0)
    cells[:, idx] = rng.random(cells[:, idx].shape, dtype=np.float32)


# (src, dst) sequence: chained copies, a fresh destination (row 9), a
# source never written (row 11, cold cells), and a copy onto itself
COPIES = ((1, 2), (2, 3), (3, 9), (9, 1), (11, 4), (4, 4), (1, 2))


def _run(sim, seed, fn):
    _stage(sim, seed)
    for src, dst in COPIES:
        fn(sim, 0, src, dst)
    return (sim._arr(0), sim._trial, sim.log.counts.get("RC", 0),
            sim.log.time_ns, sim.log.energy_pj)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("p_type", [float, np.float64],
                         ids=["pyfloat", "npfloat64"])
@pytest.mark.parametrize("p", P_VALUES)
@pytest.mark.parametrize("trials", [None, 8], ids=["float64", "float32"])
def test_rowclone_equals_float_oracle(trials, p, p_type, seed):
    got = _run(_sim(trials, p, p_type, seed=seed), seed, BankSim.rowclone)
    ref = _run(_sim(trials, p, p_type, seed=seed), seed, oracle_rowclone)
    assert np.array_equal(got[0], ref[0])       # every cell, bit for bit
    assert got[1] == ref[1] == len(COPIES)      # one generator a copy
    assert got[2] == ref[2] == len(COPIES)
    assert got[3:] == ref[3:]                   # the command log's totals


@pytest.mark.parametrize("p", P_VALUES + (1e-300, 1 - 2**-30, 7.0, 0.0,
                                          np.float32(0.3)))
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_uniform_hits_are_the_float_comparison(dtype, p):
    for seed in range(8):
        for size in (1, 7, 1000):
            ss = np.random.SeedSequence([seed, 0x7A1A1, 1])
            want = np.flatnonzero(
                np.random.default_rng(ss).random(size, dtype=dtype) < p)
            got = _uniform_hits(np.random.default_rng(ss), size, dtype, p)
            assert np.array_equal(got, want), (seed, size, got, want)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_uniform_hits_at_a_drawn_value(dtype):
    """p equal to one of the draws, and one step either side of it: the
    integer threshold splits the words exactly where ``u < p`` does."""
    for seed in range(4):
        ss = np.random.SeedSequence([seed, 0x7A1A1, 2])
        u = np.random.default_rng(ss).random(64, dtype=dtype)
        for v in u[:8]:
            for p in (v, np.nextafter(v, dtype(0)), np.nextafter(v, dtype(1))):
                for cast in (float, np.float64):
                    want = np.flatnonzero(u < cast(p))
                    got = _uniform_hits(np.random.default_rng(ss), u.size,
                                        dtype, cast(p))
                    assert np.array_equal(got, want), (seed, p, got, want)


def test_source_restored_and_copied():
    sim = _sim(4, 1e-3)
    bits = np.random.default_rng(1).integers(0, 2, (4, ROW_BITS))
    sim.write_row(0, 1, bits)
    sim._cells(0)[:, sim._row(0, 1)] *= 0.9      # charge leaked, not lost
    sim.rowclone(0, 1, 2)
    row = sim._cells(0)[:, sim._row(0, 1)]
    assert set(np.unique(row)) <= {0.0, 1.0}
    assert np.array_equal(sim.read_row(0, 1), bits)
    assert np.mean(sim.read_row(0, 2) != bits) < 0.01


def test_ideal_model_copies_exactly():
    sim = _sim(4, 0.5, error_model="ideal")
    bits = np.random.default_rng(2).integers(0, 2, (4, ROW_BITS))
    sim.write_row(0, 1, bits)
    sim.rowclone(0, 1, 2)
    assert np.array_equal(sim.read_row(0, 2), bits)
    assert sim._trial == 0                       # no generator opened


def test_fresh_destination_gets_a_slot():
    sim = _sim(4, 0.05)
    sim.write_row(0, 1, np.ones(ROW_BITS, np.uint8))
    assert sim._rowmap[0][9] < 0
    sim.rowclone(0, 1, 9)
    assert sim._rowmap[0][9] >= 0
    assert 0.0 < np.mean(sim.read_row(0, 9) == 0) < 0.2
    # a second subarray with no slots at all
    sim.rowclone(2, 5, 6)
    assert sim._rowmap[2][5] >= 0 and sim._rowmap[2][6] >= 0


@pytest.mark.parametrize("sub,src,dst", [(0, -1, 2), (0, 1, 512),
                                         (0, 600, 1), (64, 1, 2)])
def test_out_of_range_raises_and_draws_nothing(sub, src, dst):
    sim = _sim(4, 0.05)
    sim.write_row(0, 1, np.ones(ROW_BITS, np.uint8))
    sim.write_row(0, 2, np.ones(ROW_BITS, np.uint8))
    with pytest.raises(IndexError):
        sim.rowclone(sub, src, dst)
    assert sim._trial == 0
    assert sim.log.counts.get("RC", 0) == 0


def _with_fail_p(monkeypatch, p):
    """Every BankSim built from here on copies with flip probability p."""
    init = BankSim.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        self.rowclone_fail_p = p

    monkeypatch.setattr(BankSim, "__init__", patched)


@pytest.mark.parametrize("p", [None, 0.01], ids=["default_p", "p0.01"])
def test_program_estimate_equals_float_oracle(monkeypatch, p):
    """The 4-bit adder's resident estimate is the same with the float
    oracle in RowClone's place."""
    if p is not None:
        _with_fail_p(monkeypatch, p)
    kw = dict(trials=48, groups=2, row_bits=ROW_BITS, seed=13,
              resident=ResidentPolicy.SCHEDULED)
    tracing.enable()
    tracing.reset()
    try:
        got = charz.mc_program_success("add4", **kw)
        flips = tracing.snapshot()["counters"].get("sim.rowclone_flips", 0)
    finally:
        tracing.disable()
        tracing.reset()
    monkeypatch.setattr(BankSim, "rowclone", oracle_rowclone)
    ref = charz.mc_program_success("add4", **kw)
    assert got == ref
    if p is not None:
        assert flips > 0                         # the flips took part


def test_flip_counter_reads_p():
    sim = _sim(64, 0.05)
    src = np.random.default_rng(4).integers(0, 2, (64, ROW_BITS))
    copies, differ = 24, 0
    tracing.enable()
    tracing.reset()
    try:
        for _ in range(copies):
            sim.write_row(0, 1, src)
            sim.rowclone(0, 1, 2)
            differ += int(np.sum(sim.read_row(0, 2) != src))
        snap = tracing.snapshot()
    finally:
        tracing.disable()
        tracing.reset()
    flips = snap["counters"]["sim.rowclone_flips"]
    assert flips == differ                       # each hit cell, once
    assert 0.045 < flips / (copies * 64 * ROW_BITS) < 0.055


def test_flip_counter_off_by_default():
    tracing.reset()
    sim = _sim(8, 0.05)
    sim.write_row(0, 1, np.ones(ROW_BITS, np.uint8))
    sim.rowclone(0, 1, 2)
    assert "sim.rowclone_flips" not in tracing.snapshot()["counters"]


@pytest.mark.parametrize("fused", [False, True], ids=["grid", "fused"])
def test_single_op_estimates_make_no_copies(fused):
    """The grid and fused cells stage operands and fill references with
    host writes: no RowClone runs in their estimates."""
    tracing.enable()
    tracing.reset()
    try:
        charz.mc_boolean_success("and", 4, trials=32, row_bits=ROW_BITS,
                                 banks=2 if fused else 1, fused=fused)
        snap = tracing.snapshot()
    finally:
        tracing.disable()
        tracing.reset()
    assert snap["spans"]["charz.estimate"]["calls"] == 1
    assert "resident.rowclone" not in snap["spans"]
    assert "sim.rowclone_flips" not in snap["counters"]
