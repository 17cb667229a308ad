"""The APA's result kept as one plane until a row of it is read or reused.

``BankSim.apa`` leaves one bool plane a side pending instead of writing it
into every activated row; ``_cells`` writes it back before any command
touches the subarray's slot buffer, ``read_shared_word`` reads it in
place and ``recycle_rows`` drops it.  The oracle is the same sim with a
``snapshot_rows`` of the activated rows after every APA, which forces the
write-back at once: every read, copy, write and later APA must see the
same cells, and the Monte-Carlo estimates and command logs must be equal.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro import tracing
from repro.core import charz
from repro.core.bankarray import BankArray
from repro.core.fused import FusedBankSim, FusedPudIsa, PerBank
from repro.core.isa import PudIsa
from repro.core.policy import ResidentPolicy
from repro.core.simulator import BankSim

ROW_BITS = 128
TRIALS = 3
#: a row no APA below activates (the RowClone destination)
SPARE_ROW = 511
SIMS = ("bank", "bank_tracked", "banks4")


def _forced_apa(apa):
    """``apa`` followed by a snapshot of its activated rows."""
    def forced(sim, rf, rl, **kw):
        act = apa(sim, rf, rl, **kw)
        rps = sim.geom.rows_per_subarray
        for g, rows in ((rf, act.rows_f), (rl, act.rows_l)):
            sub = int(sim._pb_vals(g)[0, 0]) // rps
            if sub in sim._pending:
                sim.snapshot_rows(sub, rows)
        return act
    return forced


@pytest.fixture
def force_writeback(monkeypatch):
    """Call to make every APA of sims built afterwards write back at once."""
    def force():
        monkeypatch.setattr(BankSim, "apa", _forced_apa(BankSim.apa))
        monkeypatch.setattr(FusedBankSim, "apa",
                            _forced_apa(FusedBankSim.apa))
    return force


def _isa(kind: str) -> PudIsa:
    if kind == "banks4":
        return FusedPudIsa(FusedBankSim(row_bits=ROW_BITS,
                                        bank_seeds=[3, 4, 5, 6],
                                        trials=TRIALS))
    return PudIsa(BankSim(row_bits=ROW_BITS, seed=3, trials=TRIALS,
                          track_unshared=kind == "bank_tracked"))


def _run_apa(isa: PudIsa, protocol: str):
    """Stage random words and run one APA: -> (rf, rl, activation)."""
    sim = isa.sim
    rng = np.random.default_rng(17)
    t = sim._T
    if protocol == "not":
        rf, rl, act = isa.plan_not(1)
        bits = rng.integers(0, 2, (t, isa.width)).astype(np.uint8)
        isa.exec_not(rf, rl, act, ("write", bits))
    else:
        _n, rf, rl, act = isa.plan_nary("nand", 4)
        ops = rng.integers(0, 2, (act.n_rl, t, isa.width)).astype(np.uint8)
        isa.exec_nary("nand", rf, rl, act, ("write_stack", ops))
    return rf, rl, act


def _rows(act, side: str) -> list:
    rows = act.rows_f if side == "f" else act.rows_l
    n = act.n_rf if side == "f" else act.n_rl
    return [rows[k] for k in range(n)]


def _row_ids(act, side: str) -> np.ndarray:
    rows = act.rows_f if side == "f" else act.rows_l
    return np.asarray(getattr(rows, "vals", rows))


def _sides(isa, act):
    return ((isa.f_sub, _rows(act, "f")), (isa.l_sub, _rows(act, "l")))


def _read(isa, rf, rl, act, reader: str, protocol: str) -> list:
    """What ``reader`` sees of every activated row after the APA."""
    sim = isa.sim
    w = sim.shared_w
    out = []
    for sub, rows in _sides(isa, act):
        if reader == "read_row":
            out += [sim.read_row(sub, r) for r in rows]
        elif reader == "snapshot_rows":
            out.append(sim.snapshot_rows(
                sub, PerBank(np.stack([r.vals for r in rows], axis=1))
                if isinstance(rows[0], PerBank) else rows))
        elif reader == "read_shared_word":
            out += [sim.read_shared_word(sub, r, sl) for r in rows
                    for sl in (slice(0, w), slice(w, 2 * w))]
        elif reader == "rowclone":
            spare = (PerBank(np.full(sim.n_banks, SPARE_ROW))
                     if isinstance(rows[0], PerBank) else SPARE_ROW)
            for r in rows:
                sim.rowclone(sub, r, spare)
                out.append(sim.read_row(sub, spare))
        elif reader == "write_cols_multi":
            sl = isa._f_sl if sub == isa.f_sub else isa._l_sl
            part = rows[: (len(rows) + 1) // 2]
            bits = np.ones((sim._T, len(part), w), dtype=np.float32)
            if isinstance(part[0], PerBank):
                part = PerBank(np.stack([r.vals for r in part], axis=1))
            sim.write_cols_multi(sub, part, sl, bits)
            out += [sim.read_row(sub, r) for r in rows]
        elif reader == "apa":
            if sub == isa.f_sub:     # once: the same pair, not re-staged
                sim.apa(sim.global_addr(isa.f_sub, rf),
                        sim.global_addr(isa.l_sub, rl),
                        first_act_restored=protocol == "not")
            out += [sim.read_row(sub, r) for r in rows]
    if sim.n_banks == 1:        # ``_arr`` lays out one bank's rows
        out += [sim._arr(isa.f_sub), sim._arr(isa.l_sub)]
    return out


READERS = ("read_row", "snapshot_rows", "read_shared_word", "rowclone",
           "write_cols_multi", "apa")


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("protocol", ["boolean", "not"])
@pytest.mark.parametrize("kind", SIMS)
def test_activated_rows_read_as_if_written(force_writeback, kind, protocol,
                                           reader):
    isa = _isa(kind)
    rf, rl, act = _run_apa(isa, protocol)
    assert (isa.sim._pending != {}) == (kind != "bank_tracked")
    got = _read(isa, rf, rl, act, reader, protocol)
    force_writeback()
    ref_isa = _isa(kind)
    ref_act = _run_apa(ref_isa, protocol)[2]
    for side in "fl":
        assert np.array_equal(_row_ids(ref_act, side), _row_ids(act, side))
    assert ref_isa.sim._pending == {}
    want = _read(ref_isa, rf, rl, act, reader, protocol)
    assert len(got) == len(want)
    for g, w in zip(got, want, strict=True):
        assert np.array_equal(g, w)
    assert isa.sim.log.events == ref_isa.sim.log.events
    assert isa.sim._trial == ref_isa.sim._trial


@pytest.mark.parametrize("kind", SIMS)
def test_boolean_apa_leaves_result_and_complement(kind):
    """Compute rows hold the comparator's decision, reference rows its
    complement, on the shared half of every activated row."""
    isa = _isa(kind)
    _rf, _rl, act = _run_apa(isa, "boolean")
    words = {sub: [isa.sim.snapshot_rows(sub, r)[..., 0, cols]
                   for r in rows]
             for (sub, rows), cols in zip(_sides(isa, act),
                                          (isa._f_cols, isa._l_cols))}
    result = words[isa.l_sub][0]
    for w in words[isa.l_sub]:
        assert np.array_equal(w, result)
    for w in words[isa.f_sub]:
        assert np.array_equal(w, 1 - result)


@pytest.mark.parametrize("protocol", ["boolean", "not"])
def test_recycle_drops_the_pending_plane(protocol):
    isa = _isa("bank")
    _rf, _rl, act = _run_apa(isa, protocol)
    slots = isa.sim._map_rows(isa.l_sub, act.rows_l)
    before = isa.sim._subarrays[isa.l_sub][:, slots].copy()
    tracing.enable()
    tracing.reset()
    try:
        isa.sim.recycle_rows()
        assert isa.sim._pending == {}
        after = isa.sim._cells(isa.l_sub)[:, slots]
        counters = tracing.snapshot()["counters"]
    finally:
        tracing.disable()
        tracing.reset()
    assert np.array_equal(after, before)     # nothing was written back
    assert counters.get("sim.writeback_rows", 0) == 0


@pytest.mark.parametrize("kind", ["bank", "banks4"])
def test_slot_buffer_growth_keeps_the_pending_plane(force_writeback, kind):
    def grown(isa):
        rf, rl, act = _run_apa(isa, "boolean")
        cap = isa.sim._subarrays[isa.l_sub].shape[1]
        fresh = [r for r in range(isa.sim.geom.rows_per_subarray)
                 if r not in _row_ids(act, "l")][:cap]
        isa.sim._map_rows(isa.l_sub, fresh)      # grows, writes nothing
        assert isa.sim._subarrays[isa.l_sub].shape[1] > cap
        pending = isa.l_sub in isa.sim._pending
        return _read(isa, rf, rl, act, "read_row", "boolean"), pending

    got, pending = grown(_isa(kind))
    force_writeback()
    want, _ = grown(_isa(kind))
    assert pending                           # grown while pending
    for g, w in zip(got, want, strict=True):
        assert np.array_equal(g, w)


def _estimate(monkeypatch, fn, *args, **kw):
    """-> (estimate, every sim's command log events) of one charz call."""
    made = []

    class Recording(BankArray):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(charz, "BankArray", Recording)
    value = fn(*args, **kw)
    logs = [isa.sim.log.events for arr in made
            for isa in (*arr._isas.values(), *arr._fused.values())]
    return value, logs


ESTIMATES = {
    "bank": (charz.mc_boolean_success, ("nand", 4),
             dict(trials=36, groups=3, row_bits=ROW_BITS, seed=5)),
    "banks16_fused": (charz.mc_boolean_success, ("or", 16),
                      dict(trials=64, groups=16, banks=16, fused=True,
                           row_bits=ROW_BITS, seed=5)),
    "add4_scheduled": (charz.mc_program_success, ("add4",),
                       dict(trials=24, groups=2, row_bits=ROW_BITS, seed=5,
                            resident=ResidentPolicy.SCHEDULED)),
}


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("case", sorted(ESTIMATES))
def test_estimates_and_logs_equal_forced_writeback(monkeypatch,
                                                   force_writeback, case,
                                                   backend):
    if backend == "pallas":
        # route ``resolve_backend="auto"`` to the (interpreted) kernel, as
        # it goes on a TPU
        import jax
        from repro.kernels import ops as kops
        assert kops._interpret_default()     # cached before the patch
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, kw = ESTIMATES[case]
    got, got_logs = _estimate(monkeypatch, fn, *args, **kw)
    force_writeback()
    want, want_logs = _estimate(monkeypatch, fn, *args, **kw)
    assert got == want
    assert got_logs and got_logs == want_logs


@pytest.mark.parametrize("case", sorted(ESTIMATES))
def test_writeback_counters(case):
    fn, args, kw = ESTIMATES[case]
    tracing.enable()
    tracing.reset()
    try:
        fn(*args, **kw)
        counters = tracing.snapshot()["counters"]
    finally:
        tracing.disable()
        tracing.reset()
    apa_rows = counters["sim.apa_rows"]
    written = counters.get("sim.writeback_rows", 0)
    if case == "add4_scheduled":
        # each RowClone or APA after an APA writes its subarray's rows back
        assert 0 < written < apa_rows
    else:
        assert written == 0 and apa_rows > 0
