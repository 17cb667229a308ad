"""Fused bank-axis execution: loop-parity, noise independence, dealers.

The load-bearing guarantees of ``repro.core.fused`` and its dispatchers:

* **bit-parity** — a ``FusedPudIsa`` episode over N banks produces, per
  bank, exactly the results *and* the command log the per-bank loop
  path produces (property-tested over random banks / trials / row_bits
  / op sequences),
* **noise independence** — fusing the bank axis must not collapse the
  per-bank noise streams: per-bank error patterns stay pairwise
  distinct, exactly as the loop path draws them,
* **charz dispatch** — ``mc_boolean_success`` / ``mc_not_success`` /
  ``mc_program_success`` return identical estimates with ``fused=True``
  and ``fused=False`` (including tail rounds when groups % banks != 0),
  and validate their ``banks`` argument (TypeError for non-ints,
  ValueError for banks>1 on the per-trial path),
* **engine dispatch** — the dram backend's fused rounds match the
  per-bank loop bit-for-bit across nary / NOT / compiled programs,
  including ragged final blocks, bank-subset tail rounds and cursor
  continuity across calls,
* **dealers** — round-robin stays the reproducible default;
  the occupancy dealer balances uneven loads (never a worse makespan)
  and rejects unknown dealers / malformed weights,
* **kernel call form** — the Pallas resolve entry gets the ``(w,)``
  static offsets and the scalar threshold shift at one bank, a per-trial
  ``(N*T, w)`` plane and a zero shift over N banks, and the same kernel
  scalars at both; the resolve scalars are memoized.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import analog as A
from repro.core import charz
from repro.core import compiler as CC
from repro.core import simulator
from repro.core.bankarray import BankArray
from repro.core.fused import (FusedBankSim, FusedGeometryError, FusedPudIsa,
                              PerBank)
from repro.core.isa import PudIsa
from repro.core.policy import EngineConfig, ResidentPolicy
from repro.core.simulator import BankSim
from repro.kernels import ref as kref


def _loop_episode(arr, ops_by_bank, not_bits_by_bank):
    """Reference: each bank's own PudIsa runs the same op sequence."""
    results, logs = [], []
    for b in range(arr.banks):
        isa = arr.isa(b)
        isa.sim.recycle_rows()
        got1 = isa.nary_op("nand", list(ops_by_bank[b].swapaxes(0, 1)))
        isa.sim.recycle_rows()
        got2 = isa.op_not(not_bits_by_bank[b])
        results.append((got1, got2))
        logs.append((isa.sim.log.time_ns, isa.sim.log.energy_pj,
                     dict(isa.sim.log.counts)))
    return results, logs


# ---------------------------------------------------------------------------
# property: fused == loop, results and command logs
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(banks=st.integers(min_value=1, max_value=4),
       trials=st.integers(min_value=1, max_value=3),
       row_bits=st.sampled_from([128, 256]))
def test_fused_matches_loop_bitwise(banks, trials, row_bits):
    arr = BankArray(banks=banks, seed=7, row_bits=row_bits,
                    error_model="analog", trials=trials,
                    track_unshared=False)
    rng = np.random.default_rng(1000 * banks + 10 * trials + row_bits)
    w = arr.isa(0).width
    ops_by_bank = [rng.integers(0, 2, (trials, 2, w)).astype(np.uint8)
                   for _ in range(banks)]
    bits_by_bank = [rng.integers(0, 2, (trials, w)).astype(np.uint8)
                    for _ in range(banks)]
    loop_res, loop_logs = _loop_episode(arr, ops_by_bank, bits_by_bank)

    fsim = FusedBankSim(arr.module, bank_seeds=arr.bank_seeds,
                        trials=trials, row_bits=row_bits,
                        error_model="analog")
    fisa = FusedPudIsa(fsim)
    fgot1 = fisa.nary_op(
        "nand", [np.concatenate([ops_by_bank[b][:, i] for b in range(banks)])
                 for i in range(2)])
    fgot2 = fisa.op_not(np.concatenate(bits_by_bank))
    flog = (fsim.log.time_ns, fsim.log.energy_pj, dict(fsim.log.counts))
    for b in range(banks):
        sl = slice(b * trials, (b + 1) * trials)
        assert (loop_res[b][0] == fgot1[sl]).all(), f"bank {b} nand"
        assert (loop_res[b][1] == fgot2[sl]).all(), f"bank {b} not"
        # one fused command drives all banks at once, so the fused log
        # equals EVERY per-bank loop log (counts, time and energy)
        assert loop_logs[b][2] == flog[2], f"bank {b} log counts"
        assert abs(loop_logs[b][0] - flog[0]) < 1e-9
        assert abs(loop_logs[b][1] - flog[1]) < 1e-9


def test_fused_noise_streams_pairwise_independent():
    """Fusing the bank axis must not collapse per-bank noise streams."""
    banks, trials = 4, 16
    arr = BankArray(banks=banks, seed=3, row_bits=512,
                    error_model="analog", trials=trials,
                    track_unshared=False)
    fisa = arr.fused_isa()
    w = fisa.width
    # identical inputs on every bank: any per-bank result difference is
    # pure noise, so identical slices would mean collapsed streams
    bits = np.tile(np.ones((trials, w), np.uint8), (banks, 1))
    got = fisa.op_not(bits)
    per_bank = fisa.split_banks(got)
    errs = [np.flatnonzero(pb != 0) for pb in per_bank]
    assert all(e.size for e in errs), "need visible errors for the test"
    for a in range(banks):
        for b in range(a + 1, banks):
            assert not np.array_equal(errs[a], errs[b]), \
                f"banks {a} and {b} drew identical noise"
    # and the underlying per-command generators are seeded differently
    assert len(set(fisa.sim.bank_noise_seeds)) == banks


# ---------------------------------------------------------------------------
# charz dispatch: fused == loop estimates, banks validation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("banks,groups", [(3, 6), (3, 4), (4, 3)])
def test_charz_boolean_fused_parity(banks, groups):
    kw = dict(trials=12, groups=groups, row_bits=256, banks=banks)
    assert charz.mc_boolean_success("and", 2, fused=False, **kw) == \
        charz.mc_boolean_success("and", 2, fused=True, **kw)


def test_charz_not_and_program_fused_parity():
    kw = dict(trials=12, groups=4, row_bits=256, banks=3)
    assert charz.mc_not_success(2, fused=False, **kw) == \
        charz.mc_not_success(2, fused=True, **kw)
    assert charz.mc_program_success("xor", fused=False, **kw) == \
        charz.mc_program_success("xor", fused=True, **kw)


@pytest.mark.parametrize("fn", [
    lambda **kw: charz.mc_boolean_success("and", 2, trials=4, **kw),
    lambda **kw: charz.mc_not_success(1, trials=4, **kw),
    lambda **kw: charz.mc_program_success("xor", trials=4, **kw),
])
def test_mc_banks_validation(fn):
    with pytest.raises(TypeError, match="banks must be an int"):
        fn(banks="4")
    with pytest.raises(TypeError, match="banks must be an int"):
        fn(banks=True)
    with pytest.raises(TypeError, match="banks must be an int"):
        fn(banks=2.0)
    with pytest.raises(ValueError, match="banks > 1 requires batched"):
        fn(banks=2, batched=False)


def test_use_fused_gating():
    mod = BankSim(row_bits=128).module
    # forcing fusion with the occupancy dealer cannot be loop-exact
    with pytest.raises(FusedGeometryError, match="occupancy"):
        charz._use_fused(True, mod, 2, "occupancy")
    assert charz._use_fused(None, mod, 2, "occupancy") is False
    assert charz._use_fused(None, mod, 1) is False
    with pytest.raises(FusedGeometryError, match="resident"):
        charz._use_fused(True, mod, 2, resident=True)


# ---------------------------------------------------------------------------
# dealers
# ---------------------------------------------------------------------------
def test_deal_groups_round_robin_and_errors():
    arr = BankArray(banks=3, row_bits=128, error_model="ideal")
    assert charz._deal_groups(arr, 7) == [0, 1, 2, 0, 1, 2, 0]
    with pytest.raises(ValueError, match="unknown dealer"):
        charz._deal_groups(arr, 3, "zigzag")
    with pytest.raises(ValueError, match="weights"):
        charz._deal_groups(arr, 3, "occupancy", weights=[1.0])


def test_occupancy_dealer_balances_uneven_loads():
    arr = BankArray(banks=3, row_bits=128, error_model="ideal")
    # heavy groups first: greedy least-loaded spreads them one per bank
    # and piles the light tail onto the emptiest bank
    weights = [9.0, 9.0, 9.0, 1.0, 1.0, 1.0]
    deal = charz._deal_groups(arr, 6, "occupancy", weights)
    load = [0.0] * 3
    for g, b in enumerate(deal):
        load[b] += weights[g]
    rr_load = [0.0] * 3
    for g in range(6):
        rr_load[g % 3] += weights[g]
    assert max(load) <= max(rr_load)
    assert max(load) == 10.0        # 9 + 1 per bank: perfectly balanced


def test_occupancy_dealer_sees_live_bank_time():
    """A pre-loaded bank is avoided until the others catch up."""
    arr = BankArray(banks=2, row_bits=128, seed=1, error_model="analog",
                    trials=2, track_unshared=False)
    isa = arr.isa(0)                # run real work on bank 0 only
    ops = np.ones((2, 2, isa.width), np.uint8)
    isa.nary_op("and", ops.swapaxes(0, 1))
    assert arr.bank_time_ns()[0] > 0
    deal = charz._deal_groups(arr, 2, "occupancy")
    assert deal[0] == 1             # least-loaded bank first


# ---------------------------------------------------------------------------
# engine dispatch
# ---------------------------------------------------------------------------
def _planes(rng, r, c):
    import jax.numpy as jnp
    return jnp.asarray(rng.integers(0, 2 ** 32, (r, c), dtype=np.uint32))


def _engine_pair(banks, **kw):
    from repro.pud.engine import PudEngine
    return (PudEngine(EngineConfig(backend="dram", banks=banks,
                                   fused=False, **kw)),
            PudEngine(EngineConfig(backend="dram", banks=banks,
                                   fused=True, **kw)))


def test_engine_fused_matches_loop():
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    el, ef = _engine_pair(3, noisy=True)
    # (8, 320): 20 chunks, block size 5 -> one 3-bank round + 1-bank
    # tail round; second call checks cursor continuity after the tail
    x, y = _planes(rng, 8, 320), _planes(rng, 8, 320)
    a = np.asarray(el.nary(jnp.stack([x, y]), "and"))
    b = np.asarray(ef.nary(jnp.stack([x, y]), "and"))
    assert (a == b).all()
    assert (np.asarray(el.not_(x)) == np.asarray(ef.not_(x))).all()
    a2 = np.asarray(el.nary(jnp.stack([x, y]), "nor"))
    b2 = np.asarray(ef.nary(jnp.stack([x, y]), "nor"))
    assert (a2 == b2).all()
    assert ef._array._fused, "fused rounds never executed"
    rl, rf = el.report.merged(), ef.report.merged()
    assert abs(rl.dram.time_ns - rf.dram.time_ns) < 1e-6
    assert rl.dram.bus_bytes == rf.dram.bus_bytes
    assert rl.staged_bytes == rf.staged_bytes


def test_engine_fused_program_host_and_resident():
    rng = np.random.default_rng(6)
    prog = CC.compile_expr({"o": CC.Xor(CC.Var("a"), CC.Var("b"))})
    for pol in (ResidentPolicy.HOST, ResidentPolicy.SCHEDULED):
        el, ef = _engine_pair(3, noisy=True, resident=pol)
        a, b = _planes(rng, 8, 320), _planes(rng, 8, 320)
        ol = el.run_program(prog, {"a": a, "b": b})
        of = ef.run_program(prog, {"a": a, "b": b})
        assert (np.asarray(ol["o"]) == np.asarray(of["o"])).all()
        if pol is ResidentPolicy.HOST:
            assert ef._array._fused, "host-policy programs must fuse"
        else:
            assert not ef._array._fused, \
                "resident programs must fall back to the loop"


def test_engine_fused_config_validation():
    from repro.pud.engine import PudEngine
    with pytest.raises(FusedGeometryError, match="banks=1"):
        PudEngine(EngineConfig(backend="dram", banks=1, fused=True))
    with pytest.raises(ValueError, match="only the dram backend"):
        PudEngine(EngineConfig(backend="jnp", fused=True))
    with pytest.raises(TypeError, match="True/False/None"):
        EngineConfig(backend="dram", banks=2, fused=1)
    # fused=False is allowed anywhere (it is the reference everywhere)
    PudEngine(EngineConfig(backend="jnp", fused=False))


# ---------------------------------------------------------------------------
# fused core odds and ends
# ---------------------------------------------------------------------------
def test_fused_sim_reseed_wants_one_seed_per_bank():
    arr = BankArray(banks=2, row_bits=128, seed=1, error_model="analog",
                    trials=2, track_unshared=False)
    fisa = arr.fused_isa()
    with pytest.raises(ValueError, match="one noise seed per bank"):
        fisa.sim.reseed_noise(7)
    fisa.sim.reseed_noise([7, 8])
    assert fisa.sim.bank_noise_seeds == [7, 8]


def test_perbank_shape_validation():
    arr = BankArray(banks=2, row_bits=128, seed=1, error_model="analog",
                    trials=2, track_unshared=False)
    fisa = arr.fused_isa()
    with pytest.raises(ValueError, match="PerBank rows"):
        fisa.sim._pb_vals(PerBank(np.zeros((3, 1), np.int64)))


def test_absorb_state_roundtrip():
    arr = BankArray(banks=3, row_bits=128, seed=2, error_model="analog",
                    trials=2, track_unshared=False)
    wide = arr.fused_isa()
    narrow = arr.fused_isa(n_banks=2)
    wide._bank_cursors[0][(2, 1)] = 5
    narrow.adopt_state(wide)
    assert narrow._bank_cursors[0][(2, 1)] == 5
    narrow._bank_cursors[1][(2, 1)] = 9
    wide.absorb_state(narrow)
    assert wide._bank_cursors[1][(2, 1)] == 9
    with pytest.raises(ValueError, match="narrower"):
        narrow.absorb_state(wide)


# ---------------------------------------------------------------------------
# the resolve kernel's call form (what the chip compiles and counts)
# ---------------------------------------------------------------------------
def _resolve_calls(monkeypatch, banks, op, n, trials=3):
    """One ``n``-input ``op`` on ``banks`` banks (bank 0 is chip 7) through
    the Pallas resolve entry, the oracle standing in for the kernel.
    -> (sim, rf, rl, n_hw, recorded (static, scalars) calls)."""
    calls = []

    def record(com, ref, static, normals, uniforms, **kw):
        calls.append((static, kw))
        return np.asarray(kref.senseamp_resolve_trials(
            com, ref, static, normals, uniforms, **kw))

    monkeypatch.setattr(simulator, "_resolve_call", record)
    kw = dict(row_bits=256, error_model="analog", resolve_backend="pallas")
    if banks == 1:
        sim = BankSim(seed=7, trials=trials, track_unshared=False, **kw)
        isa = PudIsa(sim)
    else:
        sim = FusedBankSim(bank_seeds=range(7, 7 + banks), trials=trials,
                           **kw)
        isa = FusedPudIsa(sim)
    ops = np.random.default_rng(n).integers(
        0, 2, (n, banks * trials, isa.width)).astype(np.uint8)
    n_hw, rf, rl, act = isa.plan_nary(op, n, pair_index=5)
    isa.exec_nary(op, rf, rl, act, ("write_stack", ops))
    return sim, rf, rl, n_hw, calls


@pytest.mark.parametrize("op,n", [("nand", 8), ("or", 2)])
@pytest.mark.parametrize("banks", [1, 4])
def test_resolve_kernel_call_form(monkeypatch, banks, op, n):
    sim, rf, rl, n_hw, calls = _resolve_calls(monkeypatch, banks, op, n)
    _sim1, _rf1, _rl1, _n1, calls1 = _resolve_calls(monkeypatch, 1, op, n)
    assert len(calls) == len(calls1) == 1
    static, kw = calls[0]
    w, t = sim.shared_w, sim.trials_per_bank
    ctx = A._base_op(op)[0]
    rows_f = np.atleast_1d(getattr(rf, "vals", rf))
    rows_l = np.atleast_1d(getattr(rl, "vals", rl))
    reg_f = sim.geom.distance_regions(rows_f, toward_upper=False)
    reg_l = sim.geom.distance_regions(rows_l, toward_upper=True)
    if banks == 1:
        p, m = sim.params, sim.module
        dv = A.margin_offset(ctx, p, compute_region=int(reg_l[0]),
                             ref_region=int(reg_f[0]),
                             mfr=m.manufacturer.value,
                             density_gb=m.density_gb, die_rev=m.die_rev)
        assert static.shape == (w,)
        assert kw["shift"] == A.op_shift(ctx, n_hw, p) + p.delta_v - dv
    else:
        assert static.shape == (banks * t, w)
        assert kw["shift"] == 0.0
    for k in ("u_com", "u_ref", "pf", "trial_sigma"):
        assert kw[k] == calls1[0][1][k], k
    # memoized: the same key returns the identical tuple
    key = dict(regions=(reg_l, reg_f), random_pattern=True)
    first = sim._resolve_params(0, ctx, n_hw, **key)
    assert sim._resolve_params(0, ctx, n_hw, **key) is first
    assert len(sim._param_cache) == 1


@pytest.mark.parametrize("per_bank_dst", [False, True], ids=["shared", "per_bank"])
def test_fused_rowclone_matches_per_bank(per_bank_dst):
    """A noisy RowClone over the bank axis is each bank's own copy: the
    same flips from each bank's generator, bit for bit."""
    banks, trials, row_bits, p = 3, 4, 256, 0.05
    seeds = [5, 6, 7]
    fsim = FusedBankSim(bank_seeds=seeds, trials=trials, row_bits=row_bits,
                        error_model="analog", rowclone_fail_p=p)
    bits = np.random.default_rng(3).integers(
        0, 2, (banks * trials, row_bits)).astype(np.uint8)
    fsim.write_row(0, 1, bits)
    dst = [2, 5, 9] if per_bank_dst else [2] * banks
    fsim.rowclone(0, 1, PerBank(dst) if per_bank_dst else 2)
    got = fsim.snapshot_rows(0, PerBank(dst))[:, 0]
    assert fsim._bank_trial == [1] * banks
    for b in range(banks):
        sim = BankSim(seed=seeds[b], trials=trials, row_bits=row_bits,
                      error_model="analog", rowclone_fail_p=p,
                      track_unshared=False)
        sl = slice(b * trials, (b + 1) * trials)
        sim.write_row(0, 1, bits[sl])
        sim.rowclone(0, 1, dst[b])
        want = sim.read_row(0, dst[b])
        assert np.array_equal(got[sl], want), f"bank {b}"
        assert 0 < np.count_nonzero(want != bits[sl]) < want.size // 4
