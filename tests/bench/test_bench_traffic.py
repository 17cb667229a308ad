"""Traffic generators and plain references of the chip benchmark (CPU)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

CHARZ = harness.load_module(BENCH / "drivers" / "charz_boolean.py")
BITMAP = harness.load_module(BENCH / "drivers" / "bitmap_query.py")
FCDRAM = harness.load_module(BENCH / "configs" / "fcdram-ddr4-hynix4gbM.py")
AMBIT = harness.load_module(BENCH / "configs" / "ambit-bitmap-u16m.py")


def test_grid_is_a_latin_square_in_every_block():
    mix = harness.load_json(BENCH / "traffic" / "boolean-grid.json")
    pts = CHARZ.grid(mix)
    assert sorted(pts) == sorted((op, n) for op in mix["ops"]
                                 for n in mix["fanins"])
    k = len(mix["ops"])
    for b in range(0, len(pts), k):
        block = pts[b:b + k]
        assert {op for op, _ in block} == set(mix["ops"])
        assert {n for _, n in block} == set(mix["fanins"])


def test_grid_order_does_not_depend_on_the_seed():
    mix = harness.load_json(BENCH / "traffic" / "boolean-grid.json")
    assert CHARZ.grid(mix) == CHARZ.grid(dict(mix))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 3_000_000_019])
def test_chip_seed_per_pass(seed):
    seeds = [CHARZ.chip_seed(seed, p) for p in range(4)]
    assert seeds == [CHARZ.chip_seed(seed, p) for p in range(4)]
    assert len(set(seeds)) == 4
    assert all(0 <= s < 2**31 for s in seeds)
    assert seeds[0] != CHARZ.chip_seed(seed + 1, 0)


def test_comparator_reference_by_hand():
    # one trial, two columns: margin = 0.1*(+0.5+0.5) - 0.2*(-0.5) - 0.05
    # + static + 0.01*normal
    com = np.array([[[1.0, 0.0], [1.0, 1.0]]], np.float32)    # (1, 2, 2)
    ref = np.array([[[0.0, 1.0]]], np.float32)                # (1, 1, 2)
    static = np.array([0.0, -0.2], np.float32)
    normals = np.array([[1.0, 0.0]], np.float32)
    un = np.array([[[0.9, 0.9]], [[0.0, 0.0]]], np.float32)
    got = FCDRAM.resolve(com, ref, static, normals, un, u_com=0.1,
                         u_ref=0.2, shift=0.05, pf=0.5, trial_sigma=0.01)
    # col 0: 0.1 + 0.1 - 0.05 + 0.01 > 0; col 1: 0 - 0.1 - 0.05 - 0.2 < 0
    assert got.tolist() == [[True, False]]
    un[0, 0, 1] = 0.1                  # activation failure: the coin wins
    got = FCDRAM.resolve(com, ref, static, normals, un, u_com=0.1,
                         u_ref=0.2, shift=0.05, pf=0.5, trial_sigma=0.01)
    assert got.tolist() == [[True, True]]


def test_ideal_families():
    com = np.array([[[1, 0, 1], [1, 0, 0]]], np.float32)
    assert FCDRAM.ideal("nand", com).tolist() == [[True, False, False]]
    assert FCDRAM.ideal("or", com).tolist() == [[True, False, True]]


def _unpack(planes):
    return np.unpackbits(np.ascontiguousarray(planes).view(np.uint8),
                         axis=-1, bitorder="little")


def test_bitmap_reference_matches_run_ideal():
    from repro.core import compiler as CC
    rng = np.random.default_rng(5)
    weeks = 2
    prog = BITMAP.query_program(weeks)
    days = [rng.integers(0, 2**32, (4, 8), dtype=np.uint32)
            for _ in range(7 * weeks)]
    gender = rng.integers(0, 2**32, (4, 8), dtype=np.uint32)
    active, male, n_a, n_m = AMBIT.query(days, gender)
    bits = {f"d{i}": _unpack(p) for i, p in enumerate(days)}
    bits["g"] = _unpack(gender)
    want = CC.run_ideal(prog, bits, width=8 * 32)
    assert np.array_equal(_unpack(active), want["active"])
    assert np.array_equal(_unpack(male), want["male"])
    assert n_a == int(want["active"].sum())
    assert n_m == int(want["male"].sum())


def test_week_days():
    assert AMBIT.week_days(27, 4) == list(range(28))
    assert AMBIT.week_days(13, 1) == list(range(7, 14))
    with pytest.raises(ValueError):
        AMBIT.week_days(26, 4)


def test_bitmap_planes_per_seed():
    a = BITMAP.make_planes(11, 3, 4, 8)
    b = BITMAP.make_planes(11, 3, 4, 8)
    c = BITMAP.make_planes(12, 3, 4, 8)
    assert all(np.array_equal(x, y) for x, y in zip(a, b, strict=True))
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], a[1])
    ones = np.mean(_unpack(np.stack([np.asarray(p) for p in a])))
    assert 0.45 < ones < 0.55


@pytest.mark.parametrize("op", ["and", "or"])
@pytest.mark.parametrize("n", [2, 3, 8, 16])
def test_model_scalars_match_the_calibration(op, n):
    """The configuration's written-out calibration gives the analog model's
    scalars for its module (the reference derives them itself)."""
    from repro.core import analog as A
    cfg = harness.load_json(BENCH / "configs" / "fcdram-ddr4-hynix4gbM.json")
    model = FCDRAM.Model(cfg)
    sgn = 1.0 if op == "and" else -1.0
    ctx = {"temp_c": 50.0, "random_pattern": True, "speed_mts": 2666}
    die = {"mfr": "sk_hynix", "density_gb": 4, "die_rev": "M"}
    assert np.allclose(model.noise(sgn, n), A.op_noise(op, n, **ctx, **die),
                       rtol=1e-12)
    assert np.isclose(model.floor(sgn, n), A.op_pfloor(op, n, **ctx),
                      rtol=1e-12)
    assert np.isclose(model.u(n), A.u_n(n))
    rps = cfg["rows_per_subarray"]
    rf = np.array([5 * rps + 3, 5 * rps + 260, 5 * rps + 500])
    rl = np.array([6 * rps + 400, 6 * rps + 10, 6 * rps + 200])
    got = model.offset(sgn, rf, rl)
    want = [A.margin_offset(op, compute_region=c, ref_region=r, **die)
            for c, r in [(2, 2), (0, 1), (1, 0)]]
    assert np.allclose(got, want, rtol=1e-12)


def test_adder_reference_matches_run_ideal():
    from repro.core import charz
    from repro.core import compiler as CC
    rng = np.random.default_rng(9)
    ins = {f"{v}{i}": rng.integers(0, 2, (3, 64), dtype=np.uint8)
           for v in "ab" for i in range(4)}
    want = CC.run_ideal(charz.get_program("add4"), ins, width=64)
    got = FCDRAM.add(ins, 4)
    assert set(got) == set(want)
    for k in got:
        assert np.array_equal(got[k], np.asarray(want[k]).astype(np.uint8))


def test_kept_cells_are_lossless_copies():
    x = np.array([[[0.0, 0.5], [1.0, 1.0]]], np.float32)
    kept = CHARZ.keep_cells(x)
    assert kept.dtype == np.float32 and np.array_equal(kept, x)
    y = (x + 0.25).astype(np.float64)
    assert np.array_equal(CHARZ.keep_cells(y), y)
    x[0, 0, 0] = 1.0                    # a copy, not a view
    assert kept[0, 0, 0] == np.float32(0.0)


def _capture(seed, every, per_point=1):
    run = harness.Run(harness.Cell("t", 1, {"trials_per_point": 8,
                                            "row_bits": 64, "module": "m",
                                            "temp_c": 50.0,
                                            "shared_columns": 32},
                                   {"groups": 2, "sample_per_point": per_point,
                                    "sample_every": every},
                                   None, None, [], []),
                      seed, harness.Instrument())
    return CHARZ.Capture(run)


@pytest.mark.parametrize("every", [1, 3, 8])
def test_first_and_one_a_block_are_captured(every):
    cap = _capture(3_000_000_019, every)
    taken = [cap.take_slot("p") is not None for _ in range(1 + 5 * every)]
    assert taken[0]
    for b in range(5):
        assert sum(taken[1 + b * every:1 + (b + 1) * every]) == 1
    again = _capture(3_000_000_019, every)
    assert taken == [again.take_slot("p") is not None for _ in taken]


def test_reservoir_keeps_per_point_of_the_captures():
    cap = _capture(11, 2, per_point=2)
    slots = [cap.take_slot("p") for _ in range(41)]
    captured = [t for t in slots if t is not None]
    assert len(captured) == 21
    assert [t[1] for t in captured[:2]] == [0, 1]
    assert all(t[1] in (0, 1, None) for t in captured)
    assert any(t[1] is None for t in captured)
    assert any(t[1] is not None for t in captured[2:])
    assert len(cap.reservoir["p"][2]) == 2
