"""Kernel byte counts and the trace reduction of the chip benchmark."""
from __future__ import annotations

import gzip
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import kernel_bytes  # noqa: E402
import xplane  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / \
    "charz_window.xplane.pb.gz"


def test_senseamp_bytes_hand_count():
    # fan-in 4 at 64 trials x 4096 shared columns, one static row:
    # com 64*4*4096*4 + ref the same + static 4096*4 + normals 64*4096*4
    # + uniforms 2*64*4096*4, then a bool out plane of 64*4096
    want = (2 * 4194304) + 16384 + 1048576 + 2097152 + 262144
    assert kernel_bytes.senseamp_resolve_trials(
        (64, 4, 4096), (64, 4, 4096), (4096,)) == want
    # a per-trial static plane (the fused bank axis) is read whole:
    # (16 + 48 + 16 + 16 + 32) f32 reads and 16 bool writes
    assert kernel_bytes.senseamp_resolve_trials(
        (2, 1, 8), (2, 3, 8), (2, 8)) == 128 * 4 + 16


def test_nary_bytes_hand_count():
    # 7 planes of 2048 x 256 words read, one written
    assert kernel_bytes.nary_bitwise((7, 2048, 256)) == 8 * 2048 * 256 * 4


def test_union_gaps_and_timeline():
    assert xplane.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == \
        [(0, 3), (5, 10)]
    assert xplane.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]
    spans = [("window", 0, 100), ("estimate", 0, 50), ("resolve", 10, 20)]
    assert xplane.timeline(spans, 0, 100) == [
        (0, 10, "estimate"), (10, 20, "resolve"), (20, 50, "estimate"),
        (50, 100, xplane.UNLABELLED)]
    pieces = xplane.timeline(spans, 0, 100)
    assert xplane.attribute([(5, 15), (40, 60)], pieces) == pytest.approx(
        {"estimate": 15e-9, "resolve": 5e-9, xplane.UNLABELLED: 10e-9})


def test_reduce_events_busy_programs_and_idle():
    dev = {"/device:TPU:0": [("jit_a", 10, 20), ("jit_b", 15, 30),
                             ("jit_a", 60, 70), ("jit_a", 200, 210)]}
    spans = [("window", 0, 100), ("estimate", 0, 100), ("resolve", 40, 60)]
    s = xplane.reduce_events(dev, spans)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(30e-9)           # [10,30] + [60,70]
    assert s.program_s == pytest.approx({"jit_a": 20e-9, "jit_b": 15e-9})
    assert s.program_calls == {"jit_a": 2, "jit_b": 1}
    # gaps [0,10], [30,60] and [70,100]: resolve covers [40,60]
    assert s.idle_by_span == pytest.approx({"estimate": 50e-9,
                                            "resolve": 20e-9})
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_a"
    assert b["idle_gaps"][0][0] == "estimate"


def test_reduce_events_needs_a_window():
    with pytest.raises(ValueError):
        xplane.reduce_events({}, [("estimate", 0, 1)])


def test_program_name_strips_fingerprint():
    assert xplane.program_name("jit_nary_bitwise(1234567)") == \
        "jit_nary_bitwise"


def test_recorded_chip_trace(tmp_path):
    """A half-second window of the boolean grid traced on one TPU v5e."""
    path = tmp_path / "window.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = xplane.read_xplane(str(path), ("estimate", "resolve"))
    assert 0 < s.busy_s < s.window_s
    calls = s.program_calls["jit_senseamp_resolve_trials"]
    assert calls >= 9
    assert s.program_s["jit_senseamp_resolve_trials"] <= s.busy_s
    idle = sum(s.idle_by_span.values())
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert s.idle_by_span["estimate"] > s.idle_by_span["resolve"] > 0
