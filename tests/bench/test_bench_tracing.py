"""The program's spans and counters as the chip benchmark would read them:
resolve bytes against the kernel's interface count, the bitmap cell's
stacked bytes, and the trace reduction with the program's span names."""
from __future__ import annotations

import gzip
import json
import shutil
import sys
from pathlib import Path

import pytest

from repro import tracing
from repro.core import charz

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import kernel_bytes  # noqa: E402
import xplane  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / \
    "charz_window.xplane.pb.gz"


@pytest.fixture
def traced():
    tracing.enable()
    tracing.reset()
    try:
        yield tracing
    finally:
        tracing.disable()
        tracing.reset()


@pytest.fixture
def tpu_paths(monkeypatch):
    """Route ``resolve_backend="auto"`` to the Pallas kernel (interpreted),
    as it goes on a TPU."""
    import jax
    from repro.kernels import ops as kops
    assert kops._interpret_default()     # cached before the patch below
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("fused", [False, True], ids=["loop", "fused"])
def test_resolve_h2d_bytes_are_the_interface_reads(traced, tpu_paths,
                                                   monkeypatch, fused):
    """Call by call, ``resolve.h2d_bytes`` grows by the kernel's interface
    bytes less its ``(T, W)`` bool output."""
    from repro.kernels import ops as kops
    resolve = kops.senseamp_resolve_trials
    calls = []

    def recording(com, ref, static, normals, uniforms, **k):
        out = resolve(com, ref, static, normals, uniforms, **k)
        t, _, w = com.shape
        want = kernel_bytes.senseamp_resolve_trials(
            com.shape, ref.shape, static.shape) - t * w
        calls.append((want, tracing.snapshot()))
        return out

    monkeypatch.setattr(kops, "senseamp_resolve_trials", recording)
    charz.mc_boolean_success("and", 4, trials=16, row_bits=256, seed=9,
                             banks=2, groups=4, fused=fused)
    snap = tracing.snapshot()
    assert len(calls) == (2 if fused else 4)
    total = 0
    for i, (want, seen) in enumerate(calls):
        total += want
        # the i-th call's span is still open while the kernel runs
        assert seen["spans"].get("sim.resolve_call", {"calls": 0})["calls"] \
            == i
        assert seen["counters"]["resolve.h2d_bytes"] == total
    assert snap["counters"]["resolve.h2d_bytes"] == total
    spans = snap["spans"]
    assert spans["sim.resolve_prep"]["calls"] == len(calls)
    assert spans["sim.resolve_call"]["calls"] == len(calls)
    assert spans["sim.resolve_call"]["total_s"] \
        <= spans["sim.apa"]["total_s"]


def test_bitmap_stack_bytes_per_query_is_the_hand_count(traced):
    """w = 4: four weekly ORs of 7 daily planes, the AND of the 4 weeks
    and the AND of the gender plane with them stack 37 planes a query
    (77,594,624 B at the cell's (2048, 256) uint32 planes)."""
    rows, words = 8, 32
    r = harness.run_cell("bitmap.ambit-w4", 3_000_000_019, 0.5, False,
                         require_chips=False, overrides={"config": {
                             "users": rows * words * 32, "plane_rows": rows,
                             "plane_words": words, "days": 40}})
    assert r["correct"], r["checks"]
    snap = tracing.snapshot()
    programs = snap["spans"]["engine.run_program"]["calls"]
    assert programs >= 3                          # two warm-up queries
    assert snap["counters"]["engine.stack_bytes"] == 37 * rows * words * 4 \
        * programs
    assert 37 * 2048 * 256 * 4 == 77_594_624


def _readings(summary):
    return harness.Readings(summary, summary.window_s, {"resolve": 0.1},
                            {"senseamp_resolve_trials": 10**6,
                             "nary_bitwise": 10**6}, 0,
                            harness.load_peaks("TPU v5 lite"))


def test_existing_metrics_read_the_same_with_program_span_names(tmp_path):
    """The fixture was recorded with tracing off, so it holds no program
    span: this shows only that asking for the program's span names as
    well changes no metric when those spans are absent (the attribution
    itself is tested on synthetic spans below)."""
    path = tmp_path / "window.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    old = xplane.read_xplane(str(path), harness.SPANS)
    new = xplane.read_xplane(str(path), harness.SPANS + tracing.SPAN_NAMES)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in (m["name"] for m in manifest["per_layer"]):
        reader = harness.metric_reader(name)
        assert reader.read(_readings(new)) == reader.read(_readings(old)), \
            name
    assert new.breakdown() == old.breakdown()


def test_idle_goes_to_the_innermost_span_of_either_kind():
    """Program spans split the harness's ``estimate``; the harness's
    ``resolve`` inside ``sim.resolve_call`` keeps its own gaps."""
    spans = [("window", 0, 100), ("estimate", 0, 100),
             ("charz.op", 10, 90), ("sim.apa", 20, 80),
             ("sim.resolve_call", 40, 60), ("resolve", 45, 55)]
    s = xplane.reduce_events({"/device:TPU:0": [("jit_k", 50, 52)]}, spans)
    assert s.idle_by_span == pytest.approx({
        "estimate": 20e-9, "charz.op": 20e-9, "sim.apa": 40e-9,
        "sim.resolve_call": 10e-9, "resolve": 8e-9})
