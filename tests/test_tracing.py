"""The program's tracer (``repro.tracing``) and the spans and counters it
puts at the layer boundaries."""
from __future__ import annotations

import re
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro import tracing
from repro.core import charz
from repro.core.bankarray import BankArray

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


@pytest.fixture
def traced():
    """Tracing on and empty for the test, off again after it."""
    tracing.enable()
    tracing.reset()
    try:
        yield tracing
    finally:
        tracing.disable()
        tracing.reset()


def test_off_returns_the_shared_noop_and_records_nothing():
    tracing.disable()
    tracing.reset()
    a, b = tracing.span("charz.estimate", op="and"), tracing.span("sim.apa")
    assert a is b
    with a:
        tracing.count("charz.trials", 64)
    assert tracing.snapshot() == {"spans": {}, "counters": {}}


def test_nested_spans_count_calls_and_self_time(traced):
    for _ in range(3):
        with tracing.span("charz.estimate"):
            with tracing.span("charz.op"):
                with tracing.span("sim.apa"):
                    pass
            with tracing.span("charz.count"):
                tracing.count("charz.trials", 8)
    snap = tracing.snapshot()
    spans = snap["spans"]
    assert {k: v["calls"] for k, v in spans.items()} == {
        "charz.estimate": 3, "charz.op": 3, "sim.apa": 3, "charz.count": 3}
    assert snap["counters"] == {"charz.trials": 24}
    for v in spans.values():
        assert 0 <= v["self_s"] <= v["total_s"]
    root = spans["charz.estimate"]
    children = spans["charz.op"]["total_s"] + spans["charz.count"]["total_s"]
    assert children <= root["total_s"]
    assert root["self_s"] == pytest.approx(root["total_s"] - children,
                                           abs=1e-9)
    assert spans["charz.op"]["total_s"] >= spans["sim.apa"]["total_s"]


def test_traced_decorator_and_reset(traced):
    @tracing.traced("sim.apa")
    def apa(x):
        return x + 1

    assert apa(1) == 2
    assert tracing.snapshot()["spans"]["sim.apa"]["calls"] == 1
    tracing.reset()
    assert tracing.snapshot() == {"spans": {}, "counters": {}}


def test_threads_keep_their_own_nesting_and_lose_no_count(traced):
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(200):
                with tracing.span("engine.run_program"):
                    with tracing.span("engine.kernel"):
                        tracing.count("engine.stack_bytes", 8)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    snap = tracing.snapshot()
    assert snap["counters"] == {"engine.stack_bytes": 8 * 1600}
    assert snap["spans"]["engine.run_program"]["calls"] == 1600
    run, kernel = (snap["spans"][k] for k in ("engine.run_program",
                                               "engine.kernel"))
    assert kernel["total_s"] <= run["total_s"]
    assert run["self_s"] <= run["total_s"]


def test_span_names_cover_every_span_in_the_program():
    used = set()
    for path in SRC.rglob("*.py"):
        used |= set(re.findall(r'tracing\.(?:span|traced)\("([^"]+)"',
                               path.read_text()))
    assert used == set(tracing.SPAN_NAMES)
    assert len(set(tracing.SPAN_NAMES)) == len(tracing.SPAN_NAMES)


def test_span_lands_on_the_profilers_host_plane(traced, tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("charz.estimate", op="and", n=2):
            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    names = {e.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert "charz.estimate" in names


# --------------------------------------------------------------------------
# observability leaves the simulation unchanged
# --------------------------------------------------------------------------
def _mc_with_arrays(monkeypatch, **kw):
    """``mc_boolean_success`` and the BankArrays it built."""
    made = []

    class Recording(BankArray):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(charz, "BankArray", Recording)
    return charz.mc_boolean_success(**kw), made


def _simulated(arrays):
    """Every ISA's statistics and every command log of the arrays."""
    out = []
    for arr in arrays:
        for isa in [*arr._isas.values(), *arr._fused.values()]:
            log = isa.sim.log
            out.append((isa.stats, log.time_ns, log.energy_pj,
                        dict(log.counts), list(log.events)))
    return out


@pytest.mark.parametrize("fused", [False, True], ids=["loop", "fused"])
def test_tracing_leaves_estimate_stats_and_log_identical(monkeypatch, fused):
    kw = dict(op="nand", n=4, trials=24, row_bits=256, seed=5, banks=2,
              groups=4, fused=fused)
    tracing.disable()
    want, arrs_off = _mc_with_arrays(monkeypatch, **kw)
    tracing.enable()
    tracing.reset()
    try:
        got, arrs_on = _mc_with_arrays(monkeypatch, **kw)
        snap = tracing.snapshot()
    finally:
        tracing.disable()
        tracing.reset()
    assert got == want
    assert _simulated(arrs_on) == _simulated(arrs_off)
    calls = {k: v["calls"] for k, v in snap["spans"].items()}
    assert calls["charz.estimate"] == 1
    # 4 groups of ceil(24 / 4) = 6 trials, on 2 banks
    assert snap["counters"]["charz.trials"] == 24
    ops = 2 if fused else 4
    assert calls["charz.op"] == calls["charz.draw"] == calls["charz.count"] \
        == calls["sim.apa"] == calls["isa.stage"] == calls["isa.readout"] \
        == ops
    assert calls["charz.chip"] >= 2


def test_not_estimate_is_traced(traced):
    charz.mc_not_success(trials=18, row_bits=256, seed=3, groups=3)
    snap = tracing.snapshot()
    assert snap["spans"]["charz.estimate"]["calls"] == 1
    assert snap["spans"]["isa.stage"]["calls"] == 3
    assert snap["counters"]["charz.trials"] == 18


def test_engine_counts_stacks_and_kernel_calls(traced):
    import jax.numpy as jnp
    from repro.core import compiler as CC
    from repro.pud.engine import PudEngine
    v = CC.Var
    prog = CC.compile_expr({"o": CC.And([CC.Or([v("a"), v("b"), v("c")]),
                                         CC.Not(v("d"))])})
    rng = np.random.default_rng(0)
    planes = {k: jnp.asarray(rng.integers(0, 2 ** 32, (8, 4),
                                          dtype=np.uint32))
              for k in "abcd"}
    eng = PudEngine("jnp")
    eng.run_program(prog, planes)
    eng.run_program(prog, planes)
    snap = tracing.snapshot()
    c = snap["counters"]
    n_stacked = sum(len(i.srcs) for i in prog.instrs
                    if i.op in ("and", "or", "nand", "nor"))
    assert c["engine.stack_bytes"] == 2 * n_stacked * 8 * 4 * 4
    n_kernels = sum(i.op in ("and", "or", "nand", "nor", "not")
                    for i in prog.instrs)
    spans = snap["spans"]
    assert spans["engine.run_program"]["calls"] == 2
    assert spans["engine.meter"]["calls"] == 2
    assert spans["engine.kernel"]["calls"] == 2 * n_kernels
