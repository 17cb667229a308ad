"""The compiled-program cell (``charz.add4-program``) on the CPU at a size a
test can hold: sound and control runs, dataflow faults planted under the
resident executor, the replay at zero noise, its readers, and its
configuration against the module's."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

CELL = "charz.add4-program"
SEED = 3_000_000_023
SMALL = {"config": {"row_bits": 256, "shared_columns": 128},
         "traffic": {"trials_per_point": 48, "groups": 2}}
REFERENCE = harness.load_module(BENCH / "configs" / "fcdram-add4-resident.py")
DRIVER = harness.load_module(BENCH / "drivers" / "charz_resident.py")


@pytest.fixture
def tpu_paths(monkeypatch):
    """Route ``resolve_backend="auto"`` to the Pallas kernel (interpreted),
    as it goes on a TPU."""
    import jax
    from repro.kernels import ops as kops
    assert kops._interpret_default()     # cached before the patch below
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _run(variant="program"):
    return harness.run_cell(CELL, SEED, 1.0, False, variant=variant,
                            require_chips=False, overrides=SMALL)


def test_sound_run_is_correct(tpu_paths):
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0
    assert set(r["checks"]) == {"resolve_mismatch", "output_count_gap",
                                "estimate_gap", "dataflow_mismatch"}
    assert set(r["metrics"]) == {"setup_s", "mc_trials_per_s"}


def test_control_is_not_correct(tpu_paths):
    r = _run("control")
    assert not r["correct"], r["checks"]


def _dataflow_fault(kind):
    from repro.core import isa, simulator
    if kind == "clone_skipped":             # destination left unchanged
        return simulator.BankSim, "rowclone", lambda sim, sub, src, dst: None
    if kind == "clone_wrong_row":           # copies the row next to src
        rowclone = simulator.BankSim.rowclone

        def wrong(sim, sub, src, dst):
            near = src + 1 if src + 1 < sim.geom.rows_per_subarray else src - 1
            return rowclone(sim, sub, near, dst)
        return simulator.BankSim, "rowclone", wrong
    stage = isa.PudIsa.stage_word           # staged_complement
    return isa.PudIsa, "stage_word", lambda self, sub, row, bits: stage(
        self, sub, row, 1 - np.asarray(bits))


@pytest.mark.parametrize("kind", ["clone_skipped", "clone_wrong_row",
                                  "staged_complement"])
def test_dataflow_fault_fails_the_dataflow_check(tpu_paths, monkeypatch,
                                                 kind):
    owner, attr, fault = _dataflow_fault(kind)
    monkeypatch.setattr(owner, attr, fault)
    r = _run()
    assert not r["correct"], r["checks"]
    c = r["checks"]["dataflow_mismatch"]
    assert c["value"] > c["limit"], r["checks"]


@pytest.mark.parametrize("kind", ["half_batch", "estimate_altered"])
def test_estimate_fault_is_caught(tpu_paths, monkeypatch, kind):
    from repro.core import charz
    estimate = charz.mc_program_success
    if kind == "half_batch":                # half the trials, counted whole
        def fault(*a, trials, **k):
            return estimate(*a, trials=trials // 2, **k)
    else:
        def fault(*a, **k):
            return estimate(*a, **k) + 1e-3
    monkeypatch.setattr(charz, "mc_program_success", fault)
    r = _run()
    assert not r["correct"], r["checks"]


def test_replay_of_an_ideal_episode_reads_zero(monkeypatch):
    """No noise, no flips: every operand row holds exactly its source."""
    from repro.core import charz
    from repro.core import compiler as CC
    from repro.core import decoder as DEC
    from repro.core.isa import PudIsa
    from repro.core.policy import ResidentPolicy
    from repro.core.simulator import BankSim

    calls = []
    apa = BankSim.apa

    def recording(sim, rf_global, rl_global, **kw):
        rps = sim.geom.rows_per_subarray
        (f_sub, f_row), (l_sub, l_row) = (divmod(rf_global, rps),
                                          divmod(rl_global, rps))
        act = DEC.activation_pattern(sim.module, f_row, l_row, seed=sim.seed)
        _stripe, f_cols, l_cols = sim._split_cols(f_sub, l_sub)
        com = sim.snapshot_rows(l_sub, act.rows_l)[..., l_cols]
        ref = sim.snapshot_rows(f_sub, act.rows_f[:-1])[..., f_cols]
        out = apa(sim, rf_global, rl_global, **kw)
        calls.append({"com": com.astype(bool), "ref": ref.astype(bool),
                      "out": sim.snapshot_rows(l_sub, act.rows_l[:1])
                      [:, 0, l_cols].astype(bool)})
        return out

    monkeypatch.setattr(BankSim, "apa", recording)
    prog = charz.get_program("add4")
    isa = PudIsa(BankSim(row_bits=256, error_model="ideal", seed=7,
                         trials=6))
    rng = np.random.default_rng(0)
    ins = {i.name: rng.integers(0, 2, (6, isa.width)).astype(np.uint8)
           for i in prog.instrs if i.op == "input"}
    out = CC.run_sim(prog, ins, isa, resident=ResidentPolicy.SCHEDULED)
    steps = DRIVER.plan_data(isa.last_resident_plan)
    assert len(calls) == sum(st["kind"] == "bool" for st in steps) > 0
    assert REFERENCE.dataflow_mismatch(ins, steps, calls, out) == 0.0
    want = REFERENCE.add(ins, 4)
    assert all(np.array_equal(out[k], want[k]) for k in want)
    # one flipped operand bit; a step executing the other form; a call
    # missing
    flipped = [dict(c) for c in calls]
    flipped[3] = {**calls[3], "com": calls[3]["com"].copy()}
    flipped[3]["com"][0, 0, 0] ^= True
    assert 0.0 < REFERENCE.dataflow_mismatch(ins, steps, flipped, out) < 1e-3
    k = next(j for j, st in enumerate(steps) if st["kind"] == "bool")
    other = {"and": "or", "or": "and"}[steps[k]["exec_op"]]
    wrong = steps[:k] + [{**steps[k], "exec_op": other}] + steps[k + 1:]
    assert REFERENCE.dataflow_mismatch(ins, wrong, calls, out) > 1e-3
    assert REFERENCE.dataflow_mismatch(ins, steps, calls[:-1], out) == 1.0


def _readings(span_s, window_s=50.0):
    return harness.Readings(trace=None, window_s=window_s, span_s=span_s,
                            kernel_bytes={}, compiles_in_window=0, peaks={})


@pytest.mark.parametrize("metric,span", [("rowclone_wall_share", "rowclone"),
                                         ("plan_wall_share", "plan")])
def test_span_share_readers(metric, span):
    reader = harness.metric_reader(metric)
    assert reader.read(_readings({span: 12.5, "estimate": 49.0})) == 25.0
    assert reader.read(_readings({"estimate": 49.0})) is None
    assert reader.read(_readings({span: 1.0}, window_s=0.0)) is None


def test_config_keeps_the_modules_block():
    new = harness.load_json(BENCH / "configs" / "fcdram-add4-resident.json")
    base = harness.load_json(BENCH / "configs" / "fcdram-ddr4-hynix4gbM.json")
    module_keys = ("module", "manufacturer", "die_rev", "density_gb",
                   "organization", "speed_mts", "activation", "row_bits",
                   "shared_columns", "rows_per_subarray",
                   "subarrays_per_bank", "temp_c", "data_pattern",
                   "comparator_precision", "paper_trials_per_point")
    assert {k: new[k] for k in module_keys} == {k: base[k]
                                                for k in module_keys}
    assert new["calibration"] == base["calibration"]
    mix = harness.load_json(BENCH / "traffic" / "add4-resident.json")
    assert (new["program"], new["bits"], new["resident"]) \
        == (mix["program"], mix["bits"], mix["resident"])
    assert set(new["reduced"]) == {"trials_per_point"}
    assert new["trials_per_point"] == mix["trials_per_point"] \
        == mix["groups"] * 24
