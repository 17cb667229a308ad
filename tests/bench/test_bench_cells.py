"""Whole runs of each cell on the CPU at a size a test can hold.

The harness's look for a chip is skipped (``require_chips=False``) and the
Pallas kernels run interpreted; the simulator is steered onto its Pallas
resolve path as it is on a TPU.  A sound run must come out correct; the
cell's control, and each fault planted under the timed path, must not.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SEED = 3_000_000_017
SMALL = {
    "charz.boolean-grid": {
        "config": {"row_bits": 256, "shared_columns": 128,
                   "trials_per_point": 384},
        "traffic": {"ops": ["and", "nor"], "fanins": [2, 4], "groups": 3,
                    "sample_per_point": 2,
                    # at this size the control differs in fewer decisions
                    # than at the cell's; sound runs read 0.0 on each
                    "limits": {"resolve_mismatch": 1e-5,
                               "decision_count_gap": 0.0,
                               "estimate_gap": 1e-5}}},
    "charz.fanin16-16bank": {
        "config": {"row_bits": 256, "shared_columns": 128},
        "traffic": {"ops": ["nand", "or"], "banks": 2, "groups": 4,
                    "warmup_groups": 2, "trials_per_point": 1024,
                    "sample_per_point": 1}},
    "charz.add4-program": {
        "workload": {"name": "charz.add4-program",
                     "config": "fcdram-ddr4-hynix4gbM",
                     "traffic": "add4-program", "chips": 1},
        "config": {"row_bits": 256, "shared_columns": 128},
        "traffic": {"trials_per_point": 24, "groups": 2,
                    "resolve_sample": 200}},
    "bitmap.ambit-w4": {
        "config": {"users": 8 * 32 * 32, "plane_rows": 8, "plane_words": 32,
                   "days": 40}},
}


@pytest.fixture
def tpu_paths(monkeypatch):
    """Route ``resolve_backend="auto"`` to the Pallas kernel (interpreted),
    as it goes on a TPU."""
    import jax
    from repro.kernels import ops as kops
    assert kops._interpret_default()     # cached before the patch below
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _run(cell, variant="program"):
    return harness.run_cell(cell, SEED, 1.0, False, variant=variant,
                            require_chips=False, overrides=SMALL[cell])


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(tpu_paths, cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) >= {"setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(tpu_paths, cell):
    r = _run(cell, variant="control")
    assert not r["correct"], r["checks"]


def _charz_fault(kind, entry="mc_boolean_success"):
    from repro.core import analog, charz
    from repro.kernels import ops as kops
    resolve = kops.senseamp_resolve_trials
    estimate = getattr(charz, entry)

    def kernel(com, ref, static, normals, uniforms, **kw):
        out = np.array(resolve(com, ref, static, normals, uniforms, **kw))
        if kind == "state_unchanged":       # rows keep their operand value
            return np.asarray(com)[:, 0, :] > 0.5
        if kind == "half_tiled":            # half the trials, tiled
            t = out.shape[0] // 2
            out[t:] = out[:t]
        if kind == "answer_altered":
            out[0, 0] = ~out[0, 0]
        return out

    if kind == "estimate_altered":
        return charz, entry, lambda *a, **k: estimate(*a, **k) + 1e-3
    if kind == "half_batch":                # half the trials, counted whole
        return charz, entry, \
            lambda *a, trials, **k: estimate(*a, trials=trials // 2, **k)
    if kind == "wrong_floor":               # a scalar from the wrong fan-in
        pfloor = analog.op_pfloor
        return analog, "op_pfloor", lambda op, n, *a, **k: pfloor(
            op, 2 * n, *a, **k)
    return kops, "senseamp_resolve_trials", kernel


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered", "estimate_altered",
                                  "half_tiled", "wrong_floor"])
def test_charz_fault_is_caught(tpu_paths, monkeypatch, kind):
    owner, attr, fault = _charz_fault(kind)
    monkeypatch.setattr(owner, attr, fault)
    r = _run("charz.boolean-grid")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered", "estimate_altered",
                                  "wrong_floor"])
def test_add4_fault_is_caught(tpu_paths, monkeypatch, kind):
    owner, attr, fault = _charz_fault(kind, "mc_program_success")
    monkeypatch.setattr(owner, attr, fault)
    r = _run("charz.add4-program")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("kind", ["half_batch", "wrong_floor"])
def test_fused_fault_is_caught(tpu_paths, monkeypatch, kind):
    owner, attr, fault = _charz_fault(kind)
    monkeypatch.setattr(owner, attr, fault)
    r = _run("charz.fanin16-16bank")
    assert not r["correct"], r["checks"]


def test_captured_estimates_hold_one_whole_call(tpu_paths, monkeypatch):
    driver = harness.load_module(BENCH / "drivers" / "charz_boolean.py")
    check, wholes = driver.Driver.check, []

    def counting(self):
        for _seen, _captures, kept, _drawn in self.reservoir.values():
            wholes.extend(sum("whole" in c for c in item[-1]) for item in kept)
        return check(self)

    monkeypatch.setattr(driver.Driver, "check", counting)
    r = _run("charz.boolean-grid")
    assert r["correct"], r["checks"]
    assert wholes and all(w == 1 for w in wholes)


def _bitmap_fault(kind):
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    nary = kops.nary_bitwise

    def kernel(planes, op, **kw):
        out = nary(planes, op, **kw)
        if kind == "state_unchanged":
            return planes[0]
        if kind == "half_batch":
            half = out.shape[0] // 2
            return jnp.concatenate([out[:half], planes[0][half:]])
        return out.at[0, 0].set(out[0, 0] ^ jnp.uint32(1))

    return kops, "nary_bitwise", kernel


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_bitmap_fault_is_caught(tpu_paths, monkeypatch, kind):
    owner, attr, fault = _bitmap_fault(kind)
    monkeypatch.setattr(owner, attr, fault)
    r = _run("bitmap.ambit-w4")
    assert not r["correct"], r["checks"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "bitmap.ambit-w4", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_without_a_result():
    p = _cli(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".jax_cache",
                                                      "__pycache__"))
    proc = _cli(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
