#!/usr/bin/env python3
"""One-chip smoke run of the PuD main path at native DDR4 geometry.

Run from the repository root on a machine with one TPU:

    python3 chip_smoke.py

It drives the system through its normal entry points in one process:

  a. resolve parity: ``BankSim`` n-ary ops through the Pallas sense-amp
     kernel against the numpy comparator, on the same RNG draws;
  b. characterization Monte-Carlo through ``charz`` (NAND16, NOR16, NOT),
     against the closed-form analog model;
  c. program Monte-Carlo: the 4-bit adder under the scheduled resident
     policy on the dram executor;
  d. ``PudEngine("pallas")`` on packed row-scale planes, bit-exact against
     ``PudEngine("jnp")`` and ``compiler.run_ideal``, plus the binary GEMM
     against a matmul.

Each phase prints what it found and its host wall time (smoke timings,
compiles included; not benchmark numbers).  The last line of standard
output is one JSON object ``{"ok": true, "device": {...}}``.  The script
exits non-zero without that line when JAX finds no TPU or any phase fails.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import calibrate, charz
from repro.core import analog as A
from repro.core import compiler as CC
from repro.core.isa import PudIsa
from repro.core.policy import ResidentPolicy
from repro.core.simulator import BankSim
from repro.kernels import ops as kops
from repro.kernels import senseamp
from repro.pud.engine import PudEngine

#: native DDR4 chip row (``core/device.py``)
ROW_BITS = 8192
#: numpy-vs-pallas resolve parity bound: both backends consume identical
#: RNG draws, so only float32 re-association exactly at the comparator
#: threshold may differ (the same bound ``tests/test_executor.py`` uses)
RESOLVE_MISMATCH_TOL = 1e-3
#: MC-vs-closed-form bound for the characterization phase [points]
CLOSED_FORM_TOL = 3.0

#: calls of the trial-batched sense-amp kernel entry point
_KERNEL_CALLS = [0]


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def _count_kernel_calls() -> None:
    """Count every resolve that reaches the sense-amp kernel (the
    simulator looks the entry point up on ``kops`` at each call)."""
    inner = kops.senseamp_resolve_trials

    def counted(*args, **kwargs):
        _KERNEL_CALLS[0] += 1
        return inner(*args, **kwargs)

    kops.senseamp_resolve_trials = counted


def _bits(words) -> np.ndarray:
    """(..., C) packed uint32 -> (..., 32C) uint8, bit i = word i//32 bit i%32."""
    w = np.ascontiguousarray(np.asarray(words, dtype=np.uint32))
    return np.unpackbits(w.view(np.uint8), axis=-1, bitorder="little")


def _pack(bits) -> np.ndarray:
    return np.packbits(np.asarray(bits, dtype=np.uint8), axis=-1,
                       bitorder="little").view(np.uint32)


def phase_resolve_parity(trials: int = 64) -> dict:
    worst = 0.0
    calls0 = _KERNEL_CALLS[0]
    for op in ("and", "or", "nand", "nor"):
        for n in (2, 4, 8, 16):
            outs = {}
            for backend in ("pallas", "numpy"):
                sim = BankSim(row_bits=ROW_BITS, seed=11, trials=trials,
                              track_unshared=False, error_model="analog",
                              resolve_backend=backend)
                isa = PudIsa(sim)
                rng = np.random.default_rng(99)
                ops = rng.integers(0, 2, (n, trials, isa.width)) \
                    .astype(np.uint8)
                outs[backend] = isa.nary_op(op, ops, pair_index=0)
            frac = float(np.mean(outs["pallas"] != outs["numpy"]))
            print(f"  {op}{n}: pallas/numpy mismatch {frac!r}")
            _check(frac <= RESOLVE_MISMATCH_TOL,
                   f"{op}{n} resolve mismatch {frac} > {RESOLVE_MISMATCH_TOL}")
            worst = max(worst, frac)
    _check(_KERNEL_CALLS[0] > calls0, "no resolve reached the Pallas kernel")
    return {"worst_mismatch": worst, "tol": RESOLVE_MISMATCH_TOL}


def phase_charz(trials: int = 216) -> dict:
    out = {}
    calls0 = _KERNEL_CALLS[0]
    ctx = {"die_rev": "M", "density_gb": 4}     # the MC's default module
    for op in ("nand", "nor"):
        got = 100.0 * charz.mc_boolean_success(op, 16, trials=trials,
                                               row_bits=ROW_BITS)
        want = calibrate._avg(op, 16, A.DEFAULT_PARAMS, **ctx)
        out[f"{op}16"] = (got, want)
    _check(_KERNEL_CALLS[0] > calls0,
           "charz Boolean MC did not resolve through the Pallas kernel")
    got = 100.0 * charz.mc_not_success(trials=trials, row_bits=ROW_BITS)
    out["not1"] = (got, calibrate._not(1, A.DEFAULT_PARAMS, **ctx))
    for name, (got, want) in out.items():
        print(f"  {name}: MC {got!r} % vs closed form {want!r} %")
        _check(abs(got - want) < CLOSED_FORM_TOL,
               f"{name} MC {got} is {abs(got - want)} points from {want}")
    return {k: {"mc": g, "closed_form": w} for k, (g, w) in out.items()}


def phase_program(trials: int = 108) -> dict:
    got = charz.mc_program_success("add4", trials=trials, row_bits=ROW_BITS,
                                   resident=ResidentPolicy.SCHEDULED)
    est = charz.program_success_estimate("add4")
    print(f"  add4: MC {got!r} vs independent-op estimate {est!r}")
    _check(est - 0.05 < got < 1.0, f"add4 MC {got} outside ({est - 0.05}, 1)")
    return {"add4": got, "estimate": est}


def _same(name: str, got, eng_ref, ideal) -> None:
    got = np.asarray(got)
    _check(np.array_equal(got, np.asarray(eng_ref)),
           f"{name}: pallas differs from jnp")
    _check(np.array_equal(got, ideal), f"{name}: pallas differs from run_ideal")
    print(f"  {name}: bit-exact, shape {got.shape}")


def phase_engine(rows: int = 512, gemm: int = 256, k_bits: int = 4096,
                 seed: int = 0) -> dict:
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    cols = ROW_BITS // 32
    pal, ref = PudEngine("pallas"), PudEngine("jnp")

    def planes(n):
        return jnp.asarray(rng.integers(0, 2 ** 32, (n, rows, cols),
                                        dtype=np.uint32))

    def ideal(prog, named):
        bits = {k: _bits(v) for k, v in named.items()}
        out = CC.run_ideal(prog, bits, width=ROW_BITS)
        return {k: _pack(v) for k, v in out.items()}

    x = planes(16)
    names = [f"x{i}" for i in range(16)]
    named = dict(zip(names, x, strict=True))
    expr = {"and": CC.And, "or": CC.Or, "nand": CC.Nand, "nor": CC.Nor}
    for op, cls in expr.items():
        want = ideal(CC.compile_expr(cls([CC.Var(v) for v in names])),
                     named)["out"]
        _same(f"nary {op}16", pal.nary(x, op).block_until_ready(),
              ref.nary(x, op), want)

    want = ideal(CC.compile_expr(CC.Not(CC.Var("x0"))), {"x0": x[0]})["out"]
    _same("not", pal.not_(x[0]).block_until_ready(), ref.not_(x[0]), want)

    a, b = planes(8), planes(8)
    adder = ideal(CC.compile_expr(CC.adder_exprs(8)),
                  {f"a{i}": a[i] for i in range(8)}
                  | {f"b{i}": b[i] for i in range(8)})
    want = np.stack([*(adder[f"s{i}"] for i in range(8)), adder["cout"]])
    _same("add 8-bit", pal.add(a, b).block_until_ready(), ref.add(a, b),
          want)

    counts = ideal(CC.compile_expr(CC.popcount_exprs(16)), named)
    want = np.stack([counts[f"c{i}"] for i in range(len(counts))])
    _same("popcount 16", pal.popcount(x).block_until_ready(),
          ref.popcount(x), want)

    prog = charz.get_program("add4")
    ins = {f"a{i}": a[i] for i in range(4)} | {f"b{i}": b[i] for i in range(4)}
    got = pal.run_program(prog, ins)
    want_ref = ref.run_program(prog, ins)
    want = ideal(prog, ins)
    for k in prog.outputs:
        _same(f"run_program add4 {k}", got[k].block_until_ready(),
              want_ref[k], want[k])

    xb = rng.integers(0, 2, (gemm, k_bits)).astype(np.uint8)
    wb = rng.integers(0, 2, (gemm, k_bits)).astype(np.uint8)
    mm = {"and": xb.astype(np.int32) @ wb.T.astype(np.int32),
          "xnor": (2 * xb.astype(np.int32) - 1)
          @ (2 * wb.T.astype(np.int32) - 1)}
    for kind, want in mm.items():
        got = np.asarray(kops.popcount_gemm_bits(xb, wb, kind=kind))
        _check(np.array_equal(got, want),
               f"popcount_gemm {kind} K={k_bits} differs from the matmul")
        print(f"  popcount_gemm {kind} K={k_bits}: bit-exact, "
              f"shape {got.shape}")
    return {"planes": [rows, ROW_BITS], "gemm_k": k_bits}


PHASES = (("a_resolve_parity", phase_resolve_parity),
          ("b_charz_mc", phase_charz),
          ("c_program_mc", phase_program),
          ("d_engine", phase_engine))


def main() -> int:
    import jax
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this smoke runs on the chip only", file=sys.stderr)
        return 1
    _check(not kops._interpret_default(), "kernels would run interpreted")
    cache = {"hits": 0, "requests": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1

    jax.monitoring.register_event_listener(on_event)
    _count_kernel_calls()
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")
    summary = {}
    for name, phase in PHASES:
        print(f"phase {name}")
        t0 = time.perf_counter()
        result = phase()
        wall = time.perf_counter() - t0
        print(f"phase {name}: ok, wall {wall!r} s (smoke timing)")
        summary[name] = {"wall_s": wall, **result}
    summary["senseamp"] = {
        "kernel_calls": _KERNEL_CALLS[0],
        "compiles": senseamp.senseamp_resolve_trials._cache_size()}
    summary["compile_cache"] = {"dir": cache_dir, **cache}
    print(json.dumps({"smoke": summary}))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
