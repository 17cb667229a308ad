"""Whole-plane program execution on the jnp and Pallas backends: each n-ary
instruction's operand stack is built by one cached jitted call
(``engine.stack_planes``) and handed to one eager kernel call."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import compiler as CC
from repro.kernels import ops as kops
from repro.pud import engine as E

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import kernel_bytes  # noqa: E402

ARITIES = (2, 4, 5, 7, 16)
NARY = {"and": (CC.And, np.bitwise_and, False),
        "or": (CC.Or, np.bitwise_or, False),
        "nand": (CC.Nand, np.bitwise_and, True),
        "nor": (CC.Nor, np.bitwise_or, True)}
SHAPE = (8, 4)


def _planes(names, seed=0):
    rng = np.random.default_rng(seed)
    return {k: rng.integers(0, 2 ** 32, SHAPE, dtype=np.uint32)
            for k in names}


def _program(op):
    """One ``op`` instruction of every arity in ``ARITIES``, a NOT, a
    constant, and an ``op`` fed by the NOT and the constant."""
    ctor = NARY[op][0]
    v = [CC.Var(f"x{i}") for i in range(max(ARITIES))]
    outs = {f"n{k}": ctor(v[:k]) for k in ARITIES}
    outs["not"] = CC.Not(v[0])
    outs["one"] = CC.Const(True)
    outs["mixed"] = ctor([CC.Not(v[0]), CC.Const(True), v[1]])
    return CC.compile_expr(outs)


def _expected(op, planes):
    red, invert = NARY[op][1:]
    x = [planes[f"x{i}"] for i in range(max(ARITIES))]
    ones = np.full(SHAPE, 0xFFFFFFFF, np.uint32)

    def apply(ops):
        out = red.reduce(np.stack(ops))
        return ~out if invert else out

    want = {f"n{k}": apply(x[:k]) for k in ARITIES}
    want["not"] = ~x[0]
    want["one"] = ones
    want["mixed"] = apply([~x[0], ones, x[1]])
    return want


def test_program_has_every_arity_once():
    for op in NARY:
        arities = sorted(len(i.srcs) for i in _program(op).instrs
                         if i.op == op)
        assert arities == sorted([*ARITIES, 3])


@pytest.mark.parametrize("op", sorted(NARY))
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_program_is_bit_exact_against_numpy(backend, op):
    planes = _planes([f"x{i}" for i in range(max(ARITIES))])
    out = E.PudEngine(backend).run_program(_program(op), planes)
    want = _expected(op, planes)
    assert out.keys() == want.keys()
    for k, w in want.items():
        np.testing.assert_array_equal(np.asarray(out[k]), w, err_msg=k)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_second_run_at_the_same_shapes_compiles_nothing(backend):
    prog = _program("nor")
    planes = _planes([f"x{i}" for i in range(max(ARITIES))], seed=1)
    eng = E.PudEngine(backend)
    eng.run_program(prog, planes)
    again = _planes(planes, seed=2)
    with harness.CompileMonitor() as monitor:
        out = jax.block_until_ready(eng.run_program(prog, again))
    assert monitor.compiles == 0
    np.testing.assert_array_equal(np.asarray(out["n7"]),
                                  _expected("nor", again)["n7"])


def test_bitmap_query_keeps_the_kernel_interface_the_benchmark_reads():
    """The bitmap cell's query at w = 4 on the Pallas backend, with
    ``kops.nary_bitwise`` wrapped as the benchmark wraps it: one eager call
    per n-ary instruction, each handed a concrete ``(n, R, C)`` array; the
    ``engine.stack`` span and ``engine.stack_bytes`` count as before (37
    stacked planes a query)."""
    bitmap = harness.load_module(BENCH / "drivers" / "bitmap_query.py")
    prog = bitmap.query_program(4)
    nary = [i for i in prog.instrs if i.op in NARY]
    assert sorted(len(i.srcs) for i in nary) == [4, 5, 7, 7, 7, 7]
    planes = _planes([*(f"d{j}" for j in range(28)), "g"], seed=3)
    calls = []
    inst = harness.Instrument()
    inst.in_window = True
    inst.wrap(kops, "nary_bitwise", kernel="nary_bitwise",
              nbytes=lambda p, *a, **k: kernel_bytes.nary_bitwise(p.shape),
              on_call=lambda args, kwargs, out: calls.append(args))
    tracing.enable()
    tracing.reset()
    try:
        out = E.PudEngine("pallas").run_program(prog, planes)
        snap = tracing.snapshot()
    finally:
        tracing.disable()
        tracing.reset()
        inst.restore()
    assert len(calls) == len(nary)
    for (stack, op), i in zip(calls, nary):
        assert isinstance(stack, jax.Array)
        assert not isinstance(stack, jax.core.Tracer)
        assert stack.shape == (len(i.srcs), *SHAPE)
        assert stack.dtype == jnp.uint32
        assert op == i.op
    plane_bytes = SHAPE[0] * SHAPE[1] * 4
    assert snap["spans"]["engine.stack"]["calls"] == len(nary)
    assert snap["counters"]["engine.stack_bytes"] == 37 * plane_bytes
    assert inst.kernel_bytes["nary_bitwise"] == (37 + 6) * plane_bytes
    weeks = [np.bitwise_or.reduce(np.stack(
        [planes[f"d{7 * w + i}"] for i in range(7)])) for w in range(4)]
    active = np.bitwise_and.reduce(np.stack(weeks))
    np.testing.assert_array_equal(np.asarray(out["active"]), active)
    np.testing.assert_array_equal(np.asarray(out["male"]),
                                  active & planes["g"])
