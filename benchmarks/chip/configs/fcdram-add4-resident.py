"""Plain reference for the compiled-program cell: the comparator of
``fcdram-ddr4-hynix4gbM`` and a replay of the resident executor's dataflow.

Independent of the program.  :class:`Model`, :func:`charge`,
:func:`resolve`, :func:`ideal` and :func:`add` are the sibling
configuration's (loaded by path, unchanged): the same module, the same
calibration.  :func:`dataflow_mismatch` replays one trial-batched episode
of a resident plan, taken as plain data, row by row:

* a RowClone copies a row, a fill writes a constant row, a park writes a
  host word, a spill reads a row back to the host;
* a Boolean APA must execute its instruction, as the op or its De Morgan
  dual, and each activated compute row must hold that instruction's
  source (a program input, a constant or an earlier result) in the
  polarity the step states, and each reference row the op's constant;
* after the APA every compute row holds the decisions the kernel
  returned, and every reference row their complement.

It returns the share of the operand bits (compute rows, the reference
rows' constants, and the outputs read back) that differ from what the
replay says those rows hold.  RowClone's modeled flip rate (2e-6 a cell a
copy) is the only sound cause of a difference.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np


def _sibling(name: str):
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "ref_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _sibling("fcdram-ddr4-hynix4gbM")
Model, charge, resolve, ideal, add = (_base.Model, _base.charge,
                                      _base.resolve, _base.ideal, _base.add)

_DUAL = {"and": "or", "or": "and"}


def _reg(reg: int, neg: bool) -> tuple:
    return ("reg", int(reg), bool(neg))


def _const(v: int) -> tuple:
    return ("const", int(v))


def dataflow_mismatch(inputs: dict, steps: list, calls: list,
                      outputs: dict) -> float:
    """Share of operand bits the episode's APAs and readouts saw that
    differ from the replay of its plan.

    inputs: input name -> ``(T, W)`` bits; steps: the plan's steps, each a
    dict (``kind`` ``host``/``bool``/``output``, the instruction's ``op``,
    ``dst``, ``srcs``, ``name``, ``value``; ``exec_op``, ``demorgan``,
    ``rows_f``, ``rows_l``, ``pre``, ``sources``, ``ref_row``; ``reg``,
    ``where``); calls: the episode's resolve calls in order, each with
    ``com`` ``(T, n, W)`` and ``ref`` ``(T, n - 1, W)`` operand bits and
    ``out`` ``(T, W)`` decisions; outputs: output name -> ``(T, W)`` bits
    the episode returned.  A row the plan never wrote, a source of the
    wrong register or polarity, an op other than the instruction's, or a
    call count other than the APA count counts all the bits concerned as
    differing.
    """
    host: dict[int, np.ndarray | None] = {}
    rows: dict[tuple[str, int], tuple] = {}     # (side, row) -> (tag, bits)
    apas = [st for st in steps if st["kind"] not in ("host", "output")]
    if len(apas) != len(calls) or any(st["kind"] != "bool" for st in apas):
        return 1.0
    differ = total = 0

    def compare(got, want, ok: bool) -> None:
        nonlocal differ, total
        got = np.asarray(got, dtype=bool)
        total += got.size
        if not ok or want is None:
            differ += got.size
            return
        want = np.broadcast_to(np.asarray(want, dtype=bool), got.shape)
        differ += int(np.count_nonzero(got != want))

    def holds(side: str, row: int) -> tuple:
        return rows.get((side, int(row)), (None, None))

    k = 0
    for st in steps:
        kind = st["kind"]
        if kind == "host":
            host[st["dst"]] = (np.asarray(inputs[st["name"]], np.uint8)
                               if st["op"] == "input"
                               else np.uint8(st["value"]))
            continue
        if kind == "output":
            got = outputs[st["name"]]
            if st["where"][0] == "host":
                compare(got, host.get(st["reg"]), True)
            else:
                side, row, neg = st["where"]
                tag, bits = holds(side, row)
                compare(got, None if bits is None else bits ^ np.uint8(neg),
                        tag == _reg(st["reg"], neg))
            continue
        for m in st["pre"]:
            if m[0] == "reloc":
                rows[(m[1], m[3])] = holds(m[1], m[2])
            elif m[0] == "fill":
                rows[(m[1], m[2])] = (_const(m[3]), np.uint8(m[3]))
            elif m[0] == "spill":
                _, reg, side, row, neg = m
                tag, bits = holds(side, row)
                host[reg] = (bits ^ np.uint8(neg) if tag == _reg(reg, neg)
                             and bits is not None else None)
            else:                                          # park
                _, reg, row, neg = m
                word = host.get(reg)
                rows[("l", row)] = (_reg(reg, neg), None if word is None
                                    else word ^ np.uint8(neg))
        call = calls[k]
        k += 1
        base = "and" if st["op"] in ("and", "nand") else "or"
        exec_op = _DUAL[base] if st["demorgan"] else base
        ok_op = st["exec_op"] == exec_op
        ident = 1 if exec_op == "and" else 0
        com, ref = np.asarray(call["com"]), np.asarray(call["ref"])
        rows_f, rows_l = list(st["rows_f"]), list(st["rows_l"])
        if com.shape[1] != len(rows_l) or ref.shape[1] != len(rows_f) - 1 \
                or len(st["sources"]) != len(rows_l):
            compare(com, None, False)
            compare(ref, None, False)
        else:
            for j, src in enumerate(st["sources"]):
                want = (_reg(st["srcs"][j], st["demorgan"])
                        if j < len(st["srcs"]) else _const(ident))
                if src[0] == "clone":
                    tag, bits = holds("l", src[1])
                else:
                    _, reg, neg = src
                    word = host.get(reg)
                    tag = _reg(reg, neg)
                    bits = None if word is None else word ^ np.uint8(neg)
                compare(com[:, j], bits, ok_op and tag == want)
            if st["ref_row"] is None:
                tag, bits = _const(ident), np.uint8(ident)
            else:
                tag, bits = holds("f", st["ref_row"])
            for j in range(len(rows_f) - 1):
                compare(ref[:, j], bits, ok_op and tag == _const(ident))
        d = np.asarray(call["out"], dtype=np.uint8)
        val_on_l = (st["op"] in ("nand", "nor")) == bool(st["demorgan"])
        for r in rows_l:
            rows[("l", int(r))] = (_reg(st["dst"], not val_on_l), d)
        for r in rows_f:
            rows[("f", int(r))] = (_reg(st["dst"], val_on_l), 1 - d)
    return differ / total if total else 1.0
