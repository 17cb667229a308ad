"""Compiled-program characterization through the scheduled resident
executor, with its dataflow checked.

Everything of ``charz_program`` (loaded by path): each unit of work is one
``charz.mc_program_success`` estimate on chip identity
``chip_seed(seed, i)``, sampled and checked as there (``resolve_mismatch``,
``output_count_gap``, ``estimate_gap``).  Added here:

* harness spans ``plan`` around ``compiler.schedule_resident`` and
  ``rowclone`` around ``isa.PudIsa.clone_word``;
* of each captured estimate, one row-pair group drawn from the seed, kept
  whole: its input planes, every resolve call's operand bits (the compute
  rows and the reference rows' constants) and decisions, packed, the
  outputs the group returned, and the plan it ran
  (``isa.last_resident_plan``) as plain data;
* a fourth check, ``dataflow_mismatch``: the configuration's replay of
  the kept group's plan gives, for every APA operand and every output,
  the value its source row holds; the share of kept bits that differ
  from it is compared with its limit.  The other checks hold however the
  executor moved its data; this one sees a RowClone that copies the
  wrong row or none, and a staged word of the wrong polarity.
"""
from __future__ import annotations

import numpy as np

import harness

_program = harness.load_module(harness.HERE / "drivers" / "charz_program.py")


def plan_data(plan) -> list[dict]:
    """A resident plan's steps as plain data (ints, strings, tuples)."""
    steps = []
    for st in plan.steps:
        d: dict = {"kind": st.kind}
        i = st.instr
        if i is not None:
            d |= {"op": i.op, "dst": int(i.dst),
                  "srcs": tuple(int(s) for s in i.srcs), "name": i.name,
                  "value": i.value}
        if st.kind in ("bool", "not"):
            d |= {"exec_op": st.exec_op, "demorgan": bool(st.demorgan),
                  "rows_f": [int(r) for r in st.act.rows_f],
                  "rows_l": [int(r) for r in st.act.rows_l],
                  "pre": [tuple(m) for m in st.pre],
                  "sources": [tuple(s) for s in st.sources],
                  "ref_row": st.ref_row}
        elif st.kind == "output":
            d |= {"name": st.name, "reg": int(st.reg),
                  "where": tuple(st.where)}
        steps.append(d)
    return steps


def _packed(x) -> np.ndarray:
    return np.packbits(np.asarray(x) > 0.5, axis=-1)


class Driver(_program.Driver):
    def __init__(self, run: harness.Run):
        super().__init__(run)
        #: the group being kept of the estimate running: its drawn index,
        #: resolve calls, and (once it ran) inputs, outputs and plan
        self.group: dict | None = None
        self.groups_done = 0
        #: estimate index -> its kept group (kept estimates only)
        self.kept_groups: dict[int, dict] = {}

    def setup(self) -> None:
        from repro.core import compiler, isa
        self.run.inst.wrap(compiler, "schedule_resident", span="plan")
        self.run.inst.wrap(isa.PudIsa, "clone_word", span="rowclone")
        super().setup()

    # -- the window --------------------------------------------------------
    def take_slot(self, key):
        taken = super().take_slot(key)
        self.groups_done = 0
        self.group = None if taken is None else {
            "index": int(self.sample_rng.integers(self.groups)),
            "calls": []}
        return taken

    def _on_resolve(self, args, kwargs, out) -> None:
        super()._on_resolve(args, kwargs, out)
        g = self.group
        if g is not None and g["index"] == self.groups_done:
            g["calls"].append({"com": _packed(args[0]),
                               "ref": _packed(np.asarray(args[1])[:, :-1]),
                               "out": _packed(out)})

    def _on_run_sim(self, args, kwargs, out) -> None:
        super()._on_run_sim(args, kwargs, out)
        g = self.group
        if g is not None and g["index"] == self.groups_done:
            g["inputs"] = {k: np.array(v) for k, v in args[1].items()}
            g["outputs"] = {k: np.array(out[k]) for k in self.outputs}
            g["steps"] = plan_data(args[2].last_resident_plan)
        self.groups_done += 1

    def unit(self, i: int) -> int:
        work = super().unit(i)
        if self.group is not None:
            self.kept_groups[i] = self.group
            self.group = None
            kept = {item[0] for _s, _c, items, _d in self.reservoir.values()
                    for item in items if item is not None}
            self.kept_groups = {j: g for j, g in self.kept_groups.items()
                                if j in kept}
        return work

    # -- the check ---------------------------------------------------------
    def check(self) -> list[harness.Check]:
        checks = super().check()
        kept = [item[0] for _s, _c, items, _d in self.reservoir.values()
                for item in items]
        worst = max((self._dataflow(self.kept_groups.get(i)) for i in kept),
                    default=np.inf)
        checks.append(harness.Check(
            "dataflow_mismatch", worst,
            self.run.traffic["limits"]["dataflow_mismatch"]))
        return checks

    def _dataflow(self, g: dict | None) -> float:
        """The reference's replay of one kept group (inf when none ran)."""
        if g is None or "steps" not in g:
            return np.inf
        calls = [{k: np.unpackbits(c[k], axis=-1, count=self.shared)
                  .astype(bool) for k in ("com", "ref", "out")}
                 for c in g["calls"]]
        return self.run.reference.dataflow_mismatch(
            g["inputs"], g["steps"], calls, g["outputs"])
