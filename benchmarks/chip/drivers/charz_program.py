"""Program-level characterization Monte-Carlo through the resident executor.

Each unit of work is one ``charz.mc_program_success`` estimate of the
traffic's compiled program (a ``bits``-bit adder): ``trials_per_point``
trials in ``groups`` trial-batched episodes, with the intermediates kept
in DRAM rows and chained by RowClone under the traffic's resident policy.
Estimate ``i`` runs on chip identity ``chip_seed(seed, i)``.

For a sample of the window's estimates, drawn from the seed, every
episode's input planes and output planes are copied, and so is a
seed-drawn sample of ``resolve_sample`` of its resolve calls (with the
APA's rows and latent uniforms, as in ``charz_boolean``).  The check then

* recomputes every kept decision with the configuration's analog model
  (``resolve_mismatch``),
* counts the output bits the kept estimates rest on against the trials,
  shared columns and outputs asked for (``output_count_gap``), and
* recomputes each kept estimate from the episodes' outputs and the plain
  adder on their inputs (``estimate_gap``), which covers the count.
"""
from __future__ import annotations

import numpy as np

import harness

_boolean = harness.load_module(harness.HERE / "drivers" / "charz_boolean.py")
chip_seed = _boolean.chip_seed
WARMUP_CHIP_SEED = _boolean.WARMUP_CHIP_SEED


class Driver(_boolean.Capture):
    def __init__(self, run: harness.Run):
        super().__init__(run)
        mix = run.traffic
        self.program = str(mix["program"])
        self.bits = int(mix["bits"])
        self.outputs = [f"s{i}" for i in range(self.bits)] + ["cout"]
        from repro.core.policy import ResidentPolicy
        self.kwargs["resident"] = ResidentPolicy(mix["resident"])
        self.per_estimate = int(mix["resolve_sample"])
        self.episodes: list | None = None
        self.calls_seen = 0

    def setup(self) -> None:
        from repro.core import compiler
        self.instrument()
        self.run.inst.wrap(compiler, "run_sim", on_call=self._on_run_sim)
        self._estimate(WARMUP_CHIP_SEED)

    def _estimate(self, seed: int) -> float:
        from repro.core import charz
        return charz.mc_program_success(self.program, trials=self.trials,
                                        seed=seed, **self.kwargs)

    def _on_run_sim(self, args, kwargs, out) -> None:
        if self.episodes is not None:
            ins = args[1]
            self.episodes.append(
                ({k: np.array(v) for k, v in ins.items()},
                 {k: np.array(out[k]) for k in self.outputs}))

    def _on_resolve(self, args, kwargs, out) -> None:
        """Keep a reservoir of ``resolve_sample`` calls of the estimate."""
        if self.capturing is None:
            return
        self.calls_seen += 1
        if len(self.capturing) < self.per_estimate:
            self.capturing.append(self.kept_call(args, out))
            return
        j = int(self.sample_rng.integers(self.calls_seen))
        if j < self.per_estimate:
            self.capturing[j] = self.kept_call(args, out)

    def unit(self, i: int) -> int:
        taken = self.take_slot(self.program)
        self.capturing = [] if taken is not None else None
        self.episodes = [] if taken is not None else None
        self.calls_seen = 0
        with self.run.inst.span("estimate"):
            value = self._estimate(chip_seed(self.run.seed, i))
        if taken is not None and taken[1] is not None:
            taken[0][taken[1]] = (i, value, self.episodes, self.capturing)
        self.capturing = self.episodes = None
        return self.trials

    def check(self) -> list[harness.Check]:
        ref = self.run.reference
        self.model = ref.Model(self.run.config)
        lim = self.run.traffic["limits"]
        differ = decided = 0
        gap = count_gap = 0.0
        asked = self.decisions_asked() * len(self.outputs)
        for _seen, _captures, kept, _drawn in self.reservoir.values():
            for _i, value, episodes, calls in kept:
                for call in calls:
                    want, _com = self.reference_decisions(call, None)
                    differ += int(np.count_nonzero(want != call["out"]))
                    decided += want.size
                ok = tot = 0
                for ins, got in episodes:
                    want = ref.add(ins, self.bits)
                    ok += sum(int(np.count_nonzero(got[k] == want[k]))
                              for k in self.outputs)
                    tot += sum(got[k].size for k in self.outputs)
                gap = max(gap, abs(value - ok / tot) if tot else np.inf)
                count_gap = max(count_gap, abs(1.0 - tot / asked))
        mismatch = differ / decided if decided else np.inf
        return [harness.Check("resolve_mismatch", mismatch,
                              lim["resolve_mismatch"]),
                harness.Check("output_count_gap", count_gap,
                              lim["output_count_gap"]),
                harness.Check("estimate_gap", gap, lim["estimate_gap"])]
