"""Characterization Monte-Carlo over an op x fan-in grid.

Each unit of work is one ``charz.mc_boolean_success`` estimate: one point
of the grid, ``trials_per_point`` trials over the configuration's stratified
row pairs, on random data.  The grid is cycled in the traffic file's order,
the same for every seed; pass ``p`` of the grid runs on chip identity
``chip_seed(seed, p)``, so each pass characterizes a new chip and draws new
data.

The timed path's resolve entry (``kernels.ops.senseamp_resolve_trials``)
runs inside a ``resolve`` span whose result is on the host when the span
ends.  Of each grid point's estimates the first is captured, then one
drawn from the seed in every block of the traffic's ``sample_every``.  A
captured estimate keeps every resolve call's output and its operand rows'
bits, packed; one of its calls, drawn from the seed, is copied whole (cell
slabs, random draws, output, and the APA's row addresses and the chip's
latent uniforms).  So a capture holds one call's slabs, not all of them:
the host memory the check takes from the program stays small.  A
reservoir over the captures keeps ``sample_per_point`` of them a point, so
every op and fan-in the window ran is checked.  The check then

* recomputes every decision of the whole calls with the configuration's
  analog model in float64, every scalar derived from its calibration and
  the APA's rows (``resolve_mismatch``: the share of decisions that
  differ, a call with the wrong number of operand rows counting as all
  differing, an estimate without its one whole call as all),
* counts the decisions each kept estimate rests on against the trials
  and shared columns asked for (``decision_count_gap``: the largest share
  by which the count falls short of or exceeds them), and
* recomputes each kept estimate from the exact Boolean result of the
  operands in the compute rows and the decisions, the reference's in its
  whole call and the program's in the others (``estimate_gap``: the
  largest difference from what the estimate returned), which covers the
  host episode's staging, readout and count.
"""
from __future__ import annotations

import functools

import numpy as np

import harness
import kernel_bytes

#: chip identity every run warms up on (the same set-up work each run)
WARMUP_CHIP_SEED = 12345


def chip_seed(seed: int, p: int) -> int:
    """Chip identity of pass ``p`` of the grid under run seed ``seed``."""
    return int(np.random.SeedSequence([seed, p]).generate_state(1)[0]
               & 0x7FFFFFFF)


def grid(traffic: dict) -> list[tuple[str, int]]:
    """The cycled (op, fan-in) order, a Latin square: block ``k`` pairs op
    ``j`` with fan-in ``j + k``, so every block of ``len(ops)`` points (and
    so a partial pass) mixes all ops and fan-ins."""
    ops, fanins = traffic["ops"], traffic["fanins"]
    return [(ops[j % len(ops)], fanins[(j + k) % len(fanins)])
            for k in range(len(fanins)) for j in range(len(ops))]


def keep_cells(x) -> np.ndarray:
    """A float32 copy of a cell slab: one plain copy, the cheapest that
    survives the program reusing its buffers after the call."""
    return np.array(x, dtype=np.float32)


def rows_of(addr) -> np.ndarray:
    """Each bank's global row address of an APA argument (an int, or a
    per-bank wrapper with ``vals`` of shape ``(B,)`` or ``(B, k)``)."""
    v = np.asarray(getattr(addr, "vals", addr), dtype=np.int64)
    return v.reshape(v.shape[0], -1)[:, 0] if v.ndim else v.reshape(1)


class Capture:
    """What the charz drivers share: the instrumented resolve entry and
    APAs, the reservoir of kept estimates, and the reference decisions.
    A driver defines ``_on_resolve(args, kwargs, out)``, which sees every
    resolve call."""

    def __init__(self, run: harness.Run):
        self.run = run
        cfg, mix = run.config, run.traffic
        self.trials = int(mix.get("trials_per_point",
                                  cfg["trials_per_point"]))
        self.groups = int(mix["groups"])
        self.kwargs = {"row_bits": int(cfg["row_bits"]),
                       "module": cfg["module"],
                       "temp_c": float(cfg["temp_c"]),
                       "groups": self.groups}
        self.shared = int(cfg["shared_columns"])
        self.sample_rng = np.random.default_rng([run.seed, 2])
        self.per_point = int(mix["sample_per_point"])
        self.every = int(mix.get("sample_every", 1))
        #: point -> [estimates seen, captures, kept samples, drawn capture]
        self.reservoir: dict = {}
        self.capturing: list | None = None
        self.apa_context: dict | None = None

    # -- set-up ----------------------------------------------------------
    def instrument(self) -> None:
        """Wrap the resolve entry and the simulators' APAs."""
        from repro.core import fused, simulator
        from repro.kernels import ops as kops
        inner = None
        if self.run.variant == "control":
            import jax
            import jax.numpy as jnp
            inner = jax.jit(functools.partial(
                self.run.reference.resolve, xp=jnp, dtype=jnp.bfloat16))
        self.run.inst.wrap(
            kops, "senseamp_resolve_trials", span="resolve",
            kernel="senseamp_resolve_trials", to_host=True,
            nbytes=lambda com, ref, static, *a, **k:
                kernel_bytes.senseamp_resolve_trials(
                    np.shape(com), np.shape(ref), np.shape(static)),
            on_call=self._on_resolve, inner=inner)
        for cls in (simulator.BankSim, fused.FusedBankSim):
            self.run.inst.wrap(cls, "apa", inner=self._noting_apa(cls.apa))

    def _noting_apa(self, apa):
        """``apa`` that notes a Boolean APA's rows and the chip's latent
        uniforms of its stripe while an estimate is being kept."""
        rps = int(self.run.config["rows_per_subarray"])

        def noted(sim, rf_global, rl_global, *args, **kwargs):
            outer = self.apa_context
            if self.capturing is not None \
                    and not kwargs.get("first_act_restored", False):
                rf, rl = rows_of(rf_global), rows_of(rl_global)
                stripe = int(min(rf[0] // rps, rl[0] // rps))
                xi1, xi2 = sim._static_latents(stripe)
                self.apa_context = {"rf": rf, "rl": rl,
                                    "xi1": np.array(xi1),
                                    "xi2": np.array(xi2)}
            try:
                return apa(sim, rf_global, rl_global, *args, **kwargs)
            finally:
                self.apa_context = outer
        return noted

    def kept_call(self, args, out) -> dict:
        """A copy of one resolve call: cell slabs, draws, output, APA."""
        com, ref, _static, normals, uniforms = args[:5]
        return {"com": keep_cells(com), "ref": keep_cells(ref),
                "normals": np.array(normals, dtype=np.float32),
                "uniform": np.array(np.asarray(uniforms)[0],
                                    dtype=np.float32),
                "out": np.array(out, dtype=bool), "apa": self.apa_context}

    # -- the window --------------------------------------------------------
    def take_slot(self, key) -> tuple[list, int | None] | None:
        """Sampling step for one more estimate of ``key``: None when it is
        not captured, else (kept list, the slot it goes into, or None).

        The first estimate of each key is captured, then one drawn from the
        seed in each following block of ``sample_every``; each capture
        takes a kept slot as a reservoir over the captures does.  So the
        captures, and the copying they cost in the window, follow from how
        many estimates ran (give or take the last block's draw), never
        from which are kept."""
        entry = self.reservoir.setdefault(key, [0, 0, [], None])
        seen, captures, kept, drawn = entry
        entry[0] = seen + 1
        if seen and (seen - 1) % self.every == 0:
            drawn = entry[3] = seen + int(self.sample_rng.integers(
                self.every))
        if seen and seen != drawn:
            return None
        entry[1] = captures = captures + 1
        if len(kept) < self.per_point:
            kept.append(None)
            return kept, len(kept) - 1
        j = int(self.sample_rng.integers(captures))
        return kept, (j if j < self.per_point else None)

    def end_to_end(self, window_s: float, units: int, work: float) -> dict:
        return {"mc_trials_per_s": work / window_s}

    def release(self) -> None:
        pass

    # -- the check ---------------------------------------------------------
    def decisions_asked(self) -> int:
        """Decisions one estimate rests on: every trial of every row-pair
        group, over every shared column."""
        return -(-self.trials // self.groups) * self.groups * self.shared

    def reference_decisions(self, call: dict, n_want: int | None
                            ) -> tuple[np.ndarray, np.ndarray]:
        """-> (the reference's decisions, the compute rows' cells) of one
        kept resolve call; a call with no noted APA, or with other than
        ``n_want`` compute rows, gets the complement of what it returned."""
        com, ref = call["com"], call["ref"]
        apa = call["apa"]
        if apa is None or (n_want is not None and com.shape[1] != n_want):
            return ~call["out"], com
        want = self.model.decide(
            self.run.reference.charge(com), self.run.reference.charge(ref),
            com.shape[1], ref.shape[1], call["normals"], call["uniform"],
            apa["xi1"], apa["xi2"], apa["rf"], apa["rl"])
        return want, com


class Driver(Capture):
    def __init__(self, run: harness.Run):
        super().__init__(run)
        self.points = grid(run.traffic)
        self.kwargs["banks"] = int(run.traffic.get("banks", 1))
        self.whole_call = 0

    def setup(self) -> None:
        """Warm up every shape and static tuple: each grid point once, on
        ``warmup_groups`` row-pair groups (default all) of the same
        trials per group."""
        self.instrument()
        groups = int(self.run.traffic.get("warmup_groups", self.groups))
        trials = -(-self.trials // self.groups) * groups
        for op, n in self.points:
            self._estimate(op, n, WARMUP_CHIP_SEED, trials=trials,
                           groups=groups)

    def _estimate(self, op: str, n: int, seed: int, **over) -> float:
        from repro.core import charz
        kw = self.kwargs | {"trials": self.trials} | over
        return charz.mc_boolean_success(op, n, seed=seed, **kw)

    # -- the window --------------------------------------------------------
    def _on_resolve(self, args, kwargs, out) -> None:
        """Of a captured estimate, every resolve call's output and operand
        bits, packed; the one call drawn from the seed whole."""
        if self.capturing is None:
            return
        call = {"out": np.array(out, dtype=bool),
                "bits": np.packbits(np.asarray(args[0]) > 0.5, axis=-1)}
        if len(self.capturing) == self.whole_call:
            call["whole"] = self.kept_call(args, out)
        self.capturing.append(call)

    def unit(self, i: int) -> int:
        p, k = divmod(i, len(self.points))
        op, n = self.points[k]
        taken = self.take_slot((op, n))
        self.capturing = [] if taken is not None else None
        if taken is not None:
            self.whole_call = int(self.sample_rng.integers(
                -(-self.groups // self.kwargs["banks"])))
        with self.run.inst.span("estimate"):
            value = self._estimate(op, n, chip_seed(self.run.seed, p))
        if taken is not None and taken[1] is not None:
            taken[0][taken[1]] = (i, op, n, value, self.capturing)
        self.capturing = None
        return self.trials

    def check(self) -> list[harness.Check]:
        ref = self.run.reference
        self.model = ref.Model(self.run.config)
        lim = self.run.traffic["limits"]
        differ = decided = 0
        gap = count_gap = 0.0
        for _seen, _captures, kept, _drawn in self.reservoir.values():
            for _i, op, n, value, calls in kept:
                ok = tot = whole = 0
                for call in calls:
                    bits = np.unpackbits(call["bits"], axis=-1,
                                         count=call["out"].shape[-1])
                    got = call["out"]
                    if bits.shape[1] != n:
                        got = ~got
                    if "whole" in call:
                        got, _com = self.reference_decisions(call["whole"], n)
                        differ += int(np.count_nonzero(got != call["out"]))
                        decided += got.size
                        whole += 1
                    ok += int(np.count_nonzero(got == ref.ideal(op, bits)))
                    tot += got.size
                if whole != 1:
                    differ, decided = np.inf, 1
                gap = max(gap, abs(value - ok / tot) if tot else np.inf)
                count_gap = max(count_gap,
                                abs(1.0 - tot / self.decisions_asked()))
        mismatch = differ / decided if decided else np.inf
        return [harness.Check("resolve_mismatch", mismatch,
                              lim["resolve_mismatch"]),
                harness.Check("decision_count_gap", count_gap,
                              lim["decision_count_gap"]),
                harness.Check("estimate_gap", gap, lim["estimate_gap"])]
