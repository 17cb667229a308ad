"""Fused bank axis: the N > 1 constructors of :class:`BankSim`'s bank axis.

``BankArray`` models N banks as concurrent chips; run as N sequential
one-bank episodes, host wall-clock for Monte-Carlo sweeps grows O(banks).
:class:`FusedBankSim` instead builds one :class:`BankSim` over N banks at
T trials per bank: a single ``(N*T, rows, row_bits)`` episode whose
per-command dispatch is paid once instead of N times.  The simulator and
the ISA are the same code at every N (``repro.core.simulator``, *Bank
axis*, has the per-bank parity argument); this module holds only what is
per-bank by nature:

* the constructor (per-bank chip identities and noise seeds) and the
  tail-round counter hand-off (:meth:`FusedBankSim.set_bank_trials`),
* :class:`FusedPudIsa`'s ``PerBank`` / :class:`FusedActivation` handles,
  the per-bank cursor hand-off between a full-width and a bank-subset
  ISA, the per-bank result split, and the lockstep row recycling on
  entry to every op.

Fusion requires every bank to run the *same command sequence with the
same activation geometry*.  On simultaneous-activation modules the pair
inventory equals the decoder's activation category, so same-bucket pairs
on all banks always share geometry; on sequential-activation modules
(Samsung) decoder misses make per-bank retries diverge, so callers
(``charz.mc_*``, ``PudEngine``) keep those on the per-bank loop.
Resident-register execution (RowClone-chained intermediates) stays
loop-only too: its row plans are seed-dependent per bank.
"""
from __future__ import annotations

import numpy as np

from .isa import PudIsa
from .simulator import (BankSim, FusedActivation, FusedExecutionError,
                        FusedGeometryError, PerBank)

__all__ = ["FusedActivation", "FusedBankSim", "FusedExecutionError",
           "FusedGeometryError", "FusedPudIsa", "PerBank"]


class FusedBankSim(BankSim):
    """N independent banks as one ``(N*T, rows, row_bits)`` episode.

    ``bank_seeds`` fixes each bank's chip identity (decoder map + static
    SA offsets); ``trials`` is the per-bank trial count T; ``noise_seeds``
    (default: the bank seeds) each bank's noise stream.

    ``track_unshared`` is forced off (the loop path's trial-batched MC
    sims run that way too); resident row chaining is unsupported.
    """

    # its own class attribute, so that wrapping ``BankSim.apa`` and then
    # ``FusedBankSim.apa`` wraps each exactly once
    apa = BankSim.apa

    def __init__(self, module=None, *, bank_seeds, trials: int,
                 noise_seeds=None, **kw):
        bank_seeds = [int(s) for s in bank_seeds]
        if not bank_seeds:
            raise ValueError("bank_seeds must name at least one bank")
        if trials is None or int(trials) < 1:
            raise ValueError(f"trials must be >= 1 per bank, got {trials}")
        if kw.pop("track_unshared", False):
            raise ValueError("FusedBankSim requires track_unshared=False "
                             "(non-shared column state is per-bank "
                             "divergent and never read back)")
        if "noise_seed" in kw:
            raise TypeError("use noise_seeds (one per bank), not noise_seed")
        if "seed" in kw:
            raise TypeError("use bank_seeds, not seed")
        super().__init__(module, seed=bank_seeds[0],
                         trials=len(bank_seeds) * int(trials),
                         track_unshared=False, **kw)
        noise_seeds = bank_seeds if noise_seeds is None else list(noise_seeds)
        if len(noise_seeds) != len(bank_seeds):
            raise ValueError(
                f"need one noise seed per bank ({len(bank_seeds)}), got "
                f"{len(noise_seeds)}")
        self._set_banks(bank_seeds, noise_seeds)

    def set_bank_trials(self, counters) -> None:
        """Pre-position the per-bank command counters (tail-round
        continuation: a k-bank subset sim continues the first k banks'
        streams after ``full`` rounds on the all-banks sim)."""
        counters = [int(c) for c in counters]
        if len(counters) != self.n_banks:
            raise ValueError(f"need {self.n_banks} counters, got "
                             f"{len(counters)}")
        self._bank_trial = counters


class FusedPudIsa(PudIsa):
    """PudIsa over a :class:`FusedBankSim`: ``PerBank`` row handles and
    :class:`FusedActivation` plans.

    Bank b's cursor/scramble stream is exactly the one its loop-path
    ``PudIsa`` would run, so default pair selection matches the loop path
    per bank.  Every ``exec_*`` recycles row slots on entry (lockstep
    slot allocation; see ``repro.core.simulator``, *Bank axis*).
    """

    def __init__(self, sim: FusedBankSim, *, f_sub: int = 0,
                 l_sub: int | None = None, bank: int = 0):
        if not isinstance(sim, FusedBankSim):
            raise TypeError("FusedPudIsa requires a FusedBankSim")
        super().__init__(sim, f_sub=f_sub, l_sub=l_sub, bank=bank)

    @property
    def n_banks(self) -> int:
        return self.sim.n_banks

    def adopt_state(self, other: "FusedPudIsa") -> None:
        """Continue the first ``self.n_banks`` banks' pair-walk cursors
        and noise counters from a wider fused ISA (tail rounds when
        groups % banks != 0)."""
        k = self.n_banks
        self._bank_cursors = [dict(c) for c in other._bank_cursors[:k]]
        self.sim.set_bank_trials(other.sim._bank_trial[:k])

    def absorb_state(self, other: "FusedPudIsa") -> None:
        """Inverse of :meth:`adopt_state`: fold a narrower subset ISA's
        cursor/counter advances back into this ISA's first banks after a
        tail round, so a *later* call's full rounds continue per-bank
        streams exactly where the loop path's per-bank ISAs would."""
        k = other.n_banks
        if k > self.n_banks:
            raise ValueError("absorb_state wants a narrower fused ISA")
        for b in range(k):
            self._bank_cursors[b] = dict(other._bank_cursors[b])
            self.sim._bank_trial[b] = other.sim._bank_trial[b]

    def _handles(self, pairs: list, acts: list):
        rf, rl = np.asarray(pairs, dtype=np.int64).T
        return PerBank(rf), PerBank(rl), FusedActivation.of(acts)

    def exec_not(self, rf, rl, act: FusedActivation, source):
        if source[0] != "write":
            raise NotImplementedError(
                "fused execution stages operands from the host "
                "(resident row chaining is loop-path only)")
        self.sim.recycle_rows()
        return super().exec_not(rf, rl, act, source)

    def exec_nary(self, op: str, rf, rl, act: FusedActivation, sources, *,
                  ref_row=None, random_pattern: bool = True):
        if ref_row is not None:
            raise NotImplementedError(
                "fused execution host-fills reference rows "
                "(resident constant rows are loop-path only)")
        if not (isinstance(sources, tuple) and sources[0] == "write_stack"):
            raise NotImplementedError(
                "fused execution stages operands with ('write_stack', ops)")
        self.sim.recycle_rows()
        return super().exec_nary(op, rf, rl, act, sources,
                                 random_pattern=random_pattern)

    # ---------------- result splitting ----------------
    def split_banks(self, word: np.ndarray) -> list[np.ndarray]:
        """(N*T, w) fused result -> one (T, w) array per bank."""
        t = self.sim.trials_per_bank
        return [word[b * t:(b + 1) * t] for b in range(self.n_banks)]
