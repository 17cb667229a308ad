"""PuD instruction set: row allocation, op scheduling, and cost accounting.

Bridges the raw APA mechanism (``repro.core.simulator``) and the Boolean
expression compiler (``repro.core.compiler``):

* :class:`PairInventory` — per (module, seed) table of which ``(R_F, R_L)``
  address pairs realize each ``N_RF:N_RL`` activation type (the software
  equivalent of the paper's reverse-engineering sweep, §4.2).
* :class:`PudIsa` — executes logical PuD instructions (NOT / many-input
  AND / OR / NAND / NOR, RowClone staging, Frac) on a :class:`BankSim`
  subarray pair, handling operand staging, reference-row initialization,
  half-row (open-bitline) data layout and result extraction.
* :class:`CostModel` — DDR4 command-level latency/energy of each logical op
  (the paper's motivation quantified: in-DRAM ops move no data over the bus).

Data layout: a logical PuD *word* is ``shared_w = row_bits/2`` bits wide
(footnote 6: inter-subarray ops compute on half a row).  Words on the
compute (R_L) side occupy even columns; on the reference (R_F) side, odd
columns.  ``PudIsa`` packs/unpacks transparently.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache

import numpy as np

from .. import tracing
from . import decoder as DEC
from .analog import ALL_OPS, _base_op
from .device import (ENERGY_PJ, ModuleConfig, get_module, timings_for,
                     VIOLATED_TRAS_NS, VIOLATED_TRP_NS)
from .simulator import BankSim, CapabilityError, FusedGeometryError, PerBank


# ---------------------------------------------------------------------------
# Pair inventory
# ---------------------------------------------------------------------------
class PairInventory:
    """All (R_F row, R_L row) pairs per activation type for a subarray pair.

    Built once per (module, seed) by evaluating the decoder hash over the
    full address cross product — the software twin of the paper's 409,600-
    combination reverse-engineering sweep.
    """

    def __init__(self, module: ModuleConfig, *, seed: int = 0):
        self.module = module
        self.seed = seed
        n = module.geometry.rows_per_subarray
        pairs: dict[tuple[int, int], list[tuple[int, int]]] = {}
        # vectorized category per pair (mirrors decoder.coverage)
        M = np.uint64(0xFFFFFFFFFFFFFFFF)
        rf = np.arange(n, dtype=np.uint64)[:, None]
        rl = np.arange(n, dtype=np.uint64)[None, :]
        with np.errstate(over="ignore"):
            x = (np.uint64(seed) * np.uint64(0x9E3779B97F4A7C15) + rf)
            for sh, mul in ((30, 0xBF58476D1CE4E5B9), (27, 0x94D049BB133111EB)):
                x = ((x ^ (x >> np.uint64(sh))) * np.uint64(mul)) & M
            x ^= x >> np.uint64(31)
            y = (rl * np.uint64(0xD6E8FEB86659FD93)) & M
            h = x ^ y
            for sh, mul in ((30, 0xBF58476D1CE4E5B9), (27, 0x94D049BB133111EB)):
                h = ((h ^ (h >> np.uint64(sh))) * np.uint64(mul)) & M
            h ^= h >> np.uint64(31)
        u = (h >> np.uint64(11)).astype(np.float64) / float(1 << 53)
        cum, cats = DEC._category_table(module.max_simultaneous_rows,
                                        module.supports_n2n)
        idx = np.searchsorted(cum, u)
        for i, cat in enumerate(cats):
            fs, ls = np.nonzero(idx == i)
            pairs.setdefault(cat, []).extend(
                zip(fs.tolist(), ls.tolist(), strict=True))
        self._pairs = {k: np.asarray(v, dtype=np.int64)
                       for k, v in pairs.items()}

    def pairs(self, n_rf: int, n_rl: int) -> np.ndarray:
        """(P, 2) array of (R_F, R_L) rows realizing n_rf:n_rl activation."""
        return self._pairs.get((n_rf, n_rl), np.zeros((0, 2), dtype=np.int64))

    def choose(self, n_rf: int, n_rl: int, k: int = 0) -> tuple[int, int]:
        ps = self.pairs(n_rf, n_rl)
        if len(ps) == 0:
            raise CapabilityError(
                f"module {self.module.name} has no {n_rf}:{n_rl} pairs")
        rf, rl = ps[k % len(ps)]
        return int(rf), int(rl)

    def coverage(self, n_rf: int, n_rl: int) -> float:
        n = self.module.geometry.rows_per_subarray
        return len(self.pairs(n_rf, n_rl)) / float(n * n)


@lru_cache(maxsize=16)
def _inventory(module_name: str, seed: int) -> PairInventory:
    with tracing.span("isa.inventory"):
        return PairInventory(get_module(module_name), seed=seed)


def inventory_for(module: ModuleConfig, seed: int = 0) -> PairInventory:
    return _inventory(module.name, seed)


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------
#: plan-search objectives: what the scheduler's dup-vs-spill gates
#: minimize.  ``energy`` (the default) gates on pJ, ``latency`` on the
#: per-bank serial ns of the same log-exact command constants.
OBJECTIVES = ("energy", "latency")


def metric_index(objective: str) -> int:
    """Index of one objective's metric in the ``log_*`` (time, energy)
    twin tuples: 0 picks ``time_ns`` for ``latency``, 1 ``energy_pj``
    for ``energy``."""
    if objective not in OBJECTIVES:
        raise ValueError(f"unknown objective {objective!r}; "
                         f"expected one of {OBJECTIVES}")
    return 0 if objective == "latency" else 1


@dataclass
class OpCost:
    time_ns: float = 0.0
    energy_pj: float = 0.0
    commands: int = 0
    bus_bytes: int = 0           # data moved over the DDR bus (PuD avoids it)

    def __add__(self, o: "OpCost") -> "OpCost":
        return OpCost(self.time_ns + o.time_ns, self.energy_pj + o.energy_pj,
                      self.commands + o.commands, self.bus_bytes + o.bus_bytes)

    def scaled(self, k: float) -> "OpCost":
        return OpCost(self.time_ns * k, self.energy_pj * k,
                      int(self.commands * k), int(self.bus_bytes * k))

    def metric(self, objective: str = "energy") -> float:
        """This cost's scalar under one plan-search objective."""
        return self.time_ns if metric_index(objective) == 0 \
            else self.energy_pj


class CostModel:
    """DDR4 command-sequence costs of logical PuD ops (per bank).

    All in-DRAM ops are row-granular: one op processes ``shared_w`` bits
    (half a row per chip; x8 chips in lock-step process 8x that per rank).

    The ``log_*`` twins reproduce the exact per-command constants the
    simulator books into ``BankSim.log``, which is what lets a static
    :class:`~repro.core.compiler.ResidentPlan` predict the measured
    command log to the float — and what adjudicates the scheduler's
    duplication-vs-spill decisions (bus movement dominates energy at the
    native row width, so in-bank APAs usually win):

    >>> cm = CostModel()                      # native 8192-bit rows
    >>> spill = cm.log_read()[1] + cm.log_write()[1] \\
    ...     + cm.io_adjustment(2)[1]          # RD + WR + off-chip bursts
    >>> dup = (3 * cm.log_rowclone()[1] + cm.log_frac()[1]
    ...        + cm.log_apa(4)[1])            # all-in-bank 2-input dual op
    >>> dup < spill
    True
    """

    def __init__(self, module: ModuleConfig | None = None, *,
                 row_bits: int | None = None):
        self.module = module or get_module()
        self.t = timings_for(self.module)
        #: geometry override for sims built with a non-default row width
        #: (``BankSim(row_bits=...)``); None = the module's native row
        self.row_bits = row_bits or self.module.geometry.row_bits

    def _apa(self, n_rows: int, first_restored: bool) -> OpCost:
        t = self.t
        t_first = t.tRAS if first_restored else VIOLATED_TRAS_NS
        return OpCost(t_first + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                      n_rows * ENERGY_PJ["act"] + 2 * ENERGY_PJ["pre"], 3, 0)

    def rowclone(self) -> OpCost:
        t = self.t
        return OpCost(t.tRAS + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                      2 * ENERGY_PJ["act"] + 2 * ENERGY_PJ["pre"], 3, 0)

    def frac(self) -> OpCost:
        t = self.t
        return OpCost(2 * (VIOLATED_TRAS_NS + t.tRP),
                      2 * (ENERGY_PJ["act"] + ENERGY_PJ["pre"]), 4, 0)

    def write_row(self) -> OpCost:
        t = self.t
        bts = self.row_bits // 8
        n_bursts = max(bts // 64, 1)
        return OpCost(t.tRCD + t.tWR + t.tRP + n_bursts * 4 * t.tCK,
                      ENERGY_PJ["act"] + ENERGY_PJ["pre"]
                      + n_bursts * (ENERGY_PJ["wr_per_64B"] + ENERGY_PJ["io_per_64B"]),
                      2 + n_bursts, bts)

    def read_row(self) -> OpCost:
        t = self.t
        bts = self.row_bits // 8
        n_bursts = max(bts // 64, 1)
        return OpCost(t.tRCD + t.tCL + t.tRP + n_bursts * 4 * t.tCK,
                      ENERGY_PJ["act"] + ENERGY_PJ["pre"]
                      + n_bursts * (ENERGY_PJ["rd_per_64B"] + ENERGY_PJ["io_per_64B"]),
                      2 + n_bursts, bts)

    def boolean(self, n: int, *, staged: bool = True,
                ref_cached: bool = True) -> OpCost:
        """N-input AND/OR/NAND/NOR.

        staged: operands already reside in the compute block (the compiler
        RowClones them in; counted separately).  ref_cached: the N-1 constant
        reference rows persist across ops; only the Frac row is refreshed.
        """
        c = self._apa(2 * n, first_restored=False)
        c = c + self.frac()                      # Frac re-store each op
        if not ref_cached:
            c = c + self.write_row().scaled(n - 1)
        if not staged:
            c = c + self.rowclone().scaled(n)
        return c

    def op_not(self, n_dst: int = 1) -> OpCost:
        return self._apa(1 + n_dst, first_restored=True)

    def cpu_baseline(self, n: int, rows: int = 1) -> OpCost:
        """Processor-centric baseline: read N operand rows over the bus,
        compute on CPU, write one result row back."""
        c = self.read_row().scaled(n * rows) + self.write_row().scaled(rows)
        bts = self.row_bits // 8
        c.energy_pj += n * rows * (bts / 64.0) * ENERGY_PJ["cpu_op_per_64B"]
        return c

    # ---- command-log twins (measured-cost reconciliation) --------------
    # ``BankSim.log`` books each DDR4 command at *on-die* cost (no off-chip
    # IO terms).  These methods reproduce the exact per-command (time_ns,
    # energy_pj) constants the simulator logs, so a static
    # ``compiler.ResidentPlan`` can predict the measured command log to the
    # float — the reconciliation contract tests/test_scheduler.py enforces.
    def _n_bursts(self) -> int:
        return self.row_bits // 512   # sim-log convention (0 for tiny rows)

    def log_write(self) -> tuple[float, float]:
        t = self.t
        return (t.tRCD + t.tWR + t.tRP,
                ENERGY_PJ["act"] + ENERGY_PJ["pre"]
                + self._n_bursts() * ENERGY_PJ["wr_per_64B"])

    def log_read(self) -> tuple[float, float]:
        t = self.t
        return (t.tRCD + t.tCL + t.tRP,
                ENERGY_PJ["act"] + ENERGY_PJ["pre"]
                + self._n_bursts() * ENERGY_PJ["rd_per_64B"])

    def log_rowclone(self) -> tuple[float, float]:
        t = self.t
        return (t.tRAS + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                2 * ENERGY_PJ["act"] + 2 * ENERGY_PJ["pre"])

    def log_frac(self) -> tuple[float, float]:
        t = self.t
        return (2 * (VIOLATED_TRAS_NS + t.tRP),
                2 * (ENERGY_PJ["act"] + ENERGY_PJ["pre"]))

    def log_apa(self, n_acts: int, *,
                first_restored: bool = False) -> tuple[float, float]:
        t = self.t
        t_first = t.tRAS if first_restored else VIOLATED_TRAS_NS
        return (t_first + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                n_acts * ENERGY_PJ["act"] + 2 * ENERGY_PJ["pre"])

    def io_adjustment(self, io_rows: int) -> tuple[float, float, int]:
        """Off-chip burst time/energy + bus bytes for ``io_rows`` WR/RD
        rows — the same per-row terms ``PudEngine._account_sim_log`` adds
        on top of the on-die command log."""
        nb = max(self.row_bits // 8 // 64, 1)
        return (io_rows * nb * 4 * self.t.tCK,
                io_rows * nb * ENERGY_PJ["io_per_64B"],
                io_rows * (self.row_bits // 8))


# ---------------------------------------------------------------------------
# The ISA executor
# ---------------------------------------------------------------------------
@dataclass
class IsaStats:
    ops: int = 0
    apas: int = 0
    rowclones: int = 0
    fracs: int = 0
    writes: int = 0
    reads: int = 0
    #: polarity spills: host RD round-trips of *resident* registers the
    #: resident executor had to take (needed polarity not on the compute
    #: side) — the quantity the compile-time scheduler minimizes
    spills: int = 0
    #: producer duplications: extra in-bank APAs the scheduled planner
    #: took *instead of* polarity spills (dual De Morgan re-execution)
    duplications: int = 0
    cost: OpCost = field(default_factory=OpCost)


class PudIsa:
    """Executes logical PuD instructions on one subarray pair of a BankSim.

    Convention: R_F side = ``f_sub`` (reference rows for Boolean ops, source
    row for NOT); R_L side = ``l_sub = f_sub + 1`` (compute rows / NOT
    destinations).  Logical words are ``shared_w`` bits.

    Planning runs over the sim's bank axis: each bank walks its own pair
    inventory and cursor, and the banks must agree on the activation
    geometry.  On a one-bank sim the row handles are ints and the
    activation the decoder's; ``repro.core.fused.FusedPudIsa`` hands out
    per-bank handles instead.
    """

    def __init__(self, sim: BankSim, *, f_sub: int = 0,
                 l_sub: int | None = None, bank: int = 0):
        self.sim = sim
        #: device address on a multi-bank array (BankArray): which bank
        #: this ISA's subarray pair lives in.  Purely an identity axis —
        #: banks are independent chips — used by the engine's round-robin
        #: dispatch and the per-bank OffloadReport attribution.
        self.bank = bank
        self.f_sub = f_sub
        self.l_sub = f_sub + 1 if l_sub is None else l_sub
        if abs(self.f_sub - self.l_sub) != 1:
            raise ValueError("PudIsa needs neighboring subarrays")
        #: each bank's pair inventory (its chip's decoder map)
        self.invs = [inventory_for(sim.module, s) for s in sim.bank_seeds]
        self.inv = self.invs[0]
        self.cost_model = CostModel(sim.module, row_bits=sim.geom.row_bits)
        self.stats = IsaStats()
        lo = min(self.f_sub, self.l_sub)
        j = np.arange(sim.shared_w)
        self._f_cols = 2 * j + 1 if self.f_sub == lo else 2 * j
        self._l_cols = 2 * j + 1 if self.l_sub == lo else 2 * j
        # same column sets as contiguous storage-layout slices (see
        # BankSim stripe-major layout)
        _lo, self._f_sl, self._l_sl = sim._col_slices(self.f_sub, self.l_sub)
        #: each bank's pair-walk cursors, keyed by (n_rf, n_rl)
        self._bank_cursors: list[dict] = [{} for _ in self.invs]
        #: the most recent ResidentPlan executed through this ISA (set by
        #: compiler._run_sim_resident; None until a resident run happens)
        self.last_resident_plan = None

    @property
    def _pair_cursor(self) -> dict[tuple[int, int], int]:
        """Bank 0's pair-walk cursors."""
        return self._bank_cursors[0]

    # ---------------- word packing ----------------
    @property
    def width(self) -> int:
        return self.sim.shared_w

    @property
    def trials(self) -> int | None:
        """Trial-batch size of the underlying sim (None = scalar API)."""
        return self.sim.trials

    def _pack(self, bits: np.ndarray, side: str) -> np.ndarray:
        """Word -> full row.  ``bits`` is (w,) or, on a batched sim, (T, w);
        the packed row keeps any leading trial axis."""
        cols = self._f_cols if side == "f" else self._l_cols
        bits = np.asarray(bits, dtype=np.float32)
        row = np.zeros((*bits.shape[:-1], self.sim.geom.row_bits),
                       dtype=np.float32)
        row[..., cols] = bits
        return row

    def _stack_words(self, words) -> np.ndarray:
        """Stack operand words along a row axis: (n, w), or (T, n, w) when
        any word carries a trial axis (others broadcast).  An ndarray input
        of shape (n, w) or (n, T, w) is used as-is (no copy)."""
        if isinstance(words, np.ndarray):
            return np.moveaxis(words, 0, -2) if words.ndim == 3 else words
        words = [np.asarray(w) for w in words]
        if any(w.ndim == 2 for w in words):
            t = max(w.shape[0] for w in words if w.ndim == 2)
            words = [np.broadcast_to(w, (t, w.shape[-1])) for w in words]
        return np.stack(words, axis=-2)

    def _unpack(self, sub: int, row: int, side: str) -> np.ndarray:
        cols = self._f_cols if side == "f" else self._l_cols
        full = self.sim.read_row(sub, row)
        self.stats.reads += 1
        self.stats.cost = self.stats.cost + self.cost_model.read_row()
        return full[..., cols]

    def _result_word(self, sub: int, row: int, side: str) -> np.ndarray:
        """Digital result word of one physical row: (w,), or (T, w) batched.

        Counted as a host readout (RD over the bus): the staged executor
        pays it per instruction, the resident executor only per program
        output / spill."""
        sl = self._f_sl if side == "f" else self._l_sl
        self.stats.reads += 1
        self.stats.cost = self.stats.cost + self.cost_model.read_row()
        with tracing.span("isa.readout"):
            return self.sim.read_shared_word(sub, row, sl)

    def read_result_word(self, sub: int, row: int) -> np.ndarray:
        """Public result readout for row handles (resident executor)."""
        side = "f" if sub == self.f_sub else "l"
        return self._result_word(sub, row, side)

    def clone_word(self, sub: int, src: int, dst: int) -> None:
        """In-bank RowClone of one row (the resident executor's data-move
        primitive): no bus traffic, 2 ACTs.  A no-op when src == dst."""
        if src == dst:
            return
        with tracing.span("resident.rowclone"):
            self.sim.rowclone(sub, src, dst)
        self.stats.rowclones += 1
        self.stats.cost = self.stats.cost + self.cost_model.rowclone()

    def fill_const_row(self, sub: int, row: int, value: int) -> None:
        """Host-write one all-``value`` row (resident const-row staging)."""
        cols = self._f_sl if sub == self.f_sub else self._l_sl
        self.sim.fill_rows(sub, [row], float(value), cols=cols)
        self.stats.writes += 1
        self.stats.cost = self.stats.cost + self.cost_model.write_row()

    def stage_word(self, sub: int, row: int, bits) -> None:
        """Host-write one word into one row (resident register staging)."""
        cols = self._f_sl if sub == self.f_sub else self._l_sl
        self.sim.write_cols_multi(sub, [row], cols,
                                  np.asarray(bits,
                                             dtype=np.float32)[..., None, :])
        self.stats.writes += 1
        self.stats.cost = self.stats.cost + self.cost_model.write_row()

    def write_word(self, sub: int, row: int, bits: np.ndarray) -> None:
        side = "f" if sub == self.f_sub else "l"
        self.sim.write_row(sub, row, self._pack(bits, side))
        self.stats.writes += 1
        self.stats.cost = self.stats.cost + self.cost_model.write_row()

    def read_word(self, sub: int, row: int) -> np.ndarray:
        side = "f" if sub == self.f_sub else "l"
        return self._unpack(sub, row, side)

    # ---------------- pair selection ----------------
    def _next_pair(self, n_rf: int, n_rl: int,
                   bank: int = 0) -> tuple[int, int]:
        """Deterministic but *scrambled* pair iteration: consecutive ops use
        pairs spread uniformly over the subarray (and hence over the
        distance regions), matching the paper's row-sweeping protocol.
        Each bank walks its own inventory, keyed by its own seed."""
        key = (n_rf, n_rl)
        cur = self._bank_cursors[bank]
        k = cur.get(key, 0)
        cur[key] = k + 1
        inv = self.invs[bank]
        n_pairs = max(len(inv.pairs(n_rf, n_rl)), 1)
        scrambled = DEC._mix64(k * 0x9E3779B97F4A7C15
                               + self.sim.bank_seeds[bank])
        return inv.choose(n_rf, n_rl, scrambled % n_pairs)

    def _plan_pairs(self, n_rf: int, n_rl: int, pair,
                    pair_index: int | None) -> list[tuple[int, int]]:
        """Each bank's (R_F, R_L) rows: ``pair`` pins them (one pair for
        every bank, or one per bank), ``pair_index`` picks from each
        bank's inventory, and by default each bank walks on."""
        n_banks = self.sim.n_banks
        if pair is not None:
            pair = list(pair.vals if isinstance(pair, PerBank) else pair)
            if len(pair) == 2 and all(
                    isinstance(x, (int, np.integer)) for x in pair):
                return [(int(pair[0]), int(pair[1]))] * n_banks
            if len(pair) != n_banks:
                raise ValueError(f"need one (rf, rl) pair per bank "
                                 f"({n_banks}), got {len(pair)}")
            return [(int(rf), int(rl)) for rf, rl in pair]
        if pair_index is not None:
            return [inv.choose(n_rf, n_rl, pair_index) for inv in self.invs]
        return [self._next_pair(n_rf, n_rl, b) for b in range(n_banks)]

    def _activation(self, bank: int, pair: tuple[int, int]) -> DEC.Activation:
        return DEC.activation_pattern(self.sim.module, *pair,
                                      seed=self.sim.bank_seeds[bank])

    def _handles(self, pairs: list, acts: list):
        """-> (rf, rl, activation) of a planned op: bank 0's row ints and
        decoder Activation (a one-bank ISA)."""
        return (*pairs[0], acts[0])

    @staticmethod
    def _one_for_all(vals: list, what: str):
        """The value every bank agrees on."""
        if len(set(vals)) != 1:
            raise FusedGeometryError(f"{what} differs across banks: {vals}")
        return vals[0]

    # ---------------- logical ops ----------------
    def not_activation(self, n_dst: int) -> int:
        """R_F-side row count for a NOT with ``n_dst`` destinations: the
        smallest available (least drive load, Obs. 5)."""
        n_rfs = []
        for inv in self.invs:
            for n_rf in (max(n_dst // 2, 1), n_dst):
                if len(inv.pairs(n_rf, n_dst)):
                    n_rfs.append(n_rf)
                    break
            else:
                raise CapabilityError(f"no activation with {n_dst} dst rows")
        return self._one_for_all(n_rfs, "NOT source-row count")

    def plan_not(self, n_dst: int = 1, *, pair_index: int | None = None,
                 pair=None):
        """Pair selection for a NOT: -> (rf, rl, activation)."""
        n_rf = self.not_activation(n_dst)
        pairs = self._plan_pairs(n_rf, n_dst, pair, pair_index)
        acts = [self._activation(b, p) for b, p in enumerate(pairs)]
        if pair is None and pair_index is None:
            # sequential-activation modules (Samsung) miss on ~2/3 of the
            # address pairs the inventory lists: sweep on, like the paper
            for b in range(len(acts)):
                for _ in range(63):
                    if acts[b].n_rf:
                        break
                    pairs[b] = self._next_pair(n_rf, n_dst, b)
                    acts[b] = self._activation(b, pairs[b])
        for b, act in enumerate(acts):
            if act.n_rf == 0:
                raise CapabilityError(
                    f"address pair {pairs[b]} yields no simultaneous "
                    f"activation on {self.sim.module.name} (bank {b})")
        return self._handles(pairs, acts)

    def exec_not(self, rf: int, rl: int, act: DEC.Activation,
                 source) -> tuple[int, int]:
        """NOT with an explicit source: ``("write", bits)`` host-stages the
        word into every activated R_F row; ``("clone", f_row)`` RowClones a
        resident R_F-side row instead (no bus traffic).  Returns the
        (result l-row, restored-source f-row) handles; the result row holds
        the complement, the f rows the restored source."""
        kind, payload = source
        if kind == "clone":
            for r in act.rows_f:
                self.clone_word(self.f_sub, int(payload), int(r))
        else:
            with tracing.span("isa.stage"):
                self.sim.write_cols_multi(
                    self.f_sub, act.rows_f, self._f_sl,
                    np.asarray(payload, dtype=np.float32)[..., None, :])
            self.stats.writes += act.n_rf
            self.stats.cost = self.stats.cost \
                + self.cost_model.write_row().scaled(act.n_rf)
        self.sim.apa(self.sim.global_addr(self.f_sub, rf),
                     self.sim.global_addr(self.l_sub, rl),
                     first_act_restored=True)
        self.stats.apas += 1
        self.stats.ops += 1
        self.stats.cost = self.stats.cost + self.cost_model.op_not(act.n_rl)
        return act.rows_l[0], act.rows_f[0]

    def op_not(self, bits: np.ndarray, *, n_dst: int = 1,
               pair_index: int | None = None,
               pair: tuple[int, int] | None = None) -> np.ndarray:
        """In-DRAM NOT: returns the (noisy) complement of ``bits``.

        ``bits`` is (w,) or, on a batched sim, (T, w) for per-trial inputs.
        ``pair`` pins the exact (R_F, R_L) rows (stratified row sweeps);
        ``pair_index`` picks from the inventory; default iterates scrambled.
        """
        rf, rl, act = self.plan_not(n_dst, pair_index=pair_index, pair=pair)
        res_row, _src_row = self.exec_not(rf, rl, act, ("write", bits))
        return self._result_word(self.l_sub, res_row, "l")

    def plan_nary(self, op: str, n: int, *, pair_index: int | None = None,
                  pair=None):
        """Capability checks + pair selection for an n-ary Boolean op.

        -> (n_hw, rf, rl, activation): the decoder only expresses
        power-of-two N:N activations, so ``n_hw >= n`` is the hardware
        fan-in (the caller pads with identity operands up to it)."""
        op = op.lower()
        if op not in ALL_OPS:
            raise ValueError(f"unknown op {op}")
        if n < 2:
            raise ValueError("n-ary op needs >= 2 operands")
        if n > self.sim.module.max_inputs:
            raise CapabilityError(
                f"{n}-input ops exceed module capability "
                f"({self.sim.module.max_inputs})")
        n_hws = []
        for inv in self.invs:
            n_hw = n
            while n_hw <= 16 and len(inv.pairs(n_hw, n_hw)) == 0:
                n_hw += n_hw % 2 or 1   # next even, then doubles via pairs check
            if len(inv.pairs(n_hw, n_hw)) == 0:
                raise CapabilityError(f"no >= {n}:{n} pairs on this module")
            n_hws.append(n_hw)
        n_hw = self._one_for_all(n_hws, "hardware fan-in")
        pairs = self._plan_pairs(n_hw, n_hw, pair, pair_index)
        acts = [self._activation(b, p) for b, p in enumerate(pairs)]
        for b, a in enumerate(acts):
            if a.n_rf != n_hw or a.n_rl != n_hw:
                raise FusedGeometryError(
                    f"pair {pairs[b]} activates {a.n_rf}:{a.n_rl} on bank "
                    f"{b}, wanted {n_hw}:{n_hw}")
        return (n_hw, *self._handles(pairs, acts))

    def exec_nary(self, op: str, rf: int, rl: int, act: DEC.Activation,
                  sources, *, ref_row: int | None = None,
                  random_pattern: bool = True) -> tuple[int, int]:
        """N-ary Boolean APA with per-operand staging sources.

        ``sources`` is one entry per activated compute row:
        ``("write", bits)`` host-writes the word, ``("clone", l_row)``
        RowClones a resident row (no bus traffic).  Alternatively the
        whole compute block stages in one zero-copy strided scatter by
        passing ``("write_stack", operands)`` — operands as accepted by
        :meth:`_stack_words` (the staged executor's hot path).  The
        reference block is host-filled when ``ref_row`` is None, else
        RowCloned from that resident constant row.  Returns (compute
        l-row, reference f-row) handles: after the APA the l row holds
        the base AND/OR result and the f row its complement (NAND/NOR).
        """
        n = act.n_rf
        base, _is_ref = _base_op(op.lower())
        # staging: everything before the APA (which ``sim.apa`` times)
        with tracing.span("isa.stage"):
            # reference block: N-1 constants + one Frac row (§6.1.2)
            if ref_row is None:
                const = 1.0 if base == "and" else 0.0
                self.sim.fill_rows(self.f_sub, act.rows_f[:-1], const,
                                   cols=self._f_sl)
                self.stats.writes += n - 1
                # keep stats.cost consistent with the WR commands just issued
                # (clone_word charges the resident path's ref staging likewise)
                self.stats.cost = self.stats.cost \
                    + self.cost_model.write_row().scaled(n - 1)
            else:
                for r in act.rows_f[:-1]:
                    self.clone_word(self.f_sub, int(ref_row), int(r))
            self.sim.frac_row(self.f_sub, act.rows_f[-1])
            self.stats.fracs += 1
            # compute block: clones in place, host words in one strided scatter
            if isinstance(sources, tuple) and sources[0] == "write_stack":
                stack = self._stack_words(sources[1])
                n_wr = stack.shape[-2]
                self.sim.write_cols_multi(self.l_sub, act.rows_l[:n_wr],
                                          self._l_sl, stack)
                self.stats.writes += n_wr
            else:
                wr_rows, wr_bits = [], []
                for i, (kind, payload) in enumerate(sources):
                    if kind == "clone":
                        self.clone_word(self.l_sub, int(payload),
                                        int(act.rows_l[i]))
                    else:
                        wr_rows.append(int(act.rows_l[i]))
                        wr_bits.append(payload)
                if wr_rows:
                    self.sim.write_cols_multi(self.l_sub, wr_rows, self._l_sl,
                                              self._stack_words(wr_bits))
                    self.stats.writes += len(wr_rows)
                n_wr = len(wr_rows)
        self.sim.op_boolean(op, self.sim.global_addr(self.f_sub, rf),
                            self.sim.global_addr(self.l_sub, rl),
                            random_pattern=random_pattern)
        self.stats.apas += 1
        self.stats.ops += 1
        self.stats.cost = self.stats.cost + self.cost_model.boolean(n) \
            + self.cost_model.write_row().scaled(n_wr)
        return act.rows_l[0], act.rows_f[0]

    def nary_op(self, op: str, operands: list[np.ndarray], *,
                pair_index: int | None = None,
                pair: tuple[int, int] | None = None,
                random_pattern: bool = True) -> np.ndarray:
        """Many-input AND/OR/NAND/NOR over equal-width operand words.

        Operands are (w,) or, on a batched sim, (T, w) for per-trial inputs
        (the result then carries the same leading trial axis).  The decoder
        only expresses power-of-two N:N activations; other fan-ins are
        padded with identity operands (all-1 rows for AND, all-0 for OR) up
        to the next supported N.
        """
        n = len(operands)
        n_hw, rf, rl, act = self.plan_nary(op, n, pair_index=pair_index,
                                           pair=pair)
        base, is_ref = _base_op(op.lower())
        if n_hw != n:
            ident = np.full(self.width, 1 if base == "and" else 0,
                            dtype=np.uint8)
            operands = list(operands) + [ident] * (n_hw - n)
        res_l, res_f = self.exec_nary(op, rf, rl, act,
                                      ("write_stack", operands),
                                      random_pattern=random_pattern)
        if is_ref:   # NAND/NOR lands in the reference subarray rows
            return self._result_word(self.f_sub, res_f, "f")
        return self._result_word(self.l_sub, res_l, "l")

    # composite ops (functional completeness in action) ------------------
    def op_xor(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """XOR from 4 NANDs: the classic functionally-complete construction."""
        n1 = self.nary_op("nand", [a, b])
        n2 = self.nary_op("nand", [a, n1])
        n3 = self.nary_op("nand", [b, n1])
        return self.nary_op("nand", [n2, n3])

    def op_maj3(self, a: np.ndarray, b: np.ndarray, c: np.ndarray) -> np.ndarray:
        ab = self.nary_op("and", [a, b])
        a_or_b = self.nary_op("or", [a, b])
        c_ab = self.nary_op("and", [c, a_or_b])
        return self.nary_op("or", [ab, c_ab])
