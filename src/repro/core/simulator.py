"""Functional + analog Monte-Carlo simulator of a DRAM bank.

Executes the paper's command sequences at cell granularity:

* ``ACT -> (wait tRAS) -> PRE -> RD/WR`` — standard operation,
* ``ACT R_F -> PRE -> ACT R_L`` (APA) with violated timings — simultaneous
  multi-row activation in *neighboring* subarrays (§4); which rows activate
  is decided by the :mod:`repro.core.decoder` model,
* RowClone (sequential same-subarray activation, §2.2),
* Frac (store VDD/2 in a row, FracDRAM [38]),
* the NOT protocol (§5: first ACT fully restores the source before PRE ->
  ACT dst) and the Boolean-op protocol (§6: both ACTs violated, reference
  subarray first).

Open-bitline geometry (footnote 6): the sense-amp stripe between neighboring
subarrays ``lo`` / ``lo+1`` hosts one SA per *shared column position*
``j``: terminal A connects to column ``2j+1`` of subarray ``lo`` and
terminal B to column ``2j`` of subarray ``lo+1``.  Inter-subarray operations
therefore compute on half a row; the remaining columns of an activated row
see a plain same-subarray (dis)charge and are restored through their own
stripe (a MAJ-against-VDD/2, which is what prior in-DRAM-compute works use).

Error injection follows ``repro.core.analog``: each SA carries a *static*
latent offset (two per-SA uniforms mapped through the op-context mixture, so
a given cell behaves consistently across trials — the paper's bimodal
box-plot populations and Obs. 3), plus per-trial noise and the
activation-failure floor.  Cell-averaged Monte-Carlo success converges to the
closed-form ``analog.boolean_success`` (tested in tests/test_simulator.py).

Trial batching
--------------
``BankSim(trials=T)`` simulates ``T`` independent Monte-Carlo repetitions of
the *same* command sequence in one pass: cell state is stored as
``(T, rows, row_bits)`` and every command (``apa``, ``op_not``,
``op_boolean``, RowClone, Frac, WR/RD) broadcasts across the leading trial
axis.  This mirrors the paper's measurement protocol — each (row pair, input
pattern) configuration is repeated many times — and replaces T Python-level
episodes with one vectorized one (the ~10-100x hot path of
``repro.core.charz``).  Static per-SA offsets are shared across trials (they
model process variation of one physical chip); per-trial noise, floor flips
and coins are drawn ``(T, w)`` at once.  With ``trials=None`` (default) the
simulator runs a single trial and keeps the seed-compatible scalar API:
identical RNG consumption, identical results, rows returned as 1-D arrays.

Seed roles
----------
``seed`` is the *chip identity*: it fixes the row-decoder hash (which
address pairs activate) and the static per-SA offset latents.  Per-trial
noise draws come from an independent stream keyed by ``noise_seed``
(default: ``seed``).  Callers that split one workload over several
command-sequence episodes on the *same* chip (e.g. the chunk-blocked
``repro.pud.engine`` dram backend) derive a fresh ``noise_seed`` per
episode via :meth:`reseed_noise`, so error patterns never repeat across
blocks while the chip's decoder map and static offsets stay put.

Resolve backends
----------------
The sense-amp comparator of the Boolean-op protocol (``_resolve``) is
pluggable via ``resolve_backend``:

* ``"numpy"`` — the in-process vectorized path (default on CPU),
* ``"pallas"`` — the fused charge-share + sense-amp kernel
  ``repro.kernels.ops.senseamp_resolve`` (Mosaic on TPU, interpret mode on
  CPU), fed the *same* RNG draws as the numpy path,
* ``"auto"`` — ``"pallas"`` when jax's default backend is a TPU, else
  ``"numpy"``.

Both backends draw identical noise/floor randomness per command, so they
agree except where float32 re-association flips a sample sitting exactly
on the comparator threshold (documented tolerance: <= 0.1% of bits on
analog-noise scales; tested in tests/test_executor.py).  The backend only
affects the ``error_model="analog"`` Boolean path — NOT's driven-restore
model and the ideal/mean models are backend-independent.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from . import analog as A
from . import decoder as DEC
from .analog import AnalogParams
from .device import (ActivationSupport, DRAMTimings, ModuleConfig,
                     SubarrayGeometry, get_module, timings_for, ENERGY_PJ,
                     VIOLATED_TRAS_NS, VIOLATED_TRP_NS)

# fraction of the Gaussian sigma that is static (per-cell) vs per-trial
STATIC_SPLIT = 0.8

#: per-cell flip probability of one same-subarray RowClone under the analog
#: error model.  RowClone's sequential ACT -> PRE -> ACT fully restores the
#: source before the destination ACT, so the copy is near-deterministic on
#: real chips (RowClone [51]; PULSAR reports no in-subarray copy errors) —
#: but it is not *exactly* free, and resident-register execution chains many
#: of them, so the simulator models a small independent failure floor.
ROWCLONE_FAIL_P = 2e-6


def _norm_ppf(q):
    """Acklam's inverse normal CDF approximation (max abs err ~1.15e-9)."""
    q = np.asarray(q, dtype=np.float64)
    a = (-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00)
    q = np.clip(q, 1e-12, 1 - 1e-12)
    out = np.empty_like(q)
    lo = q < 0.02425
    hi = q > 1 - 0.02425
    mid = ~(lo | hi)
    if np.any(mid):
        x = q[mid] - 0.5
        r = x * x
        out[mid] = ((((((a[0]*r+a[1])*r+a[2])*r+a[3])*r+a[4])*r+a[5])*x /
                    (((((b[0]*r+b[1])*r+b[2])*r+b[3])*r+b[4])*r+1))
    if np.any(lo):
        r = np.sqrt(-2*np.log(q[lo]))
        out[lo] = (((((c[0]*r+c[1])*r+c[2])*r+c[3])*r+c[4])*r+c[5]) / \
                  ((((d[0]*r+d[1])*r+d[2])*r+d[3])*r+1)
    if np.any(hi):
        r = np.sqrt(-2*np.log(1-q[hi]))
        out[hi] = -((((((c[0]*r+c[1])*r+c[2])*r+c[3])*r+c[4])*r+c[5]) /
                    ((((d[0]*r+d[1])*r+d[2])*r+d[3])*r+1))
    return out


@dataclass(frozen=True)
class LogEvent:
    """One logical command as recorded by :class:`CommandLog`.

    ``seq`` is a per-log monotonic issue index (command order survives the
    count aggregation of ``counts``); ``bank``/``sub`` identify the issuing
    bank and subarray (``sub = -1`` when the command has no single home
    subarray).  ``count`` repeats the command back-to-back — e.g. one WR
    event with ``count=3`` stages three rows."""

    seq: int
    cmd: str
    t_ns: float
    e_pj: float
    count: int
    bank: int
    sub: int


@dataclass
class CommandLog:
    """Per-command time/energy accounting (feeds the ISA cost model).

    Besides the aggregate time/energy/counts used by the cost model, the
    log keeps an ordered :class:`LogEvent` stream (issuing bank/subarray +
    monotonic sequence index) that the static timing linter
    (``repro.analysis.timing``) replays against DDR4 timing rules."""

    time_ns: float = 0.0
    energy_pj: float = 0.0
    counts: dict = field(default_factory=dict)
    events: list = field(default_factory=list)

    def add(self, cmd: str, t_ns: float, e_pj: float,
            count: int = 1, *, bank: int = 0, sub: int = -1) -> None:
        self.time_ns += t_ns * count
        self.energy_pj += e_pj * count
        self.counts[cmd] = self.counts.get(cmd, 0) + count
        self.events.append(LogEvent(len(self.events), cmd, t_ns, e_pj,
                                    count, bank, sub))

    def reset(self) -> None:
        self.time_ns = 0.0
        self.energy_pj = 0.0
        self.counts.clear()
        self.events.clear()


class BankSim:
    """One DRAM bank: lazily-allocated subarrays of float32 cell voltages."""

    def __init__(self, module: ModuleConfig | str | None = None, *,
                 row_bits: int | None = None, seed: int = 0,
                 params: AnalogParams | None = None, temp_c: float = 50.0,
                 error_model: str = "analog", trials: int | None = None,
                 track_unshared: bool = True, noise_seed: int | None = None,
                 resolve_backend: str = "auto",
                 rowclone_fail_p: float = ROWCLONE_FAIL_P,
                 bank: int = 0):
        self.module = (get_module(module) if isinstance(module, str)
                       else module or get_module())
        geom = self.module.geometry
        if row_bits is not None:
            geom = SubarrayGeometry(geom.subarrays_per_bank,
                                    geom.rows_per_subarray, row_bits)
        self.geom = geom
        self.timings: DRAMTimings = timings_for(self.module)
        self.params = params or A.DEFAULT_PARAMS
        self.temp_c = temp_c
        assert error_model in ("analog", "mean", "ideal", "none")
        self.error_model = error_model
        self.seed = seed
        #: bank index stamped on every CommandLog event (array position;
        #: purely log metadata — the sim itself is always one bank)
        self.bank = int(bank)
        #: independent per-trial noise stream (chip identity stays ``seed``)
        self.noise_seed = seed if noise_seed is None else int(noise_seed)
        if resolve_backend not in ("auto", "numpy", "pallas"):
            raise ValueError(f"unknown resolve backend {resolve_backend!r}")
        self.resolve_backend = resolve_backend
        #: per-cell RowClone flip probability (analog error model only)
        self.rowclone_fail_p = float(rowclone_fail_p)
        if trials is not None and trials < 1:
            raise ValueError(f"trials must be >= 1, got {trials}")
        #: None = legacy scalar API (rows are 1-D); int T = batched trials
        #: (rows carry a leading (T,) axis).  Internally state is always 3-D.
        self.trials = trials
        self._T = 1 if trials is None else int(trials)
        # float32 noise on the batched path (2x less bandwidth, stats-only);
        # float64 in scalar mode keeps bit-exact legacy RNG consumption.
        self._noise_dtype = np.float64 if trials is None else np.float32
        #: False skips the same-subarray MAJ restore of *non-shared*
        #: columns after an APA.  That state never feeds back into
        #: shared-column results (operand/reference rows are fully re-staged
        #: before every op), so word-level outputs follow the identical
        #: distribution.  The batched MC uses this; keep True when full-row
        #: snapshots must be cell-accurate.
        self.track_unshared = track_unshared
        self._subarrays: dict[int, np.ndarray] = {}
        self._rowmap: dict[int, np.ndarray] = {}
        self._nrows: dict[int, int] = {}
        self._static: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self._trial = 0
        # stripe-major internal column layout: storage position j < w holds
        # physical column 2j+1 (the lower-stripe shared set), position w+j
        # holds column 2j.  Shared-column access — the hot path — is then a
        # contiguous slab; physical order is materialized only on full-row
        # reads/writes.
        w = self.geom.row_bits // 2
        self._perm = np.concatenate([np.arange(self.geom.row_bits)[1::2],
                                     np.arange(self.geom.row_bits)[0::2]])
        self._invperm = np.empty(self.geom.row_bits, dtype=np.int64)
        self._invperm[self._perm] = np.arange(self.geom.row_bits)
        self.log = CommandLog()

    # ---------------- geometry helpers ----------------
    @property
    def shared_w(self) -> int:
        return self.geom.row_bits // 2

    @property
    def batched(self) -> bool:
        return self.trials is not None

    # ---------------- compact row-remapped cell storage ----------------
    # Physical row addresses map to densely-allocated slots of a
    # (T, slots, row_bits) buffer per subarray: a bank exposes 512 rows but
    # a Monte-Carlo run touches a few dozen, and dense slots keep the
    # trial-batched gathers/scatters contiguous instead of striding a
    # (T, 512, row_bits) arena.  Unwritten rows read as 0 V (cold cells).
    def _map_rows(self, sub: int, rows) -> np.ndarray:
        """Slot indices of physical rows, allocating slots on first touch."""
        if not 0 <= sub < self.geom.subarrays_per_bank:
            raise IndexError(f"subarray {sub} out of range")
        rows = np.atleast_1d(np.asarray(rows, dtype=np.int64))
        if rows.size and (rows.min() < 0
                          or rows.max() >= self.geom.rows_per_subarray):
            raise IndexError(f"row out of range in {rows}")
        rmap = self._rowmap.get(sub)
        if rmap is None:
            rmap = self._rowmap[sub] = np.full(
                self.geom.rows_per_subarray, -1, dtype=np.int64)
            self._nrows[sub] = 0
        idx = rmap[rows]
        fresh = idx < 0
        if np.any(fresh):
            new_rows = rows[fresh]
            start = self._nrows[sub]
            rmap[new_rows] = np.arange(start, start + new_rows.size)
            self._nrows[sub] = start + new_rows.size
            buf = self._subarrays.get(sub)
            cap = 0 if buf is None else buf.shape[1]
            if self._nrows[sub] > cap:
                new_cap = min(max(16, 2 * cap, self._nrows[sub]),
                              self.geom.rows_per_subarray)
                new_buf = np.zeros((self._T, new_cap, self.geom.row_bits),
                                   dtype=np.float32)
                if buf is not None:
                    new_buf[:, :cap] = buf
                self._subarrays[sub] = new_buf
            idx = rmap[rows]
        return idx

    def _row(self, sub: int, row: int) -> int:
        return int(self._map_rows(sub, row)[0])

    def recycle_rows(self) -> None:
        """Forget all row-slot assignments; slot buffers are kept and reused
        (contents become don't-care).  Safe whenever subsequent ops re-stage
        every row they read — the Monte-Carlo harness does this between
        activation-pair groups to keep the hot working set bounded by one
        op's row count instead of growing with every new pair."""
        for sub, rmap in self._rowmap.items():
            rmap.fill(-1)
            self._nrows[sub] = 0

    def _cells(self, sub: int) -> np.ndarray:
        """(T, slots, row_bits) backing buffer (slot order = first touch)."""
        if sub not in self._subarrays:
            self._map_rows(sub, [0])    # force allocation
        return self._subarrays[sub]

    def _arr(self, sub: int) -> np.ndarray:
        """Cell voltages in *physical* row order: (rows, row_bits) in scalar
        mode, (T, rows, row_bits) batched.  A materialized snapshot (the
        backing store is slot-compacted) — read-only debug/inspection aid."""
        out = np.zeros((self._T, self.geom.rows_per_subarray,
                        self.geom.row_bits), dtype=np.float32)
        rmap = self._rowmap.get(sub)
        if rmap is not None:
            live = np.nonzero(rmap >= 0)[0]
            out[:, live] = self._subarrays[sub][:, rmap[live]][
                ..., self._invperm]
        return out if self.batched else out[0]

    def _out(self, rows: np.ndarray) -> np.ndarray:
        """Strip the trial axis in legacy scalar mode."""
        return rows if self.batched else rows[0]

    def _static_latents(self, stripe: int) -> tuple[np.ndarray, np.ndarray]:
        """Two per-SA uniforms for the static offset mixture of a stripe."""
        if stripe not in self._static:
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, 0xC0FFEE, stripe]))
            self._static[stripe] = (rng.random(self.shared_w),
                                    rng.random(self.shared_w))
        return self._static[stripe]

    def _rng(self) -> np.random.Generator:
        self._trial += 1
        return np.random.default_rng(
            np.random.SeedSequence([self.noise_seed, 0x7A1A1, self._trial]))

    def reseed_noise(self, noise_seed: int) -> None:
        """Point subsequent per-trial noise draws at an independent stream.

        Chip identity — the decoder's activation map and the static per-SA
        offsets — stays tied to ``seed``; only the per-command noise/floor
        generators change.  The command counter restarts so the stream is a
        pure function of ``noise_seed`` (callers pass unique seeds, e.g.
        ``np.random.SeedSequence(seed).spawn`` children)."""
        self.noise_seed = int(noise_seed)
        self._trial = 0

    def _resolve_backend(self) -> str:
        """Effective resolve backend ('auto' settles on first use)."""
        if self.resolve_backend == "auto":
            import jax
            self.resolve_backend = \
                "pallas" if jax.default_backend() == "tpu" else "numpy"
        return self.resolve_backend

    def static_offsets(self, stripe: int, op: str, n: int, *,
                       random_pattern: bool = True,
                       speed_mts: int | None = None) -> np.ndarray:
        """Per-SA static offset [V] under an op context (see module doc)."""
        xi1, xi2 = self._static_latents(stripe)
        s, b, wp, wm = A.op_noise(
            op, n, self.params, temp_c=self.temp_c,
            random_pattern=random_pattern,
            speed_mts=speed_mts or self.module.speed_mts,
            mfr=self.module.manufacturer.value,
            density_gb=self.module.density_gb, die_rev=self.module.die_rev)
        comp = np.where(xi1 < wm, -1.0, np.where(xi1 > 1.0 - wp, 1.0, 0.0))
        return comp * b + STATIC_SPLIT * s * _norm_ppf(xi2)

    # ---------------- standard commands ----------------
    def write_row(self, sub: int, row: int, bits: np.ndarray) -> None:
        """Write a row; ``bits`` is (row_bits,) — broadcast to all trials —
        or (T, row_bits) for per-trial contents in batched mode."""
        bits = np.asarray(bits)
        w = self.geom.row_bits
        if bits.shape != (w,) and bits.shape != (self._T, w):
            raise ValueError(
                f"row is {w} bits (optionally with a leading {self._T}-trial "
                f"axis), got {bits.shape}")
        i = self._row(sub, row)
        self._cells(sub)[:, i] = bits[..., self._perm].astype(np.float32)
        t = self.timings
        n_bursts = self.geom.row_bits // 512  # 64B bursts per chip-row
        self.log.add("WR", t.tRCD + t.tWR + t.tRP,
                     ENERGY_PJ["act"] + ENERGY_PJ["pre"]
                     + n_bursts * ENERGY_PJ["wr_per_64B"],
                     bank=self.bank, sub=sub)

    def _log_wr(self, n_rows: int = 1, sub: int = -1) -> None:
        t = self.timings
        n_bursts = self.geom.row_bits // 512
        self.log.add("WR", t.tRCD + t.tWR + t.tRP,
                     ENERGY_PJ["act"] + ENERGY_PJ["pre"]
                     + n_bursts * ENERGY_PJ["wr_per_64B"], count=n_rows,
                     bank=self.bank, sub=sub)

    def write_cols_multi(self, sub: int, rows, cols,
                         bits: np.ndarray) -> None:
        """WR of one packed word per row in one strided scatter.

        ``bits`` is (n_rows, w) or (T, n_rows, w); each slice lands on
        ``cols`` of the matching row (the batched operand-staging hot path).
        """
        idx = self._map_rows(sub, rows)
        arr = self._cells(sub)
        if self.track_unshared:
            arr[:, idx] = 0.0
        arr[:, idx, cols] = np.asarray(bits, dtype=np.float32)
        self._log_wr(len(idx), sub=sub)

    def fill_rows(self, sub: int, rows, value: float,
                  cols=None) -> None:
        """WR of constant rows (reference-block staging).  With
        ``track_unshared=False`` callers may restrict to the observed
        columns (``cols=None`` fills the whole row)."""
        idx = self._map_rows(sub, rows)
        if not self.track_unshared and cols is not None:
            self._cells(sub)[:, idx, cols] = value
        else:
            self._cells(sub)[:, idx] = value
        self._log_wr(len(idx), sub=sub)

    def read_row(self, sub: int, row: int) -> np.ndarray:
        i = self._row(sub, row)
        arr = self._cells(sub)
        t = self.timings
        n_bursts = self.geom.row_bits // 512
        self.log.add("RD", t.tRCD + t.tCL + t.tRP,
                     ENERGY_PJ["act"] + ENERGY_PJ["pre"]
                     + n_bursts * ENERGY_PJ["rd_per_64B"],
                     bank=self.bank, sub=sub)
        return self._out((arr[:, i][..., self._invperm] > 0.5)
                         .astype(np.uint8))

    def frac_row(self, sub: int, row: int) -> None:
        """FracDRAM: store VDD/2 in every cell of the row."""
        # map the row *before* grabbing the buffer: a first touch can grow
        # (reallocate) the slot buffer, and the old one must not be indexed
        i = self._row(sub, row)
        self._cells(sub)[:, i] = 0.5
        t = self.timings
        # Frac = ACT -> PRE with violated tRAS, twice (per FracDRAM)
        self.log.add("FRAC", 2 * (VIOLATED_TRAS_NS + t.tRP),
                     2 * (ENERGY_PJ["act"] + ENERGY_PJ["pre"]),
                     bank=self.bank, sub=sub)

    def rowclone(self, sub: int, src: int, dst: int) -> None:
        """Same-subarray RowClone (sequential ACT -> PRE -> ACT).

        Trial-batched like every other command (the copy broadcasts over
        the leading trial axis).  Under the analog error model the copy is
        *noisy*: each destination cell independently flips with probability
        ``rowclone_fail_p`` (the source, fully restored by the first ACT,
        is unaffected) — the resident-register executor chains many clones,
        so the floor is modeled rather than assumed away.
        """
        isrc, idst = self._map_rows(sub, [src, dst])
        arr = self._cells(sub)
        restored = (arr[:, isrc] > 0.5).astype(np.float32)
        copied = restored
        if self.error_model == "analog" and self.rowclone_fail_p > 0.0:
            rng = self._rng()
            flip = rng.random(restored.shape,
                              dtype=self._noise_dtype) < self.rowclone_fail_p
            copied = np.where(flip, 1.0 - restored, restored)
        arr[:, idst] = copied
        arr[:, isrc] = restored  # source restored
        t = self.timings
        self.log.add("RC", t.tRAS + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                     2 * ENERGY_PJ["act"] + 2 * ENERGY_PJ["pre"],
                     bank=self.bank, sub=sub)

    # ---------------- APA: simultaneous multi-row activation ----------------
    def _split_cols(self, f_sub: int, l_sub: int):
        """-> (stripe id, f-side columns, l-side columns) for the shared SA
        stripe between neighboring subarrays."""
        if abs(f_sub - l_sub) != 1:
            raise ValueError("APA requires *neighboring* subarrays")
        lo = min(f_sub, l_sub)
        j = np.arange(self.shared_w)
        lo_cols, hi_cols = 2 * j + 1, 2 * j
        f_cols = lo_cols if f_sub == lo else hi_cols
        l_cols = lo_cols if l_sub == lo else hi_cols
        return lo, f_cols, l_cols

    def _col_slices(self, f_sub: int, l_sub: int):
        """Shared columns as contiguous *storage-layout* slices: the same
        column sets ``_split_cols`` returns as physical index arrays, in the
        same j order, but contiguous in the stripe-major layout."""
        if abs(f_sub - l_sub) != 1:
            raise ValueError("APA requires *neighboring* subarrays")
        lo = min(f_sub, l_sub)
        w = self.shared_w
        lo_sl, hi_sl = slice(0, w), slice(w, 2 * w)
        return (lo, lo_sl if f_sub == lo else hi_sl,
                lo_sl if l_sub == lo else hi_sl)

    def _other_slice(self, sl: slice) -> slice:
        """The complementary column half (non-shared, storage layout)."""
        w = self.shared_w
        return slice(w, 2 * w) if sl.start == 0 else slice(0, w)

    def _resolve_params(self, stripe: int, op: str, n: int, *,
                        regions: tuple[int, int], random_pattern: bool):
        """Shared analog-model scalars of one comparator resolve:
        (margin offset dv, noise sigma s, threshold shift, static offsets,
        activation-failure floor pf)."""
        p = self.params
        dv = A.margin_offset(op, p, compute_region=regions[0],
                             ref_region=regions[1],
                             mfr=self.module.manufacturer.value,
                             density_gb=self.module.density_gb,
                             die_rev=self.module.die_rev)
        s, _b, _wp, _wm = A.op_noise(
            op, n, p, temp_c=self.temp_c, random_pattern=random_pattern,
            speed_mts=self.module.speed_mts,
            mfr=self.module.manufacturer.value,
            density_gb=self.module.density_gb, die_rev=self.module.die_rev)
        shift = A.op_shift(op, n, p)
        static = self.static_offsets(stripe, op, n,
                                     random_pattern=random_pattern) \
            .astype(self._noise_dtype, copy=False)
        pf = A.op_pfloor(op, n, p, temp_c=self.temp_c,
                         random_pattern=random_pattern,
                         speed_mts=self.module.speed_mts)
        return dv, s, shift, static, pf

    def _resolve(self, margin: np.ndarray, stripe: int, op: str, n: int, *,
                 regions: tuple[int, int], random_pattern: bool,
                 rng: np.random.Generator) -> np.ndarray:
        """Sense-amp comparator outcome (bool per (trial, shared column)).

        ``margin`` is (T, w); static offsets broadcast across trials (one
        physical chip), noise/floor draws are per-trial.  This is the numpy
        backend; the pallas backend (:meth:`_resolve_pallas`) consumes the
        same draws through the fused kernel.
        """
        p = self.params
        if self.error_model in ("ideal", "none", "mean"):
            return margin > 0.0
        dv, s, shift, static, pf = self._resolve_params(
            stripe, op, n, regions=regions, random_pattern=random_pattern)
        acc = rng.standard_normal(margin.shape, dtype=self._noise_dtype)
        acc *= math.sqrt(max(1.0 - STATIC_SPLIT ** 2, 0.0)) * s
        acc += margin
        acc += static
        out = acc > -(dv - shift - p.delta_v)
        if self.batched:
            # one uniform: conditioned on u < pf, (u < pf/2) is a fair coin
            u = rng.random(margin.shape, dtype=self._noise_dtype)
            return np.where(u < pf, u < 0.5 * pf, out)
        flip = rng.random(margin.shape, dtype=self._noise_dtype) < pf
        coin = rng.random(margin.shape, dtype=self._noise_dtype) < 0.5
        return np.where(flip, coin, out)

    def _resolve_pallas(self, com_cells: np.ndarray, ref_cells: np.ndarray,
                        u_com: float, u_ref: float, stripe: int, op: str,
                        n: int, *, regions: tuple[int, int],
                        random_pattern: bool,
                        rng: np.random.Generator) -> np.ndarray:
        """Fused charge-share + sense-amp resolve through the Pallas kernel.

        ``com_cells`` / ``ref_cells`` are the activated cell slabs
        ``(T, n_rows, w)``; the kernel recomputes the charge-shared margin
        itself (``repro.kernels.senseamp``).  RNG consumption matches
        :meth:`_resolve` draw-for-draw, so at one seed the two backends
        differ only by float32 re-association at the comparator threshold.
        """
        from ..kernels import ops as kops
        p = self.params
        dv, s, shift, static, pf = self._resolve_params(
            stripe, op, n, regions=regions, random_pattern=random_pattern)
        shape = com_cells.shape[:1] + com_cells.shape[2:]      # (T, w)
        nz = rng.standard_normal(shape, dtype=self._noise_dtype)
        if self.batched:
            u = rng.random(shape, dtype=self._noise_dtype)
            # same single-uniform flip/coin decisions as the numpy path:
            # the kernel's coin is (un[1] < 0.5), so encode it as 0/1
            coin = np.where(u < 0.5 * pf, np.float32(0.0), np.float32(1.0))
            un = np.stack([u.astype(np.float32, copy=False), coin])
        else:
            flip_u = rng.random(shape, dtype=self._noise_dtype)
            coin_u = rng.random(shape, dtype=self._noise_dtype)
            un = np.stack([flip_u, coin_u]).astype(np.float32, copy=False)
        trial_sigma = math.sqrt(max(1.0 - STATIC_SPLIT ** 2, 0.0)) * s
        # numpy threshold: margin + static + noise > -(dv - shift - delta_v)
        # kernel threshold: margin_k - shift_k + static + noise > 0
        out = kops.senseamp_resolve_trials(
            com_cells, ref_cells,
            static.astype(np.float32, copy=False),
            nz.astype(np.float32, copy=False), un,
            u_com=float(u_com), u_ref=float(u_ref),
            shift=float(shift + p.delta_v - dv), pf=float(pf),
            trial_sigma=float(trial_sigma))
        return np.asarray(out)

    def _maj_restore(self, sub: int, rows, cols: slice,
                     rng: np.random.Generator) -> None:
        """Same-subarray multi-row activation on non-shared columns: cells
        charge-share against VDD/2 and the (other-stripe) SA restores the
        majority value into all activated cells (prior works' MAJ)."""
        arr = self._cells(sub)
        rows = np.asarray(rows)     # slot indices (pre-translated by apa)
        n = len(rows)
        u = A.u_n(n, self.params)
        v = u * (np.sum(arr[:, rows, cols], axis=1) - 0.5 * n)
        if self.error_model == "analog":
            s = self.params.sigma_sa
            v = v + s * rng.standard_normal(v.shape, dtype=self._noise_dtype)
        out = (v > 0.0).astype(np.float32)
        arr[:, rows, cols] = out[:, None, :]

    def apa(self, rf_global: int, rl_global: int, *,
            first_act_restored: bool = False,
            random_pattern: bool = True) -> DEC.Activation:
        """``ACT R_F -> PRE -> ACT R_L`` with violated timings.

        Global row address = subarray * rows_per_subarray + row.
        ``first_act_restored=True`` models the NOT protocol (§5): the first
        ACT waits full tRAS, so R_F's value is fully restored and then
        *drives* the R_L rows through the shared SAs.  Otherwise both sides
        charge-share from VDD/2 and the SA acts as a comparator (§6).
        """
        rps = self.geom.rows_per_subarray
        f_sub, f_row = divmod(rf_global, rps)
        l_sub, l_row = divmod(rl_global, rps)
        act = DEC.activation_pattern(self.module, f_row, l_row, seed=self.seed)
        t = self.timings
        t_first = t.tRAS if first_act_restored else VIOLATED_TRAS_NS
        self.log.add("APA", t_first + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                     (act.n_rf + act.n_rl) * ENERGY_PJ["act"]
                     + 2 * ENERGY_PJ["pre"],
                     bank=self.bank, sub=f_sub)
        if act.n_rf == 0:
            return act
        if self.module.activation is ActivationSupport.SEQUENTIAL \
                and not first_act_restored:
            return act  # sequential activation cannot charge-share both sides
        stripe, f_cols, l_cols = self._col_slices(f_sub, l_sub)
        rows_f = self._map_rows(f_sub, act.rows_f)
        rows_l = self._map_rows(l_sub, act.rows_l)
        arr_f, arr_l = self._cells(f_sub), self._cells(l_sub)
        rng = self._rng()
        geom = self.geom
        reg_f = geom.distance_region(f_row, toward_upper=f_sub > l_sub)
        reg_l = geom.distance_region(l_row, toward_upper=l_sub > f_sub)

        if first_act_restored:
            # ---- NOT protocol: R_F drives, R_L receives the complement ----
            n_src = act.n_rf
            u = A.u_n(n_src, self.params)
            v_src = 0.5 + u * (np.sum(arr_f[:, rows_f, f_cols], axis=1)
                               - 0.5 * n_src)
            src_bit = v_src > 0.5                       # (T, w)
            if self.error_model == "analog":
                p_ok = A.not_success(
                    act.n_rl, pattern=("N2N" if act.kind == "N:2N" else "NN"),
                    p=self.params, temp_c=self.temp_c,
                    src_region=reg_f, dst_region=reg_l,
                    speed_mts=self.module.speed_mts,
                    mfr=self.module.manufacturer.value,
                    density_gb=self.module.density_gb,
                    die_rev=self.module.die_rev)
                # static per-cell variation around the mean success rate;
                # E[phi(a + s Z)] = phi(a / sqrt(1+s^2)) keeps the cell-mean
                # exactly equal to the closed-form not_success.
                spread = 0.75
                xi1, _xi2 = self._static_latents(stripe)
                a = _norm_ppf(np.clip(p_ok, 1e-9, 1 - 1e-9)) \
                    * math.sqrt(1.0 + spread ** 2)
                z = A.phi(a + spread * _norm_ppf(xi1)) \
                    .astype(self._noise_dtype, copy=False)  # (w,) per-cell
                ok = rng.random(src_bit.shape, dtype=self._noise_dtype) < z
            else:
                ok = np.ones(src_bit.shape, dtype=bool)
            dst_bit = np.where(ok, ~src_bit, src_bit).astype(np.float32)
            src_f = src_bit.astype(np.float32)
            arr_l[:, rows_l, l_cols] = dst_bit[:, None, :]
            arr_f[:, rows_f, f_cols] = src_f[:, None, :]
        else:
            # ---- Boolean-op protocol: comparator across the stripe ----
            n_f, n_l = act.n_rf, act.n_rl
            u_f = A.u_n(n_f, self.params)
            u_l = A.u_n(n_l, self.params)
            v_f = u_f * (np.sum(arr_f[:, rows_f, f_cols], axis=1)
                         - 0.5 * n_f)
            # noise context: the reference level sets the common mode
            # (V_REF > VDD/2 -> AND-family, < VDD/2 -> OR-family)
            op_ctx = "and" if float(np.mean(v_f)) >= 0.0 else "or"
            if self.error_model == "analog" \
                    and self._resolve_backend() == "pallas":
                out = self._resolve_pallas(
                    arr_l[:, rows_l, l_cols], arr_f[:, rows_f, f_cols],
                    u_l, u_f, stripe, op_ctx, n_l, regions=(reg_l, reg_f),
                    random_pattern=random_pattern, rng=rng)
            else:
                v_l = u_l * (np.sum(arr_l[:, rows_l, l_cols], axis=1)
                             - 0.5 * n_l)
                # margin: compute side (R_L, §6) minus reference (R_F)
                margin = v_l - v_f                      # (T, w)
                out = self._resolve(margin, stripe, op_ctx, n_l,
                                    regions=(reg_l, reg_f),
                                    random_pattern=random_pattern, rng=rng)
            outf = out.astype(np.float32)
            arr_l[:, rows_l, l_cols] = outf[:, None, :]
            arr_f[:, rows_f, f_cols] = (1.0 - outf)[:, None, :]
        # non-shared columns: same-subarray restore (MAJ against VDD/2)
        other_f, other_l = self._other_slice(f_cols), self._other_slice(l_cols)
        if self.track_unshared:
            self._maj_restore(f_sub, rows_f, other_f, rng)
            self._maj_restore(l_sub, rows_l, other_l, rng)
        # (untracked: the restore's noise draws are skipped too — every apa
        # uses a fresh per-command generator, so later ops are unaffected)
        return act

    def apa_then_write(self, rf_global: int, rl_global: int,
                       pattern: np.ndarray) -> DEC.Activation:
        """§4.2 reverse-engineering methodology: APA followed by a WR that
        overdrives the sense amps (Obs. 1 semantics)."""
        rps = self.geom.rows_per_subarray
        f_sub, f_row = divmod(rf_global, rps)
        l_sub, l_row = divmod(rl_global, rps)
        act = DEC.activation_pattern(self.module, f_row, l_row, seed=self.seed)
        self.log.add("APA+WR", 30.0, ENERGY_PJ["act"] * (act.n_rf + act.n_rl),
                     bank=self.bank, sub=f_sub)
        if act.n_rf == 0:
            return act
        pattern = np.asarray(pattern, dtype=np.float32)
        rows_f = self._map_rows(f_sub, act.rows_f)
        rows_l = self._map_rows(l_sub, act.rows_l)
        arr_f, arr_l = self._cells(f_sub), self._cells(l_sub)
        _stripe, f_cols, l_cols = self._split_cols(f_sub, l_sub)
        arr_f[:, rows_f] = pattern[..., self._perm]  # exact pattern (Obs. 1)
        _lo, _f_sl, l_sl = self._col_slices(f_sub, l_sub)
        arr_l[:, rows_l, l_sl] = \
            (1.0 - pattern[..., l_cols])[..., None, :]  # negated shared half
        return act

    # ---------------- high-level op helpers (ISA entry points) ----------------
    def op_not(self, src_global: int, dst_global: int, *,
               n_dst: int | None = None) -> DEC.Activation:
        """NOT: source row fully restored, then APA into dst's subarray."""
        return self.apa(src_global, dst_global, first_act_restored=True)

    def op_boolean(self, op: str, ref_global: int, com_global: int, *,
                   random_pattern: bool = True) -> DEC.Activation:
        """Many-input AND/OR (+ NAND/NOR on the reference side).

        The caller must have initialized the reference subarray rows
        (N-1 constants + Frac) and the compute rows (operands); see
        repro.core.isa for the full protocol.
        """
        base, _is_ref = A._base_op(op)
        del base
        return self.apa(ref_global, com_global, first_act_restored=False,
                        random_pattern=random_pattern)

    # ---------------- convenience ----------------
    def global_addr(self, sub: int, row: int) -> int:
        return sub * self.geom.rows_per_subarray + row

    def read_shared_word(self, sub: int, row: int, sl: slice) -> np.ndarray:
        """Digital value of one shared-column half of a row, in j order —
        the ISA's result readout ((w,), or (T, w) batched).  Logged as a
        full RD: the host pulls the row over the DDR bus to get the word."""
        i = self._row(sub, row)
        t = self.timings
        n_bursts = self.geom.row_bits // 512
        self.log.add("RD", t.tRCD + t.tCL + t.tRP,
                     ENERGY_PJ["act"] + ENERGY_PJ["pre"]
                     + n_bursts * ENERGY_PJ["rd_per_64B"],
                     bank=self.bank, sub=sub)
        return self._out((self._cells(sub)[:, i, sl] > 0.5).astype(np.uint8))

    def snapshot_rows(self, sub: int, rows) -> np.ndarray:
        """(n_rows, row_bits) digital snapshot; (T, n_rows, row_bits) when
        batched."""
        idx = self._map_rows(sub, rows)
        arr = self._cells(sub)
        return self._out((arr[:, idx][..., self._invperm] > 0.5)
                         .astype(np.uint8))
