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

An APA leaves one ``(T, w)`` bool plane in every activated row of a side,
and the sim keeps that plane, not the rows: one pending record per
subarray (slots, column slice, plane).  ``_cells``, the one gate to a
subarray's slot buffer, writes the record into its rows (as 1.0 / 0.0)
before any command reads or writes that buffer; ``read_shared_word``
writes nothing back (a pending row's word on the pending slice is read
off the plane, any other word off the buffer); and ``recycle_rows``
drops every record, since recycled contents are don't-care.  An MC
episode (recycle, stage, APA, one readout) thus never writes its APA's
rows at all.

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

Bank axis
---------
A sim can also carry N independent banks (chip identities) stacked onto
the trial axis: ``(N*T, rows, row_bits)`` state, bank b owning trials
``b*T .. (b+1)*T - 1``.  ``BankSim(...)`` is the one-bank case;
``repro.core.fused.FusedBankSim`` builds N > 1.  Every command runs once
for all banks, and per bank it is bit for bit the command a one-bank sim
of that bank would run (gated in ``tests/test_fused.py`` and
``benchmarks/diff_bench.py``):

* *RNG consumption*: each command opens one generator per bank, seeded
  ``SeedSequence([noise_seed_b, 0x7A1A1, counter_b])`` from the bank's own
  noise seed and command counter, and draws each bank's ``(T, ...)``
  slice from it (:class:`_BankRng`), so each bank sees the call sequence
  its own sim would.
* *Chip identity*: static SA latents are drawn per bank seed and stacked
  ``(N, w)``; decoder activations are evaluated per bank seed.
* *Analog scalars*: the margin offset ``dv`` depends on the rows'
  distance regions, which differ per bank, so the comparator threshold
  is applied per bank slice with the one-bank scalar expression: no
  array promotion drift.
* *Row slots*: the banks' row maps must agree on which slot a row
  occupies (:class:`FusedExecutionError` otherwise), so multi-bank ISA
  ops recycle row slots on entry, pinning all banks to one first-touch
  order.  That is parity-neutral: one-bank callers recycle at least that
  often, recycling logs and draws nothing, and every op re-stages the
  rows it reads under ``track_unshared=False``.

Banks must run the *same command sequence with the same activation
geometry* (row counts per APA, :class:`FusedGeometryError` otherwise);
their data, noise, offsets, regions and decoder row sets may differ.
The one place the code branches on the bank count is the resolve kernel's
call form: at one bank it gets the ``(w,)`` static offsets and the scalar
threshold shift; over N banks each bank's shift is folded into a
per-trial ``(N*T, w)`` static plane (one re-associated float add, so
multi-bank parity on the Pallas backend is tolerance-class like
Pallas-vs-numpy, while the numpy backend is bit-exact).

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

from .. import tracing
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

#: (mantissa bits, word bits) of ``Generator.random``'s uniforms
_UNIFORM_BITS = {np.dtype(np.float32): (24, 32),
                 np.dtype(np.float64): (53, 64)}


class CapabilityError(RuntimeError):
    """The module cannot express the requested activation/op."""


class FusedExecutionError(RuntimeError):
    """Per-bank execution diverged where the bank axis requires lockstep
    (row-slot allocation or noise-context sign) — a bug guard, not a
    capability limit: callers should gate fusion, not catch this."""


class FusedGeometryError(CapabilityError):
    """Banks disagree on activation geometry (row counts / fan-in), so
    the command sequence cannot run as one pass over the bank axis.
    Callers fall back to one sim per bank."""


class PerBank:
    """Marker wrapper for per-bank values on a multi-bank sim's APIs.

    Wraps an ``(N, ...)`` integer array (leading axis = banks).  BankSim
    methods receiving a plain row/int broadcast it to all banks; a
    ``PerBank`` carries bank-distinct rows (decoder row sets differ per
    bank seed).  Indexing selects positions along each bank's row list.
    """

    __slots__ = ("vals",)

    def __init__(self, vals):
        self.vals = np.asarray(vals, dtype=np.int64)

    def __getitem__(self, k) -> "PerBank":
        return PerBank(self.vals[:, k])

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"PerBank({self.vals.tolist()})"


@dataclass(frozen=True, slots=True)
class FusedActivation:
    """Per-bank activation sets of one APA over the bank axis (uniform
    geometry); ``rows_f`` / ``rows_l`` are ``PerBank`` ``(N, n)``."""

    n_rf: int
    n_rl: int
    kind: str
    rows_f: PerBank
    rows_l: PerBank

    @classmethod
    def of(cls, acts: list) -> "FusedActivation":
        """Stack each bank's decoder Activation; the banks must agree on
        the row counts."""
        a0 = acts[0]
        if any(a.n_rf != a0.n_rf or a.n_rl != a0.n_rl for a in acts[1:]):
            raise FusedGeometryError(
                "activation geometry differs across banks: "
                f"{[(a.n_rf, a.n_rl) for a in acts]}")
        return cls(a0.n_rf, a0.n_rl, a0.kind,
                   PerBank(np.asarray([a.rows_f for a in acts],
                                      dtype=np.int64)),
                   PerBank(np.asarray([a.rows_l for a in acts],
                                      dtype=np.int64)))


class _BankRng:
    """One per-command generator per bank; draws concatenate bank-major,
    so slice ``[b*T:(b+1)*T]`` of every draw is bank b's own draw."""

    __slots__ = ("gens", "t")

    def __init__(self, gens: list, t: int):
        self.gens = gens
        self.t = t

    def _draw(self, name: str, shape, dtype) -> np.ndarray:
        shape = tuple(shape)
        if shape[0] != self.t * len(self.gens):
            raise FusedExecutionError(
                f"fused draw of shape {shape} does not stack "
                f"{len(self.gens)} banks x {self.t} trials")
        return np.concatenate([getattr(g, name)((self.t, *shape[1:]),
                                                dtype=dtype)
                               for g in self.gens])

    def standard_normal(self, shape, dtype=np.float64) -> np.ndarray:
        return self._draw("standard_normal", shape, dtype)

    def random(self, shape, dtype=np.float64) -> np.ndarray:
        return self._draw("random", shape, dtype)


def _uniform_hits(rng, size: int, dtype, p: float) -> np.ndarray:
    """Flat indices where ``rng.random(size, dtype) < p``, bit for bit,
    read off the generator's raw words without making the floats.

    NumPy's PCG64 builds a float32 uniform as ``(u32 >> 8) * 2**-24``,
    handing out each 64-bit word's low half, then its high half, and a
    float64 as ``(u64 >> 11) * 2**-53``.  So ``u < p`` holds exactly when
    ``word < ceil(p * 2**m) << (bits - m)``, with ``p`` cast as the float
    comparison casts it (a Python float to ``dtype``; a NumPy float64
    promotes the comparison to float64).  A :class:`_BankRng` reads each
    bank's slice off that bank's generator."""
    if isinstance(rng, _BankRng):
        n = size // len(rng.gens)
        return np.concatenate([_uniform_hits(g, n, dtype, p) + b * n
                               for b, g in enumerate(rng.gens)])
    m, bits = _UNIFORM_BITS[np.dtype(dtype)]
    pc = float(np.asarray(p, dtype=np.result_type(dtype, p)))
    k = math.ceil(min(pc, 1.0) * 2.0 ** m)
    if k >= 1 << m:
        return np.arange(size)
    if bits == 32:
        words = rng.bit_generator.random_raw((size + 1) // 2) \
            .astype("<u8", copy=False).view("<u4")[:size]
    else:
        words = rng.bit_generator.random_raw(size)
    return np.flatnonzero(words < words.dtype.type(k << (bits - m)))


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


def _resolve_call(com_cells, ref_cells, static, normals, uniforms,
                  **scalars) -> np.ndarray:
    """The sense-amp kernel entry on host arrays, its decisions brought
    back to the host: the resolve boundary's host-to-device copies,
    kernel and copy back (timed as ``sim.resolve_call``; the copies in
    are counted as ``resolve.h2d_bytes``)."""
    from ..kernels import ops as kops
    tracing.count("resolve.h2d_bytes",
                  com_cells.nbytes + ref_cells.nbytes + static.nbytes
                  + normals.nbytes + uniforms.nbytes)
    with tracing.span("sim.resolve_call"):
        return np.asarray(kops.senseamp_resolve_trials(
            com_cells, ref_cells, static, normals, uniforms, **scalars))


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
    """One DRAM bank — or N stacked on the trial axis (module doc, *Bank
    axis*): lazily-allocated subarrays of float32 cell voltages."""

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
        #: purely log metadata)
        self.bank = int(bank)
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
        #: per subarray, the bank-major row -> slot map (bank b's row r
        #: at ``b * rows_per_subarray + r``; -1 = no slot yet)
        self._rowmap: dict[int, np.ndarray] = {}
        self._nrows: dict[int, int] = {}
        self._static: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        #: per subarray, the last APA's result not yet written into its
        #: rows: (slots, storage column slice, (T, w) bool plane)
        self._pending: dict[int, tuple[np.ndarray, slice, np.ndarray]] = {}
        #: memoized resolve scalars and NOT success planes: pure functions
        #: of chip identity and the op context
        self._param_cache: dict = {}
        self._not_z_cache: dict = {}
        self._set_banks([seed], [seed if noise_seed is None else noise_seed])
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

    # ---------------- the bank axis ----------------
    def _set_banks(self, seeds, noise_seeds) -> None:
        """Lay ``len(seeds)`` banks over the trial axis: chip identities
        ``seeds``, noise streams ``noise_seeds``, fresh command counters."""
        #: each bank's chip identity (decoder map + static SA offsets)
        self.bank_seeds = [int(s) for s in seeds]
        self.n_banks = len(self.bank_seeds)
        self.trials_per_bank = self._T // self.n_banks
        self._bank_base = (np.arange(self.n_banks, dtype=np.int64)[:, None]
                           * self.geom.rows_per_subarray)
        #: each bank's per-trial noise stream (chip identity stays put)
        self.bank_noise_seeds = [int(s) for s in noise_seeds]
        #: each bank's command counter (one generator per command)
        self._bank_trial = [0] * self.n_banks

    @property
    def _trial(self) -> int:
        """Bank 0's command counter."""
        return self._bank_trial[0]

    def _bank_slices(self) -> list[slice]:
        """Each bank's rows of the stacked trial axis."""
        t = self.trials_per_bank
        return [slice(b * t, (b + 1) * t) for b in range(self.n_banks)]

    def _pb_vals(self, rows) -> np.ndarray:
        """(N, k) per-bank row matrix from a PerBank or a shared spec."""
        if isinstance(rows, PerBank):
            r = rows.vals
            if r.ndim == 1:
                r = r[:, None]
            if r.ndim != 2 or r.shape[0] != self.n_banks:
                raise ValueError(
                    f"PerBank rows must be ({self.n_banks}, k), got shape "
                    f"{rows.vals.shape}")
            return r
        base = np.atleast_1d(np.asarray(rows, dtype=np.int64))
        return np.broadcast_to(base, (self.n_banks, base.size))

    # ---------------- compact row-remapped cell storage ----------------
    # Physical row addresses map to densely-allocated slots of a
    # (T, slots, row_bits) buffer per subarray: a bank exposes 512 rows but
    # a Monte-Carlo run touches a few dozen, and dense slots keep the
    # trial-batched gathers/scatters contiguous instead of striding a
    # (T, 512, row_bits) arena.  Unwritten rows read as 0 V (cold cells).
    def _map_rows(self, sub: int, rows) -> np.ndarray:
        """Slot indices of physical rows, allocating slots on first touch.

        ``rows`` is shared by every bank or a :class:`PerBank` matrix; the
        banks allocate in lockstep and must agree on every slot."""
        if not 0 <= sub < self.geom.subarrays_per_bank:
            raise IndexError(f"subarray {sub} out of range")
        r = self._pb_vals(rows)
        if r.size and (r.min() < 0
                       or r.max() >= self.geom.rows_per_subarray):
            raise IndexError(f"row out of range in {r}")
        rmap = self._rowmap.get(sub)
        if rmap is None:
            rmap = self._rowmap[sub] = np.full(
                self.n_banks * self.geom.rows_per_subarray, -1,
                dtype=np.int64)
            self._nrows[sub] = 0
        flat = r + self._bank_base
        idx = rmap[flat]
        fresh = idx < 0
        if np.any(fresh):
            if not (fresh == fresh[0]).all():
                raise FusedExecutionError(
                    "per-bank first-touch order diverged (some banks have "
                    "already allocated a row others have not) — fused ops "
                    "must recycle rows so all banks allocate in lockstep")
            new_rows = flat[:, fresh[0]]
            start = self._nrows[sub]
            rmap[new_rows] = np.arange(start, start + new_rows.shape[1])
            self._nrows[sub] = start + new_rows.shape[1]
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
            idx = rmap[flat]
        if idx.size and not (idx == idx[0]).all():
            raise FusedExecutionError(
                "per-bank slot maps diverged — banks disagree on which "
                "storage slot a row occupies")
        return idx[0]

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
        self._pending.clear()

    def _cells(self, sub: int) -> np.ndarray:
        """(T, slots, row_bits) backing buffer (slot order = first touch),
        with any pending APA result written into its rows first."""
        if sub not in self._subarrays:
            self._map_rows(sub, [0])    # force allocation
        arr = self._subarrays[sub]
        pending = self._pending.pop(sub, None)
        if pending is not None:
            slots, cols, plane = pending
            arr[:, slots, cols] = plane[:, None, :]
            tracing.count("sim.writeback_rows", len(slots))
        return arr

    def _arr(self, sub: int) -> np.ndarray:
        """Cell voltages in *physical* row order: (rows, row_bits) in scalar
        mode, (T, rows, row_bits) batched.  A materialized snapshot (the
        backing store is slot-compacted) — read-only debug/inspection aid."""
        out = np.zeros((self._T, self.geom.rows_per_subarray,
                        self.geom.row_bits), dtype=np.float32)
        rmap = self._rowmap.get(sub)
        if rmap is not None:
            live = np.nonzero(rmap >= 0)[0]
            if live.size:
                out[:, live] = self._cells(sub)[:, rmap[live]][
                    ..., self._invperm]
        return out if self.batched else out[0]

    def _out(self, rows: np.ndarray) -> np.ndarray:
        """Strip the trial axis in legacy scalar mode."""
        return rows if self.batched else rows[0]

    def _static_latents(self, stripe: int) -> tuple[np.ndarray, np.ndarray]:
        """Two per-SA uniforms for the static offset mixture of a stripe:
        ``(w,)`` each at one bank, each bank's stacked ``(N, w)`` over N."""
        if stripe not in self._static:
            lat = []
            for s in self.bank_seeds:
                rng = np.random.default_rng(
                    np.random.SeedSequence([s, 0xC0FFEE, stripe]))
                lat.append((rng.random(self.shared_w),
                            rng.random(self.shared_w)))
            self._static[stripe] = lat[0] if self.n_banks == 1 else \
                tuple(np.stack(x) for x in zip(*lat, strict=True))
        return self._static[stripe]

    def _rng(self):
        """This command's generator: a plain one at one bank, else one per
        bank (:class:`_BankRng`)."""
        gens = []
        for b, s in enumerate(self.bank_noise_seeds):
            self._bank_trial[b] += 1
            gens.append(np.random.default_rng(
                np.random.SeedSequence([s, 0x7A1A1, self._bank_trial[b]])))
        return gens[0] if self.n_banks == 1 else \
            _BankRng(gens, self.trials_per_bank)

    def reseed_noise(self, noise_seed) -> None:
        """Point subsequent per-trial noise draws at an independent stream.

        Chip identity — the decoder's activation map and the static per-SA
        offsets — stays tied to ``seed``; only the per-command noise/floor
        generators change.  The command counter restarts so the stream is a
        pure function of ``noise_seed`` (callers pass unique seeds, e.g.
        ``np.random.SeedSequence(seed).spawn`` children).  A sim over N
        banks takes one seed per bank (an int only at one bank)."""
        if isinstance(noise_seed, (int, np.integer)):
            if self.n_banks != 1:
                raise ValueError(
                    f"fused sim over {self.n_banks} banks needs one noise "
                    "seed per bank (a shared seed would collide streams)")
            noise_seed = [noise_seed]
        seeds = [int(s) for s in noise_seed]
        if len(seeds) != self.n_banks:
            raise ValueError(f"need {self.n_banks} noise seeds, got "
                             f"{len(seeds)}")
        self.bank_noise_seeds = seeds
        self._bank_trial = [0] * self.n_banks

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
        self._log_wr(sub=sub)

    def _log_wr(self, n_rows: int = 1, sub: int = -1) -> None:
        t = self.timings
        n_bursts = self.geom.row_bits // 512  # 64B bursts per chip-row
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

    def _log_rd(self, sub: int) -> None:
        t = self.timings
        n_bursts = self.geom.row_bits // 512
        self.log.add("RD", t.tRCD + t.tCL + t.tRP,
                     ENERGY_PJ["act"] + ENERGY_PJ["pre"]
                     + n_bursts * ENERGY_PJ["rd_per_64B"],
                     bank=self.bank, sub=sub)

    def read_row(self, sub: int, row: int) -> np.ndarray:
        i = self._row(sub, row)
        arr = self._cells(sub)
        self._log_rd(sub)
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

    def rowclone(self, sub: int, src, dst) -> None:
        """Same-subarray RowClone (sequential ACT -> PRE -> ACT).

        Trial-batched like every other command (the copy broadcasts over
        the leading trial axis).  Under the analog error model the copy is
        *noisy*: each destination cell independently flips with probability
        ``rowclone_fail_p`` (the source, fully restored by the first ACT,
        is unaffected) — the resident-register executor chains many clones,
        so the floor is modeled rather than assumed away.  The flips are
        read off the copy's generator words and only the hit cells are
        touched (:func:`_uniform_hits`).
        """
        rmap = self._rowmap.get(sub)
        nrows = self.geom.rows_per_subarray
        if (self.n_banks == 1 and rmap is not None
                and isinstance(src, (int, np.integer))
                and isinstance(dst, (int, np.integer))
                and 0 <= src < nrows and 0 <= dst < nrows
                and rmap[src] >= 0 and rmap[dst] >= 0):
            # one bank's row map has no bank axis: read both slots off it
            isrc, idst = rmap[src], rmap[dst]
        else:
            isrc, idst = self._map_rows(sub, PerBank(np.concatenate(
                [self._pb_vals(src)[:, :1], self._pb_vals(dst)[:, :1]],
                axis=1)))
        arr = self._cells(sub)
        restored = arr[:, isrc]
        np.greater(restored, 0.5, out=restored)  # source restored
        hit = None
        if self.error_model == "analog" and self.rowclone_fail_p > 0.0:
            hit = _uniform_hits(self._rng(), restored.size,
                                self._noise_dtype, self.rowclone_fail_p)
        if idst != isrc:
            copied = arr[:, idst]
            copied[...] = restored
            if hit is not None:
                tracing.count("sim.rowclone_flips", hit.size)
                trial, col = np.divmod(hit, restored.shape[1])
                copied[trial, col] = 1.0 - copied[trial, col]
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
                        regions, random_pattern: bool):
        """Shared analog-model scalars of one comparator resolve:
        (each bank's margin offset dv, noise sigma s, threshold shift,
        static offsets, activation-failure floor pf).  ``regions`` are the
        compute and reference rows' distance regions, one per bank (the
        margin offset depends on them); the static offsets are ``(w,)`` at
        one bank, else a per-trial ``(N*T, w)`` plane.  Memoized — every
        value is a pure function of chip identity and the op context."""
        reg_c = tuple(int(x) for x in np.atleast_1d(regions[0]))
        reg_r = tuple(int(x) for x in np.atleast_1d(regions[1]))
        key = (stripe, op, n, random_pattern, reg_c, reg_r)
        cached = self._param_cache.get(key)
        if cached is None:
            p = self.params
            dv = tuple(
                A.margin_offset(op, p, compute_region=c, ref_region=r,
                                mfr=self.module.manufacturer.value,
                                density_gb=self.module.density_gb,
                                die_rev=self.module.die_rev)
                for c, r in zip(reg_c, reg_r, strict=True))
            s, _b, _wp, _wm = A.op_noise(
                op, n, p, temp_c=self.temp_c, random_pattern=random_pattern,
                speed_mts=self.module.speed_mts,
                mfr=self.module.manufacturer.value,
                density_gb=self.module.density_gb,
                die_rev=self.module.die_rev)
            shift = A.op_shift(op, n, p)
            static = self.static_offsets(stripe, op, n,
                                         random_pattern=random_pattern) \
                .astype(self._noise_dtype, copy=False)
            if self.n_banks > 1:        # (N, w) -> each bank's trials
                static = np.repeat(static, self.trials_per_bank, axis=0)
            pf = A.op_pfloor(op, n, p, temp_c=self.temp_c,
                             random_pattern=random_pattern,
                             speed_mts=self.module.speed_mts)
            cached = self._param_cache[key] = (dv, s, shift, static, pf)
        return cached

    def _resolve(self, margin: np.ndarray, stripe: int, op: str, n: int, *,
                 regions, random_pattern: bool, rng) -> np.ndarray:
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
        # each bank's threshold, a scalar expression per bank slice
        out = np.empty(margin.shape, dtype=bool)
        for sl, dv_b in zip(self._bank_slices(), dv, strict=True):
            np.greater(acc[sl], -(dv_b - shift - p.delta_v), out=out[sl])
        if self.batched:
            # one uniform: conditioned on u < pf, (u < pf/2) is a fair coin
            u = rng.random(margin.shape, dtype=self._noise_dtype)
            return np.where(u < pf, u < 0.5 * pf, out)
        flip = rng.random(margin.shape, dtype=self._noise_dtype) < pf
        coin = rng.random(margin.shape, dtype=self._noise_dtype) < 0.5
        return np.where(flip, coin, out)

    def _resolve_pallas(self, com_cells: np.ndarray, ref_cells: np.ndarray,
                        u_com: float, u_ref: float, stripe: int, op: str,
                        n: int, *, regions, random_pattern: bool,
                        rng) -> np.ndarray:
        """Fused charge-share + sense-amp resolve through the Pallas kernel.

        ``com_cells`` / ``ref_cells`` are the activated cell slabs
        ``(T, n_rows, w)``; the kernel recomputes the charge-shared margin
        itself (``repro.kernels.senseamp``).  RNG consumption matches
        :meth:`_resolve` draw-for-draw, so at one seed the two backends
        differ only by float32 re-association at the comparator threshold.
        """
        p = self.params
        with tracing.span("sim.resolve_prep"):
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
            static = static.astype(np.float32, copy=False)
            nz = nz.astype(np.float32, copy=False)
            # numpy threshold: margin + static + noise > -(dv - shift - delta_v)
            # kernel threshold: margin_k - shift_k + static + noise > 0
            if self.n_banks == 1:
                shift_k = float(shift + p.delta_v - dv[0])
            else:
                # each bank's shift folds into the per-trial static plane
                shift_col = np.repeat(
                    np.asarray([shift + p.delta_v - dv_b for dv_b in dv],
                               dtype=np.float32), self.trials_per_bank)
                static, shift_k = static - shift_col[:, None], 0.0
        return _resolve_call(
            com_cells, ref_cells, static, nz, un,
            u_com=float(u_com), u_ref=float(u_ref), shift=shift_k,
            pf=float(pf), trial_sigma=float(trial_sigma))

    def _not_plane(self, b: int, stripe: int, act, reg_f: int,
                   reg_l: int) -> np.ndarray:
        """Bank b's per-cell NOT success probability ``(w,)`` (memoized).

        Static per-cell variation around the mean success rate;
        E[phi(a + s Z)] = phi(a / sqrt(1+s^2)) keeps the cell-mean exactly
        equal to the closed-form not_success."""
        key = (b, stripe, act.n_rl, act.kind, int(reg_f), int(reg_l))
        z = self._not_z_cache.get(key)
        if z is None:
            p_ok = A.not_success(
                act.n_rl, pattern=("N2N" if act.kind == "N:2N" else "NN"),
                p=self.params, temp_c=self.temp_c,
                src_region=int(reg_f), dst_region=int(reg_l),
                speed_mts=self.module.speed_mts,
                mfr=self.module.manufacturer.value,
                density_gb=self.module.density_gb,
                die_rev=self.module.die_rev)
            spread = 0.75
            xi1 = np.atleast_2d(self._static_latents(stripe)[0])[b]
            a = _norm_ppf(np.clip(p_ok, 1e-9, 1 - 1e-9)) \
                * math.sqrt(1.0 + spread ** 2)
            z = self._not_z_cache[key] = \
                A.phi(a + spread * _norm_ppf(xi1)) \
                .astype(self._noise_dtype, copy=False)
        return z

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

    @tracing.traced("sim.apa")
    def apa(self, rf_global, rl_global, *, first_act_restored: bool = False,
            random_pattern: bool = True):
        """``ACT R_F -> PRE -> ACT R_L`` with violated timings.

        Global row address = subarray * rows_per_subarray + row; a
        :class:`PerBank` address gives each bank its own rows in one
        subarray pair.  ``first_act_restored=True`` models the NOT protocol
        (§5): the first ACT waits full tRAS, so R_F's value is fully
        restored and then *drives* the R_L rows through the shared SAs.
        Otherwise both sides charge-share from VDD/2 and the SA acts as a
        comparator (§6).  Returns the decoder's Activation (over N banks,
        a :class:`FusedActivation` of every bank's rows).
        """
        rps = self.geom.rows_per_subarray
        f_subs, f_rows = np.divmod(self._pb_vals(rf_global)[:, 0], rps)
        l_subs, l_rows = np.divmod(self._pb_vals(rl_global)[:, 0], rps)
        if (f_subs != f_subs[0]).any() or (l_subs != l_subs[0]).any():
            raise FusedGeometryError(
                "fused APA needs one subarray pair shared by all banks")
        f_sub, l_sub = int(f_subs[0]), int(l_subs[0])
        acts = [DEC.activation_pattern(self.module, int(f), int(l), seed=s)
                for f, l, s in zip(f_rows, l_rows, self.bank_seeds,
                                   strict=True)]
        fact = FusedActivation.of(acts)
        act = acts[0] if self.n_banks == 1 else fact
        t = self.timings
        t_first = t.tRAS if first_act_restored else VIOLATED_TRAS_NS
        self.log.add("APA", t_first + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                     (fact.n_rf + fact.n_rl) * ENERGY_PJ["act"]
                     + 2 * ENERGY_PJ["pre"],
                     bank=self.bank, sub=f_sub)
        if fact.n_rf == 0:
            return act
        if self.module.activation is ActivationSupport.SEQUENTIAL \
                and not first_act_restored:
            return act  # sequential activation cannot charge-share both sides
        stripe, f_cols, l_cols = self._col_slices(f_sub, l_sub)
        rows_f = self._map_rows(f_sub, fact.rows_f)
        rows_l = self._map_rows(l_sub, fact.rows_l)
        arr_f, arr_l = self._cells(f_sub), self._cells(l_sub)
        rng = self._rng()
        geom = self.geom
        reg_f = geom.distance_regions(f_rows, toward_upper=f_sub > l_sub)
        reg_l = geom.distance_regions(l_rows, toward_upper=l_sub > f_sub)

        if first_act_restored:
            # ---- NOT protocol: R_F drives, R_L receives the complement ----
            n_src = fact.n_rf
            u = A.u_n(n_src, self.params)
            v_src = 0.5 + u * (np.sum(arr_f[:, rows_f, f_cols], axis=1)
                               - 0.5 * n_src)
            src_bit = v_src > 0.5                       # (T, w)
            if self.error_model == "analog":
                draws = rng.random(src_bit.shape, dtype=self._noise_dtype)
                ok = np.empty(src_bit.shape, dtype=bool)
                for b, sl in enumerate(self._bank_slices()):
                    np.less(draws[sl],
                            self._not_plane(b, stripe, fact, reg_f[b],
                                            reg_l[b]), out=ok[sl])
            else:
                ok = np.ones(src_bit.shape, dtype=bool)
            out_l, out_f = np.where(ok, ~src_bit, src_bit), src_bit
        else:
            # ---- Boolean-op protocol: comparator across the stripe ----
            n_f, n_l = fact.n_rf, fact.n_rl
            u_f = A.u_n(n_f, self.params)
            u_l = A.u_n(n_l, self.params)
            ref_cells = arr_f[:, rows_f, f_cols]
            v_f = u_f * (np.sum(ref_cells, axis=1) - 0.5 * n_f)
            # noise context: the reference level sets the common mode
            # (V_REF > VDD/2 -> AND-family, < VDD/2 -> OR-family); every
            # bank runs the same op with same-sign references
            ctx = {float(np.mean(v_f[sl])) >= 0.0
                   for sl in self._bank_slices()}
            if len(ctx) != 1:
                raise FusedExecutionError(
                    "reference common-mode sign differs across banks")
            op_ctx = "and" if ctx.pop() else "or"
            if self.error_model == "analog" \
                    and self._resolve_backend() == "pallas":
                out = self._resolve_pallas(
                    arr_l[:, rows_l, l_cols], ref_cells,
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
            out_l = np.asarray(out, dtype=bool)
            out_f = ~out_l
        # every activated row of a side now holds that side's plane: keep
        # the planes until a row of them is read or reused (``_cells``)
        tracing.count("sim.apa_rows", fact.n_rf + fact.n_rl)
        self._pending[l_sub] = (rows_l, l_cols, out_l)
        self._pending[f_sub] = (rows_f, f_cols, out_f)
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
    def global_addr(self, sub: int, row):
        if isinstance(row, PerBank):
            return PerBank(sub * self.geom.rows_per_subarray + row.vals)
        return sub * self.geom.rows_per_subarray + row

    def read_shared_word(self, sub: int, row: int, sl: slice) -> np.ndarray:
        """Digital value of one shared-column half of a row, in j order —
        the ISA's result readout ((w,), or (T, w) batched).  Logged as a
        full RD: the host pulls the row over the DDR bus to get the word."""
        i = self._row(sub, row)
        self._log_rd(sub)
        pending = self._pending.get(sub)
        if pending is not None and pending[1] == sl and i in pending[0]:
            bits = pending[2]               # the row holds the plane
        else:                               # no pending plane covers the word
            bits = self._subarrays[sub][:, i, sl] > 0.5
        return self._out(bits.astype(np.uint8))

    def snapshot_rows(self, sub: int, rows) -> np.ndarray:
        """(n_rows, row_bits) digital snapshot; (T, n_rows, row_bits) when
        batched."""
        idx = self._map_rows(sub, rows)
        arr = self._cells(sub)
        return self._out((arr[:, idx][..., self._invperm] > 0.5)
                         .astype(np.uint8))
