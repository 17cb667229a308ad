"""PuD engine: backend dispatch + offload accounting.

The framework-facing entry point for bulk Boolean work.  Three backends
share identical semantics:

  * ``jnp``    — plain jax ops (the oracle / fastest on CPU),
  * ``pallas`` — the packed-uint32 TPU kernels (repro.kernels),
  * ``dram``   — the FCDRAM simulator through the ISA (command-accurate,
                 optionally noisy; width-limited by the DRAM row).

Every call is metered: the engine accumulates the DDR4 command cost the
*same* work would incur in-DRAM versus the processor-centric baseline
(read operands over the bus, compute, write back), quantifying the paper's
motivation for each workload that routes through it
(``OffloadReport``).

The ``dram`` backend is *chunk-batched*: a bit-plane wider than one DRAM
word is split into row-sized chunks, and each block of chunks executes as
the trial axis of one ``BankSim(trials=C)`` episode (all chunks of a block
run the same command sequence on the same activation pair).  The legacy
path advanced the scrambled pair walk per chunk; to keep noisy-mode error
statistics region-mixed, planes with >= 4 chunks are split over at least
``DRAM_MIN_PAIR_SWEEP`` blocks, each advancing the pair cursor.  Every
block additionally gets an independent noise stream (a
``np.random.SeedSequence(seed).spawn`` child reseeds the cached sim via
``BankSim.reseed_noise``) so error patterns never repeat across blocks or
planes while the simulated chip — decoder map + static offsets — stays
the same.

Compiled Boolean *programs* (``repro.core.compiler.Program``) execute on
any backend through :meth:`PudEngine.run_program`: jnp / Pallas run each
instruction on whole packed planes; dram runs the trial-batched program
executor (``compiler.run_sim``) per chunk block.  ``add`` routes in-DRAM
arithmetic the same way.

Program execution on the dram backend defaults to the **scheduled
resident-register** executor (``ResidentPolicy.SCHEDULED``):
intermediates chain in-bank via RowClone instead of round-tripping
through the host between instructions, the compile-time scheduler
converts polarity spills into dual-form producer duplications, and chunk
blocks chain through ``ResidentSession`` (constant rows + pinned input
words stay in the bank between blocks).  The ``OffloadReport`` books
RowClones (``report.rowclones``) in place of most host staging writes
(``report.staged_bytes``).  ``GREEDY`` is the bit-for-bit PR-3 resident
reference and ``HOST`` the host-staged reference path (legacy
``resident=True/False/"greedy"/"scheduled"`` spellings coerce with a
one-shot DeprecationWarning).  On the dram backend the report's
dram-side cost is *measured* from the simulator's command log rather
than modeled, so all modes are compared on the commands they actually
issued.

The whole configuration can be passed as one frozen
:class:`~repro.core.policy.EngineConfig`
(``PudEngine(EngineConfig(backend="dram", banks=16))``); the individual
kwargs keep working and build the equivalent config.

**Multi-bank sharding** (``banks=N`` on the dram backend): the engine
holds a :class:`~repro.core.bankarray.BankArray` of N independent
per-bank chips (own decoder maps, static offsets and noise streams) and
deals chunk blocks round-robin across them — block j runs on bank
``j % N``.  Banks operate concurrently in real DRAM, so the array-level
modeled time is the *makespan* over per-bank command logs (the
``BankArray`` owns that accounting); the OffloadReport keeps per-bank
sub-ledgers (``report.bank(b)``) next to the array totals.  Under the
scheduled policy the ~0.5 s planner search runs once on bank 0 and
sibling banks replay the frozen decisions.  ``banks=1`` is bit-for-bit
the single-bank engine.

**Fused multi-bank rounds** (``fused``, dram backend): instead of
looping bank-by-bank, each round of ``banks`` same-size chunk blocks is
stacked onto the trial axis of one
:class:`~repro.core.fused.FusedPudIsa` episode — a single
``(banks * block, w)`` array pass whose per-bank slices are
bit-identical to the loop path's per-bank results *and* command logs
(per-bank chip identity and noise streams ride along as batched
parameters; see ``repro.core.fused``).  ``fused=None`` (default)
auto-enables this whenever it is loop-parity-safe (>1 bank,
simultaneous-activation module); ``False`` keeps the bit-exact per-bank
loop as the reference; ``True`` forces it (raising when it cannot
apply).  Compiled programs fuse under the host-staged policy only —
resident row plans are seed-dependent per bank — and single-chunk /
ragged final blocks always stay on the loop.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..core import compiler as CC
from ..core.bankarray import BankArray
from ..core.device import ENERGY_PJ, ActivationSupport, get_module
from ..core.fused import FusedGeometryError
from ..core.isa import CostModel, OpCost, PudIsa
from ..core.policy import EngineConfig, ResidentPolicy, coerce_resident
from ..core.simulator import BankSim
from ..kernels import ops as kops

BACKENDS = ("jnp", "pallas", "dram")


@lru_cache(maxsize=16)
def _adder_program(k: int) -> CC.Program:
    """K-bit ripple-carry adder lowered to the native PuD op set."""
    return CC.compile_expr(CC.adder_exprs(k))


@jax.jit
def stack_planes(*planes: jax.Array) -> jax.Array:
    """Equal-shape ``(R, C)`` planes -> one ``(n, R, C)`` operand stack in
    one device program (a fused copy).  jit's own cache keys it on the
    arity and the plane shape; an eager ``jnp.stack`` would dispatch one
    ``broadcast_in_dim`` per plane and then a ``concatenate``."""
    return jnp.stack(planes)


@dataclass
class OffloadReport:
    """Accumulated in-DRAM vs CPU-baseline cost of engine traffic.

    ``ops``/``bits`` count logical PuD instructions and the logical bits
    each processed — backend-invariant by construction (every backend
    meters the *synthesized native instruction stream*, so e.g. ``add``
    books the same ops/bits on jnp, pallas and dram).  ``dram``/``cpu``
    aggregate the modeled DDR4 command costs; on the dram backend the
    dram side is *measured* from the simulator's command log instead of
    modeled, so staging traffic (host WR/RD) shows up exactly as issued.
    ``rowclones`` counts in-bank RowClone copies (resident-register
    execution stages operands with these instead of host writes) and
    ``staged_bytes`` the bytes the host pushed over the bus to stage
    operand/reference rows — the resident executor's headline is cutting
    ``staged_bytes`` while ``rowclones`` grows.

    **Field layout on a multi-bank engine** — two levels:

    * *array level* (the fields above): ``ops``/``bits``/``cpu`` count
      logical work and its processor-centric baseline — properties of
      the workload, not of any bank — and ``dram``/``rowclones``/
      ``staged_bytes`` accumulate the measured cost over **all** banks.
    * *per bank* (``banks``): every simulator-executed call also books
      its measured quantities into the sub-report of the bank it ran on
      (``report.bank(b)``) — only ``dram``/``rowclones``/
      ``staged_bytes`` are populated there (logical fields stay 0).

    :meth:`merged` folds the per-bank ledgers back into one array-level
    view; it matches the top-level measured side exactly for
    simulator-executed traffic (modeled entries — e.g. ``popcount``,
    which has no simulator path — are array-level only and not
    attributed to a bank).
    """

    ops: int = 0
    bits: int = 0
    dram: OpCost = field(default_factory=OpCost)
    cpu: OpCost = field(default_factory=OpCost)
    rowclones: int = 0
    staged_bytes: int = 0
    #: per-bank measured sub-reports (dram backend): bank index -> report
    banks: dict = field(default_factory=dict)
    #: rank-level timing (array level only, dram backend; stamped by
    #: :meth:`PudEngine.schedule_timing`): the optimistic
    #: independent-bank makespan next to the rank-legal one, with the
    #: legality cost split into cross-bank arbitration and refresh
    makespan_ns: float = 0.0
    legal_makespan_ns: float = 0.0
    rank_stall_ns: float = 0.0
    refresh_stall_ns: float = 0.0

    def bank(self, b: int) -> "OffloadReport":
        """The (auto-created) measured sub-report of one bank."""
        sub = self.banks.get(b)
        if sub is None:
            sub = self.banks[b] = OffloadReport()
        return sub

    def merged(self) -> "OffloadReport":
        """One array-level view folding the per-bank ledgers together:
        logical fields copied from this report, measured fields summed
        over ``banks`` (or copied verbatim when no bank ever booked —
        non-dram backends)."""
        m = OffloadReport(ops=self.ops, bits=self.bits, cpu=self.cpu)
        if not self.banks:
            m.dram, m.rowclones = self.dram, self.rowclones
            m.staged_bytes = self.staged_bytes
            return m
        for b in sorted(self.banks):
            sub = self.banks[b]
            m.dram = m.dram + sub.dram
            m.rowclones += sub.rowclones
            m.staged_bytes += sub.staged_bytes
        return m

    @property
    def energy_saving(self) -> float:
        if self.cpu.energy_pj == 0:
            return 0.0
        return 1.0 - self.dram.energy_pj / self.cpu.energy_pj

    @property
    def bus_bytes_avoided(self) -> int:
        return self.cpu.bus_bytes - self.dram.bus_bytes

    @property
    def host_bytes_moved(self) -> int:
        """Bytes that crossed the host DDR bus on the in-DRAM side
        (operand/reference staging WRs + result RDs) — measured from the
        command log on the dram backend, modeled elsewhere.  The
        workload-level comparison number: the CPU baseline moves
        ``cpu.bus_bytes`` for the same logical work."""
        return self.dram.bus_bytes

    def summary(self) -> dict:
        return {
            "ops": self.ops,
            "bits": self.bits,
            "dram_time_us": self.dram.time_ns / 1e3,
            "cpu_time_us": self.cpu.time_ns / 1e3,
            "dram_energy_uj": self.dram.energy_pj / 1e6,
            "cpu_energy_uj": self.cpu.energy_pj / 1e6,
            "energy_saving": self.energy_saving,
            "bus_bytes_avoided": self.bus_bytes_avoided,
            "host_bytes_moved": self.host_bytes_moved,
            "rowclones": self.rowclones,
            "staged_bytes": self.staged_bytes,
            "makespan_ns": self.makespan_ns,
            "legal_makespan_ns": self.legal_makespan_ns,
            "rank_stall_ns": self.rank_stall_ns,
            "refresh_stall_ns": self.refresh_stall_ns,
        }


class PudEngine:
    """Bulk-Boolean execution engine with cost metering.

    Data model: *bit-planes* — uint32-packed 2D arrays (R, C) representing
    R x 32C logical bits (one DRAM row = one plane row chunk).
    """

    #: max chunks executed as one batched trial axis (bounds sim memory)
    DRAM_CHUNK_BATCH = 32
    #: min activation pairs swept per plane (region mixing in noisy mode)
    DRAM_MIN_PAIR_SWEEP = 4

    def __init__(self, backend: "str | EngineConfig" = "jnp", *,
                 config: EngineConfig | None = None,
                 module: str | None = None,
                 noisy: bool = False, seed: int = 0,
                 resident: "ResidentPolicy | bool | str | None" = None,
                 chain_blocks: bool = True, banks: int = 1,
                 fused: bool | None = None,
                 verify: bool | None = None):
        if isinstance(backend, EngineConfig):
            if config is not None:
                raise ValueError("pass the EngineConfig positionally or "
                                 "as config=, not both")
            config = backend
        if config is not None:
            backend = config.backend
            module = config.module
            noisy = config.noisy
            seed = config.seed
            resident = config.resident
            chain_blocks = config.chain_blocks
            banks = config.banks
            fused = config.fused
            verify = config.verify
        assert backend in BACKENDS, backend
        self.backend = backend
        self.module = get_module(module) if module else get_module()
        self.cost_model = CostModel(self.module)
        self.report = OffloadReport()
        self.noisy = noisy
        self.seed = seed
        #: dram backend: how compiled programs execute — a
        #: :class:`~repro.core.policy.ResidentPolicy`.  Default (None):
        #: ``SCHEDULED`` on the dram backend — intermediates chain
        #: in-bank via RowClone under the compile-time polarity/residency
        #: scheduler (duplication instead of polarity spills, pinned
        #: input words across chunk blocks); the ~0.5 s planning pass
        #: amortizes through a frozen-decision cache keyed on (program,
        #: isa geometry).  ``GREEDY`` is the bit-for-bit PR-3 resident
        #: reference; ``HOST`` the host-staged reference path.  Legacy
        #: plain ``True``/``False``/``"greedy"``/``"scheduled"`` coerce
        #: with a one-shot DeprecationWarning.
        self.policy = coerce_resident(
            resident, where="PudEngine",
            default=(ResidentPolicy.SCHEDULED if backend == "dram"
                     else ResidentPolicy.HOST))
        #: legacy tri-state spelling (``False`` | ``"greedy"`` |
        #: ``"scheduled"``) — kept for callers that predate
        #: :attr:`policy`; both always agree
        self.resident = self.policy.to_legacy()
        #: the full (frozen) configuration this engine runs under
        self.config = EngineConfig(
            backend=backend, module=module if isinstance(module, str)
            else None, noisy=noisy, seed=seed, resident=self.policy,
            chain_blocks=chain_blocks, banks=banks, fused=fused,
            verify=verify)
        #: resident mode: chain residency across chunk *blocks* — the
        #: in-bank constant rows block k leaves behind feed block k+1 via
        #: RowClone instead of fresh host writes (``False`` restores the
        #: PR-3 per-block restaging for comparison)
        self.chain_blocks = chain_blocks
        #: dram backend: number of independent banks chunk blocks are
        #: dealt across (round-robin); other backends have no banks
        self.banks = banks
        #: dram backend: fused execution tri-state — ``None`` (auto)
        #: stacks each round of ``banks`` same-size chunk blocks into one
        #: bank-fused episode when that is loop-parity-safe; ``False``
        #: keeps the per-bank loop (the bit-exact reference); ``True``
        #: forces fusion (``FusedGeometryError`` when it cannot apply)
        self.fused = fused
        #: static plan-verification tri-state: ``True`` verifies every
        #: resident plan the engine schedules
        #: (:func:`repro.analysis.verify_plan`), ``False`` never does,
        #: ``None`` defers to :func:`repro.analysis.default_verify`
        #: (on under pytest, off in benchmarks)
        self.verify = verify
        self._isa: PudIsa | None = None
        self._array: BankArray | None = None
        if backend == "dram":
            #: N per-bank chips; bank 0 IS the single-bank engine's chip
            #: (same seed, spawn-identical noise streams), so ``banks=1``
            #: reproduces the legacy engine bit-for-bit
            self._array = BankArray(
                self.module, banks=banks, seed=seed,
                error_model="analog" if noisy else "ideal")
            self._isa = self._array.isa(0)
            reasons = []
            if banks <= 1:
                reasons.append("banks=1 has nothing to fuse")
            if self.module.activation is not ActivationSupport.SIMULTANEOUS:
                reasons.append(
                    f"{self.module.name} activates sequentially (per-bank "
                    "decoder-miss retries diverge)")
            if fused is None:
                self._fuse_ok = not reasons
            elif fused and reasons:
                raise FusedGeometryError(
                    "fused=True but fusion cannot apply: "
                    + "; ".join(reasons))
            else:
                self._fuse_ok = bool(fused)
        elif banks != 1:
            raise ValueError(
                f"banks={banks}: only the dram backend has banks")
        else:
            if fused:
                raise ValueError(
                    "fused=True: only the dram backend has banks to fuse")
            self._fuse_ok = False

    def _isa_for(self, n_chunks: int, *, recycle: bool = True,
                 bank: int = 0) -> PudIsa:
        """ISA for one chunk block on one bank: a trial-batched BankSim
        with ``n_chunks`` trials (cached per (bank, batch size);
        single-chunk work uses the bank's scalar sim).  Each call
        dedicates an independent noise stream to the block — cached sims
        are *rebuilt* from the bank's identity seed per batch size, so
        without reseeding, equal-trial blocks of different calls (and the
        leading trials of different-size blocks) would draw identical
        error patterns.  Row slots are recycled so the working set stays
        bounded by one op's rows; ``recycle=False`` preserves them
        (cross-block residency: a later block RowClones constant rows an
        earlier block of the same size left in the bank)."""
        if n_chunks <= 1:
            isa = self._array.isa(bank)
        else:
            isa = self._array.isa(bank, n_chunks, track_unshared=False)
        isa.sim.reseed_noise(self._array.next_noise_seed(bank))
        if recycle:
            isa.sim.recycle_rows()
        return isa

    def _fused_isa_for(self, k: int, t: int, full_isa):
        """Fused ISA for one round of ``k`` same-size chunk blocks (one
        per bank, banks 0..k-1): reseeded with exactly the per-bank noise
        seeds the loop path's ``_isa_for`` calls would spawn for those
        blocks, rows recycled like every loop block does.  A bank-subset
        tail round (``k < banks``) first adopts the full-width ISA's
        per-bank pair cursors so each bank's pair walk stays continuous
        (the caller absorbs them back afterwards)."""
        seeds = [self._array.next_noise_seed(b) for b in range(k)]
        fisa = self._array.fused_isa(n_banks=k, trials=t)
        if full_isa is not None and fisa is not full_isa:
            fisa.adopt_state(full_isa)
        fisa.sim.reseed_noise(seeds)
        fisa.sim.recycle_rows()
        return fisa

    def _fuse_plan(self, n_chunks: int, blk_sz: int) -> int:
        """Number of *full-size* chunk blocks the fused path may stack
        for this dispatch (0 = run the per-bank loop for everything).
        Single-chunk blocks keep the loop (they run on the banks' scalar
        sims), as does a single full block (nothing to stack); a ragged
        final block always stays on the loop — both engines run it
        through the identical ``_isa_for`` call."""
        if not self._fuse_ok or blk_sz <= 1:
            return 0
        full = n_chunks // blk_sz
        return full if full > 1 else 0

    # ------------- accounting -------------
    def _meter(self, op: str, n_inputs: int, n_bits: int, *,
               modeled: bool | None = None) -> None:
        """Book one logical instruction: ops/bits + the CPU baseline on
        every backend; the *modeled* in-DRAM command cost unless the call
        executes on the simulator (dram backend), whose cost is measured
        from the sim log instead — :meth:`_account_sim_log` — so staging
        traffic is charged exactly as issued, not idealized away."""
        w = self.module.geometry.shared_bits
        rows = max(1, -(-n_bits // w))      # DRAM rows touched per operand
        self.report.ops += 1
        self.report.bits += n_bits
        n = 1 if op == "not" else max(n_inputs, 2)
        self.report.cpu = self.report.cpu + self.cost_model.cpu_baseline(
            n, rows)
        if modeled is None:
            modeled = self.backend != "dram"
        if not modeled:
            return
        if op == "not":
            dram = self.cost_model.op_not(1)
        else:
            dram = self.cost_model.boolean(n)
        self.report.dram = self.report.dram + dram.scaled(rows)

    def _account_sim_log(self, sim: BankSim, before: tuple,
                         bank: int | None = None) -> None:
        """Fold the sim's command-log delta since ``before`` into the
        report's dram side: measured time/energy, host WR/RD bus bytes,
        RowClone and staging counters.  With ``bank`` given, the same
        measured quantities are also booked into that bank's sub-report
        (``report.bank(bank)``) so per-bank ledgers stay next to the
        array totals.

        The sim log books WR/RD at on-die (array access) cost; the
        off-chip IO energy and burst transfer time that the modeled
        CostModel and the CPU baseline include are added here per
        transferred row, so measured and modeled report sides stay
        comparable."""
        t0, e0, c0 = before
        log = sim.log
        counts = {k: v - c0.get(k, 0) for k, v in log.counts.items()}
        row_bytes = sim.geom.row_bits // 8
        wr = counts.get("WR", 0)
        rd = counts.get("RD", 0)
        n_bursts = max(row_bytes // 64, 1)
        io_rows = wr + rd
        cost = OpCost(
            (log.time_ns - t0)
            + io_rows * n_bursts * 4 * self.cost_model.t.tCK,
            (log.energy_pj - e0)
            + io_rows * n_bursts * ENERGY_PJ["io_per_64B"],
            commands=sum(counts.values()),
            bus_bytes=io_rows * row_bytes)
        targets = [self.report]
        if bank is not None:
            targets.append(self.report.bank(bank))
        for rep in targets:
            rep.dram = rep.dram + cost
            rep.rowclones += counts.get("RC", 0)
            rep.staged_bytes += wr * row_bytes

    @staticmethod
    def _log_snapshot(sim: BankSim) -> tuple:
        return (sim.log.time_ns, sim.log.energy_pj, dict(sim.log.counts))

    def _meter_program(self, prog: CC.Program, n_bits: int) -> None:
        """Meter a compiled program's native compute instructions — the
        single definition both ``run_program`` and the fused-kernel ``add``
        use, keeping ops/bits backend-invariant by construction."""
        for i in prog.instrs:
            if i.op == "not":
                self._meter("not", 1, n_bits)
            elif i.op in ("and", "or", "nand", "nor"):
                self._meter(i.op, len(i.srcs), n_bits)

    # ------------- ops on packed planes -------------
    def nary(self, planes: jax.Array, op: str) -> jax.Array:
        """planes: (N, R, C) uint32 -> (R, C)."""
        n, r, c = planes.shape
        self._meter(op, n, r * c * 32)
        if self.backend == "pallas":
            return kops.nary_bitwise(planes, op)
        if self.backend == "dram":
            return self._dram_nary(planes, op)
        return kops.ref.nary_bitwise(op, planes)

    def not_(self, plane: jax.Array) -> jax.Array:
        r, c = plane.shape
        self._meter("not", 1, r * c * 32)
        if self.backend == "pallas":
            return kops.bitwise_not(plane)
        if self.backend == "dram":
            return self._dram_not(plane)
        return ~plane

    def add(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Bit-serial adder: (K, R, C) + (K, R, C) -> (K+1, R, C).

        jnp/pallas use the fused ripple-carry kernel; the dram backend
        synthesizes the adder from the paper's native op set
        (``compiler.adder_exprs``) and runs it through the trial-batched
        program executor.  *Every* backend meters the same synthesized
        native instruction stream, so ``OffloadReport.ops``/``bits`` are
        backend-invariant (the jnp/pallas kernels fuse the 12K ops into
        one call, but the work they stand in for is identical).
        """
        k, r, c = a.shape
        prog = _adder_program(k)
        if self.backend == "dram":
            planes = {f"a{i}": a[i] for i in range(k)} \
                | {f"b{i}": b[i] for i in range(k)}
            out = self.run_program(prog, planes)
            return jnp.stack([*(out[f"s{i}"] for i in range(k)),
                              out["cout"]])
        self._meter_program(prog, r * c * 32)
        if self.backend == "pallas":
            return kops.add_planes(a, b)
        return kops.ref.add_planes(a, b)

    def popcount(self, planes: jax.Array) -> jax.Array:
        n = planes.shape[0]
        # no simulator path: always the modeled in-DRAM equivalent cost
        self._meter("and", n, planes.size * 32, modeled=True)
        if self.backend == "pallas":
            return kops.bitcount_planes(planes)
        return kops.ref.bitcount_planes(planes)

    # ------------- rank-level timing -------------
    def schedule_timing(self):
        """Rank-legal schedule of everything this engine has executed.

        Runs the :mod:`repro.analysis.schedule` event-driven scheduler
        over the dram backend's accumulated BankArray command logs and
        stamps the resulting makespans/stalls onto :attr:`report` (so
        ``report.summary()`` carries both timing models).  Returns the
        :class:`~repro.analysis.ScheduledTimeline`; raises on non-dram
        backends (no command logs to schedule)."""
        if self._array is None:
            raise RuntimeError("schedule_timing() needs the dram backend"
                               " (no command logs on jnp/pallas)")
        from repro import analysis
        tl = analysis.schedule_bank_array(self._array)
        self.report.makespan_ns = float(self._array.makespan_ns())
        self.report.legal_makespan_ns = tl.legal_makespan_ns
        self.report.rank_stall_ns = tl.rank_stall_ns
        self.report.refresh_stall_ns = tl.refresh_stall_ns
        return tl

    # ------------- compiled Boolean programs -------------
    def run_program(self, prog: CC.Program,
                    planes: dict[str, jax.Array]) -> dict[str, jax.Array]:
        """Execute a compiled :class:`~repro.core.compiler.Program` over
        packed ``(R, C)`` uint32 bit-planes on this backend.

        ``planes`` maps the program's input names to equal-shape planes;
        returns one plane per program output.  jnp/pallas execute each
        instruction on whole planes; the dram backend splits the planes
        into row chunks and runs the trial-batched program executor
        (``compiler.run_sim``) one chunk block at a time — by default
        through the *scheduled resident-register* executor, with chunk
        blocks of one size chained through a
        :class:`~repro.core.compiler.ResidentSession` (in-bank constant
        rows and pinned input words carry between blocks).  Every compute
        instruction is metered into the :class:`OffloadReport` (operand
        staging is not; it is counted in ``Program.cost``).

        >>> import jax.numpy as jnp
        >>> from repro.core import compiler as CC
        >>> from repro.pud.engine import PudEngine
        >>> prog = CC.compile_expr(CC.Xor(CC.Var("a"), CC.Var("b")))
        >>> eng = PudEngine("jnp")
        >>> a = jnp.asarray([[5]], jnp.uint32)
        >>> b = jnp.asarray([[3]], jnp.uint32)
        >>> int(eng.run_program(prog, {"a": a, "b": b})["out"][0, 0])
        6
        >>> eng.report.ops                      # 4 NANDs were metered
        4
        """
        if not planes:
            raise ValueError("run_program needs at least one input plane")
        with tracing.span("engine.run_program"):
            named = {k: jnp.asarray(v, jnp.uint32) for k, v in planes.items()}
            shapes = {v.shape for v in named.values()}
            if len(shapes) != 1:
                raise ValueError(f"input planes disagree on shape: {shapes}")
            (shape,) = shapes
            missing = {i.name for i in prog.instrs if i.op == "input"} \
                - named.keys()
            if missing:       # validate before metering: a failed run must not
                raise ValueError(   # inflate the offload report
                    f"program inputs missing from planes: {sorted(missing)}")
            r, c = shape
            with tracing.span("engine.meter"):
                self._meter_program(prog, r * c * 32)
            if self.backend == "dram":
                return self._dram_run_program(prog, named, shape)
            return self._planes_run_program(prog, named, shape)

    def _planes_run_program(self, prog: CC.Program, planes, shape):
        """Whole-plane program execution (jnp ops or Pallas kernels)."""
        pallas = self.backend == "pallas"
        regs: dict[int, jax.Array] = {}
        for i in prog.instrs:
            if i.op == "input":
                regs[i.dst] = planes[i.name]
            elif i.op == "const":
                fill = jnp.uint32(0xFFFFFFFF if i.value else 0)
                regs[i.dst] = jnp.full(shape, fill, jnp.uint32)
            elif i.op == "not":
                with tracing.span("engine.kernel"):
                    regs[i.dst] = (kops.bitwise_not(regs[i.srcs[0]])
                                   if pallas else ~regs[i.srcs[0]])
            elif i.op in ("and", "or", "nand", "nor"):
                with tracing.span("engine.stack"):
                    stack = stack_planes(*(regs[s] for s in i.srcs))
                tracing.count("engine.stack_bytes", stack.nbytes)
                with tracing.span("engine.kernel"):
                    regs[i.dst] = (kops.nary_bitwise(stack, i.op) if pallas
                                   else kops.ref.nary_bitwise(i.op, stack))
            else:
                raise ValueError(i.op)
        return {k: regs[v] for k, v in prog.outputs.items()}

    def _dram_run_program(self, prog: CC.Program, planes, shape):
        """Chunk-blocked program execution on the DRAM simulator: each
        block of row chunks runs the whole program as one trial-batched
        ``compiler.run_sim`` episode — through the scheduled resident-
        register executor by default (intermediates chain in-bank via
        RowClone and only program outputs cross the bus), host-staged
        when the engine was built with ``resident=False``.

        Resident mode additionally chains residency across blocks
        (``chain_blocks``): blocks of one (bank, size) share a
        ``compiler.ResidentSession``, so the reference/identity constant
        rows block k staged stay in the bank and block k+1 RowClones them
        instead of paying fresh host writes — and under the scheduled
        policy the session also *pins input words*: a block whose input
        word equals the previous block's (e.g. a broadcast operand)
        RowClones the pinned row instead of re-staging it.  Every block
        still gets its own noise stream (``reseed_noise``) — persistent
        rows change what the host *writes*, not what the chip *draws*.

        An input plane whose row chunks are all *identical* (a broadcast
        operand) is handed to each block as one ``(w,)`` word instead of
        a ``(t, w)`` stack: the executor broadcasts it across the trial
        axis, so it is staged into the bank once per block (and, pinned,
        once per session) rather than once per chunk.

        With ``banks > 1`` blocks are dealt round-robin across the
        array — block j on bank ``j % banks`` — each bank chaining its
        own sessions; under the scheduled policy bank 0's session runs
        the planner search and sibling banks replay its frozen decisions
        (plans are seed-dependent, decisions are not).

        With fusion enabled and the host-staged policy, each round of
        ``banks`` same-size blocks instead runs the whole program as one
        bank-stacked ``run_sim`` episode (``FusedPudIsa``) — per-bank
        results and command logs stay bit-identical to the loop path."""
        r, c = shape
        n_bits = r * c * 32
        w = self._isa.width
        chunks = {name: self._to_chunks(
            np.asarray(kops.ref.unpack_bits(p)).reshape(n_bits), w)
            for name, p in planes.items()}           # each (C, w)
        n_chunks = -(-n_bits // w)
        # chunk-constant planes broadcast as one word per block (zero
        # padding makes a ragged last chunk differ, disabling the
        # collapse — conservative and correct)
        const = {name: n_chunks > 1 and bool((ch == ch[0]).all())
                 for name, ch in chunks.items()}
        blk_sz = self._block_size(n_chunks)
        pieces: dict[str, list[np.ndarray]] = {k: [] for k in prog.outputs}
        chain = self.policy.is_resident and self.chain_blocks
        sessions: dict[tuple[int, int], CC.ResidentSession] = {}
        shared = None       # bank-0 adjudicated decisions, non-chained
        # same-program chunk blocks fuse across banks only under the
        # host-staged policy: resident row plans are seed-dependent per
        # bank, so fused resident execution could not be loop-exact
        full = (self._fuse_plan(n_chunks, blk_sz)
                if self.policy is ResidentPolicy.HOST else 0)
        full_isa = None
        for j0 in range(0, full, self.banks):        # fused rounds
            k = min(self.banks, full - j0)
            fisa = self._fused_isa_for(k, blk_sz, full_isa)
            lo = j0 * blk_sz
            kt = k * blk_sz
            ins = {name: (ch[0] if const[name] else ch[lo:lo + kt])
                   for name, ch in chunks.items()}
            before = self._log_snapshot(fisa.sim)
            res = CC.run_sim(prog, ins, fisa, resident=self.policy)
            for b in range(k):
                self._account_sim_log(fisa.sim, before, bank=b)
            for name in pieces:
                v = np.asarray(res[name])
                if v.ndim == 1:     # broadcast input passed through
                    v = np.broadcast_to(v, (kt, w))
                pieces[name].extend(fisa.split_banks(v))
            if k == self.banks:
                full_isa = fisa
            elif full_isa is not None:
                full_isa.absorb_state(fisa)

        def bank0_fixed():
            """Frozen scheduler decisions for sibling-bank replay: taken
            from a bank-0 session that already planned, else computed
            once on bank 0's scalar isa (memoized in _SCHED_CACHE)."""
            for (b, _t), s in sessions.items():
                if b == 0 and s._fixed is not None:
                    return s._fixed
            return CC.shared_schedule_decisions(prog, self._array.isa(0),
                                                pin_inputs=chain)

        for j, lo in enumerate(range(full * blk_sz, n_chunks, blk_sz),
                               start=full):          # loop leftovers
            t = min(blk_sz, n_chunks - lo)
            bank = j % self.banks
            ins = {}
            for name, ch in chunks.items():
                ins[name] = (ch[0] if const[name]
                             else ch[lo] if t == 1 else ch[lo:lo + t])
            isa = self._isa_for(t, bank=bank,
                                recycle=not (chain and (bank, t) in
                                             sessions))
            before = self._log_snapshot(isa.sim)
            if chain:
                sess = sessions.get((bank, t))
                if sess is None:
                    fixed = None
                    if (bank != 0
                            and self.policy is ResidentPolicy.SCHEDULED):
                        fixed = bank0_fixed()
                    sess = sessions[(bank, t)] = CC.ResidentSession(
                        prog, isa, policy=self.policy.value, fixed=fixed,
                        verify=self.verify)
                res = sess.run(ins)
            else:
                plan = None
                if (bank != 0
                        and self.policy is ResidentPolicy.SCHEDULED):
                    if shared is None:
                        shared = bank0_fixed()
                    plan = CC.schedule_resident(prog, isa,
                                                policy="scheduled",
                                                verify=self.verify,
                                                _fixed=shared)
                res = CC.run_sim(prog, ins, isa, resident=self.policy,
                                 plan=plan)
            if t == 1:
                res = {k: np.asarray(v)[None] for k, v in res.items()}
            else:       # (w,) pass-through of a broadcast input -> (t, w)
                res = {k: (np.broadcast_to(v, (t, w))
                           if np.asarray(v).ndim == 1 else v)
                       for k, v in res.items()}
            self._account_sim_log(isa.sim, before, bank=bank)
            for name in pieces:
                pieces[name].append(res[name])
        out = {}
        for name, ps in pieces.items():
            flat = np.concatenate(ps, axis=0).reshape(-1)[:n_bits]
            out[name] = kops.ref.pack_bits(
                jnp.asarray(flat.reshape(r, c * 32)))
        return out

    # ------------- DRAM backend plumbing -------------
    def _block_size(self, n_chunks: int) -> int:
        """Chunks per batched episode: capped by DRAM_CHUNK_BATCH, and
        small enough that a plane sweeps >= DRAM_MIN_PAIR_SWEEP activation
        pairs (one per block) when it has that many chunks."""
        target = max(1, -(-n_chunks // self.DRAM_MIN_PAIR_SWEEP))
        return min(self.DRAM_CHUNK_BATCH, target)

    @staticmethod
    def _to_chunks(bits: np.ndarray, w: int) -> np.ndarray:
        """(..., B) bit vector -> (..., C, w) zero-padded row chunks."""
        n_bits = bits.shape[-1]
        n_chunks = -(-n_bits // w)
        pad = n_chunks * w - n_bits
        if pad:
            bits = np.pad(bits,
                          [*[(0, 0)] * (bits.ndim - 1), (0, pad)])
        return bits.reshape((*bits.shape[:-1], n_chunks, w))

    def _dram_nary(self, planes: jax.Array, op: str) -> jax.Array:
        pl = np.asarray(planes)
        n, r, c = pl.shape
        bits = np.asarray(kops.ref.unpack_bits(jnp.asarray(pl))).reshape(
            n, r * c * 32)
        w = self._isa.width
        chunks = self._to_chunks(bits, w)            # (n, C, w)
        n_chunks = chunks.shape[1]
        blk_sz = self._block_size(n_chunks)
        pieces = []
        full = self._fuse_plan(n_chunks, blk_sz)
        full_isa = None
        for j0 in range(0, full, self.banks):        # fused rounds
            k = min(self.banks, full - j0)
            fisa = self._fused_isa_for(k, blk_sz, full_isa)
            lo = j0 * blk_sz
            before = self._log_snapshot(fisa.sim)
            res = fisa.nary_op(op, chunks[:, lo:lo + k * blk_sz])
            for b in range(k):
                self._account_sim_log(fisa.sim, before, bank=b)
            pieces.extend(fisa.split_banks(res))
            if k == self.banks:
                full_isa = fisa
            elif full_isa is not None:
                full_isa.absorb_state(fisa)
        for j, lo in enumerate(range(full * blk_sz, n_chunks, blk_sz),
                               start=full):          # loop leftovers
            blk = chunks[:, lo:lo + blk_sz]          # (n, C', w)
            bank = j % self.banks
            isa = self._isa_for(blk.shape[1], bank=bank)
            before = self._log_snapshot(isa.sim)
            if blk.shape[1] == 1:
                res = isa.nary_op(op, list(blk[:, 0]))[None]
            else:
                res = isa.nary_op(op, blk)           # (C', w)
            self._account_sim_log(isa.sim, before, bank=bank)
            pieces.append(res)
        out = np.concatenate(pieces, axis=0).reshape(-1)[:r * c * 32]
        return kops.ref.pack_bits(jnp.asarray(out.reshape(r, c * 32)))

    def _dram_not(self, plane: jax.Array) -> jax.Array:
        pl = np.asarray(plane)
        r, c = pl.shape
        bits = np.asarray(kops.ref.unpack_bits(jnp.asarray(pl))).reshape(
            r * c * 32)
        w = self._isa.width
        chunks = self._to_chunks(bits, w)            # (C, w)
        n_chunks = chunks.shape[0]
        blk_sz = self._block_size(n_chunks)
        pieces = []
        full = self._fuse_plan(n_chunks, blk_sz)
        full_isa = None
        for j0 in range(0, full, self.banks):        # fused rounds
            k = min(self.banks, full - j0)
            fisa = self._fused_isa_for(k, blk_sz, full_isa)
            lo = j0 * blk_sz
            before = self._log_snapshot(fisa.sim)
            res = fisa.op_not(chunks[lo:lo + k * blk_sz])
            for b in range(k):
                self._account_sim_log(fisa.sim, before, bank=b)
            pieces.extend(fisa.split_banks(res))
            if k == self.banks:
                full_isa = fisa
            elif full_isa is not None:
                full_isa.absorb_state(fisa)
        for j, lo in enumerate(range(full * blk_sz, n_chunks, blk_sz),
                               start=full):          # loop leftovers
            blk = chunks[lo:lo + blk_sz]
            bank = j % self.banks
            isa = self._isa_for(blk.shape[0], bank=bank)
            before = self._log_snapshot(isa.sim)
            if blk.shape[0] == 1:
                res = isa.op_not(blk[0])[None]
            else:
                res = isa.op_not(blk)                # (C', w)
            self._account_sim_log(isa.sim, before, bank=bank)
            pieces.append(res)
        out = np.concatenate(pieces, axis=0).reshape(-1)[:r * c * 32]
        return kops.ref.pack_bits(jnp.asarray(out.reshape(r, c * 32)))
