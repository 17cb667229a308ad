"""Characterization harness: reproduces every experiment of the paper.

Each ``fig*`` function mirrors one figure/observation of the paper and
returns plain dicts (consumed by ``benchmarks/`` which prints CSV +
model-vs-paper deltas).  Two evaluation paths:

* closed-form (default): the calibrated ``repro.core.analog`` model,
* Monte-Carlo (``mc=True``): actual command-level trials on
  :class:`~repro.core.simulator.BankSim` through the ISA, per-cell success
  over ``trials`` repetitions — the software twin of the paper's
  10,000-trial DRAM Bender methodology.

The MC path is **trial-batched by default** (``batched=True``): one
``BankSim(trials=T)`` episode per activation pair replaces T Python-level
episodes.  Row pairs are *stratified* over the 3x3 (R_F region, R_L region)
grid — the paper's protocol of sweeping rows uniformly across the subarray —
so the batched estimate targets the same region-averaged quantity as the
legacy per-trial scrambled-pair walk (``batched=False``, kept as the
reference implementation and for parity tests).  For quick sweeps at
closed-form fidelity there are also one-call jax samplers
(``model_boolean_success`` / ``model_not_success``).
Program-level characterization (``mc_program_success``) measures the same
statistic one level up: whole compiled Boolean programs (XOR-from-NANDs,
MAJ3, ripple-carry adders) execute on the noisy simulator through the
trial-batched program executor (``compiler.run_sim``), reproducing the
composed-operation reliability methodology of the follow-on PuD works
(PULSAR, Simultaneous Many-Row Activation).  ``resident=True`` runs the
same statistic through the resident-register executor (RowClone-chained
intermediates) — the command stream the paper's in-bank cost argument
actually assumes.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

from .. import tracing
from . import analog as A
from . import compiler as CC
from . import decoder as DEC
from .analog import CLOSE, FAR, MIDDLE
from .bankarray import BankArray
from .device import MODULE_ZOO, ActivationSupport, get_module
from .fused import FusedGeometryError
from .isa import PudIsa
from .policy import ResidentPolicy, coerce_resident
from .simulator import BankSim

REGION_NAMES = {CLOSE: "close", MIDDLE: "middle", FAR: "far"}
OPS = ("and", "nand", "or", "nor")
NS = (2, 4, 8, 16)
NOT_DSTS = (1, 2, 4, 8, 16, 32)
TEMPS = (50, 60, 70, 80, 95)

#: default number of stratified activation pairs per batched MC estimate —
#: one per (compute-region, reference-region) combination.
MC_PAIR_GROUPS = 9

#: group-dealing strategies for multi-bank MC sweeps
DEALERS = ("round_robin", "occupancy")


def _check_banks(banks, *, batched: bool) -> int:
    """Validate the ``banks`` argument of the mc_* entry points."""
    if isinstance(banks, bool) or not isinstance(banks, (int, np.integer)):
        raise TypeError(
            f"banks must be an int, got {type(banks).__name__}")
    banks = int(banks)
    if banks > 1 and not batched:
        raise ValueError(
            "banks > 1 requires batched=True (the per-trial reference "
            "path is single-bank)")
    return banks


def _use_fused(fused: bool | None, module, banks: int,
               dealer: str = "round_robin", *,
               resident: bool = False) -> bool:
    """Settle the ``fused`` tri-state of an MC sweep.

    ``None`` (auto) fuses exactly when it is profitable *and* provably
    loop-parity-safe: more than one bank, round-robin dealing (the fused
    group->bank layout is bank-major round-robin by construction), a
    simultaneous-activation module (sequential modules retry decoder
    misses per bank, so command sequences diverge), and host-staged
    execution (resident row plans are seed-dependent per bank).
    ``True`` forces fusion — raising :class:`FusedGeometryError` when one
    of those conditions rules it out — and ``False`` forces the loop."""
    reasons = []
    if dealer != "round_robin":
        reasons.append("occupancy dealing breaks the bank-major group "
                       "layout fusion requires")
    if module.activation is not ActivationSupport.SIMULTANEOUS:
        reasons.append(f"{module.name} activates sequentially (per-bank "
                       "decoder-miss retries diverge)")
    if resident:
        reasons.append("resident execution chains seed-dependent per-bank "
                       "row plans")
    if fused is None:
        return banks > 1 and not reasons
    if fused and reasons:
        raise FusedGeometryError(
            "fused=True but fusion cannot apply: " + "; ".join(reasons))
    return bool(fused)


# ---------------------------------------------------------------------------
# Monte-Carlo measurement through the full simulator stack
# ---------------------------------------------------------------------------
def _stratified_pairs(isa: PudIsa, n_rf: int, n_rl: int,
                      groups: int, *, seed: int) -> list[tuple[int, int]]:
    """``groups`` (R_F, R_L) address pairs cycling the 3x3 region grid.

    The paper sweeps row combinations uniformly over the subarray; the
    batched MC pins one pair per batch, so we stratify pairs across the
    (R_F region, R_L region) combinations to keep the estimate targeting
    the same region-averaged success rate as a uniform row sweep.
    """
    ps = isa.inv.pairs(n_rf, n_rl)
    if len(ps) == 0:
        from .isa import CapabilityError
        raise CapabilityError(
            f"module {isa.sim.module.name} has no {n_rf}:{n_rl} pairs")
    geom = isa.sim.geom
    reg_f = geom.distance_regions(ps[:, 0], toward_upper=isa.f_sub > isa.l_sub)
    reg_l = geom.distance_regions(ps[:, 1], toward_upper=isa.l_sub > isa.f_sub)
    buckets = {(rf, rl): np.nonzero((reg_f == rf) & (reg_l == rl))[0]
               for rf in (0, 1, 2) for rl in (0, 1, 2)}
    combos = [(rf, rl) for rf in (0, 1, 2) for rl in (0, 1, 2)]
    module, mseed = isa.sim.module, isa.sim.seed
    out = []
    for g in range(groups):
        idxs = buckets[combos[g % len(combos)]]
        if len(idxs) == 0:           # region combo unreachable on this module
            idxs = np.arange(len(ps))
        # sequential-activation modules miss on a fraction of listed pairs;
        # rescramble within the bucket until the decoder actually fires
        for salt in range(16):
            k = DEC._mix64((g + groups * salt) * 0x9E3779B97F4A7C15
                           + seed) % len(idxs)
            rf, rl = (int(x) for x in ps[idxs[k]])
            if DEC.activation_pattern(module, rf, rl, seed=mseed).n_rf:
                out.append((rf, rl))
                break
    if not out:
        from .isa import CapabilityError
        raise CapabilityError(
            f"no activating {n_rf}:{n_rl} pairs found on {module.name}")
    return out


def _deal_groups(arr: BankArray, n_groups: int,
                 dealer: str = "round_robin",
                 weights=None) -> list[int]:
    """Bank index for each of ``n_groups`` MC group slots.

    ``round_robin`` (default, the reproducible reference): group g runs
    on bank ``g % banks``.  ``occupancy`` deals each group to the bank
    with the smallest *projected* command time — its live
    ``bank_time_ns`` plus the ``weights`` (estimated per-group cost,
    uniform by default) of groups already dealt to it in this call —
    which tightens the modeled makespan whenever loads are uneven
    (``n_groups % banks != 0``, mixed fan-ins, or a pre-loaded array).
    Greedy least-loaded dealing changes which chip measures which group,
    so it trades bit-reproducibility of the round-robin estimate for
    makespan (same target statistic).
    """
    if dealer not in DEALERS:
        raise ValueError(f"unknown dealer {dealer!r} (want one of "
                         f"{DEALERS})")
    if dealer == "round_robin":
        return [g % arr.banks for g in range(n_groups)]
    load = [float(t) for t in arr.bank_time_ns()]
    if weights is None:
        w = [1.0] * n_groups
    else:
        w = [float(x) for x in weights]
        if len(w) != n_groups:
            raise ValueError(f"want {n_groups} weights, got {len(w)}")
    out = []
    for g in range(n_groups):
        b = min(range(arr.banks), key=lambda i: (load[i], i))
        load[b] += w[g]
        out.append(b)
    return out


def _bank_pair_schedule(arr: BankArray, groups: int, pairs_of, *,
                        dealer: str = "round_robin", weights=None):
    """Deal MC pair groups across the array's banks (:func:`_deal_groups`).

    Each dealt group consumes its bank's own stratified pair list
    (``pairs_of(isa)``) in order — each bank sweeps the 3x3 region grid
    of *its own chip* while the total group count stays
    ``groups``-bounded.  With ``banks=1`` this yields exactly the
    single-bank pair sequence (bit-for-bit the legacy estimate); with N
    banks the modeled makespan drops ~1/N because the groups execute on
    independent banks concurrently.  Yields ``(isa, pair)`` in run order.
    """
    its = {}
    for b in _deal_groups(arr, groups, dealer, weights):
        if b not in its:
            with tracing.span("charz.chip"):
                its[b] = iter(pairs_of(arr.isa(b)))
        pair = next(its[b], None)
        if pair is not None:        # a bank may drop decoder-miss groups
            yield arr.isa(b), pair


def _fused_mc_rounds(arr: BankArray, groups: int, run_round) -> None:
    """Drive one fused MC sweep as ``ceil(groups / banks)`` rounds.

    Round r executes the round-robin layout's groups ``r*banks ..
    r*banks+banks-1`` — one per bank — as a single fused episode on
    ``arr.fused_isa()``.  A tail round (``groups % banks != 0``) runs on
    a bank-subset fused ISA that *continues* the first banks' noise
    counters and pair cursors (:meth:`FusedPudIsa.adopt_state`), so per
    bank the command/noise streams are exactly the loop path's.
    ``run_round(fisa, r)`` performs round r's draws, ops and accounting.
    """
    full, tail = divmod(groups, arr.banks)
    with tracing.span("charz.chip"):
        fisa = arr.fused_isa() if full else None
    for r in range(full):
        run_round(fisa, r)
    if tail:
        with tracing.span("charz.chip"):
            ft = arr.fused_isa(n_banks=tail)
            if fisa is not None:
                ft.adopt_state(fisa)
        run_round(ft, full)
        if fisa is not None:
            # fold the tail's cursor/counter advances back so the next
            # sweep's full rounds continue each bank's stream exactly
            # where the loop path would
            fisa.absorb_state(ft)


def _fill_stats(stats: dict | None, arr: BankArray, groups: int,
                tg: int) -> None:
    """Record modeled concurrent-bank timing into a caller-passed dict.

    Reports both timing models: the optimistic independent-bank
    ``makespan_ns`` and the rank-legal ``legal_makespan_ns`` (the
    :mod:`repro.analysis.schedule` event-driven schedule of the same
    logs), with the legality cost broken into cross-bank arbitration
    (``rank_stall_ns``) and refresh (``refresh_stall_ns``) stalls."""
    if stats is None:
        return
    from .. import analysis         # analysis sits above core
    tl = analysis.schedule_bank_array(arr)
    stats.update({
        "banks": arr.banks, "groups": groups, "trials_per_group": tg,
        "bank_time_ns": arr.bank_time_ns(),
        "makespan_ns": arr.makespan_ns(),
        "total_time_ns": arr.total_time_ns(),
        "legal_makespan_ns": tl.legal_makespan_ns,
        "rank_stall_ns": tl.rank_stall_ns,
        "refresh_stall_ns": tl.refresh_stall_ns,
        "refreshes": tl.refreshes,
    })


def _random_bits(rng: np.random.Generator, shape: tuple) -> np.ndarray:
    """Uniform random 0/1 uint8 array from bulk entropy (~20x faster than
    ``rng.integers(0, 2, ...)`` at Monte-Carlo sizes)."""
    n = int(np.prod(shape))
    raw = np.frombuffer(rng.bytes((n + 7) // 8), dtype=np.uint8)
    return np.unpackbits(raw)[:n].reshape(shape)


def _want_nary(op: str, ops: np.ndarray | list, axis: int = 0) -> np.ndarray:
    if A._base_op(op)[0] == "and":
        want = np.bitwise_and.reduce(ops, axis=axis)
    else:
        want = np.bitwise_or.reduce(ops, axis=axis)
    if A._base_op(op)[1]:
        want = 1 - want
    return want


def mc_boolean_success(op: str, n: int, *, trials: int = 200,
                       row_bits: int = 2048, seed: int = 0,
                       module: str | None = None, temp_c: float = 50.0,
                       batched: bool = True, banks: int = 1,
                       groups: int = MC_PAIR_GROUPS,
                       fused: bool | None = None,
                       dealer: str = "round_robin",
                       stats: dict | None = None) -> float:
    """Cell-averaged MC success of an n-input op on the noisy simulator.

    ``batched=True`` (default) runs ``ceil(trials/groups)`` trials per
    stratified activation pair in one vectorized episode each; the legacy
    ``batched=False`` path runs one episode per trial with a scrambled pair
    walk (same target statistic, ~10-30x slower).

    ``banks`` shards the stratified pair groups across a
    :class:`~repro.core.bankarray.BankArray` of independent per-bank
    chips (``dealer`` picks the group->bank mapping, round-robin by
    default — see :func:`_deal_groups`) — the estimate then averages
    over *chips* as well as regions, like the paper's multi-chip
    protocol.  ``banks=1`` is bit-for-bit the single-``BankSim`` path.

    ``fused`` stacks the bank axis onto the trial axis so each round of
    ``banks`` groups runs as **one** ``(banks*tg, rows, bits)`` episode
    (``repro.core.fused``) — bit-identical per bank to the loop path but
    with the per-command host overhead paid once instead of ``banks``
    times.  ``None`` (default) auto-fuses when parity-safe
    (:func:`_use_fused`); ``False`` forces the loop reference.

    ``stats``, if a dict, receives the modeled concurrent-bank timing
    (per-bank time, makespan).
    """
    banks = _check_banks(banks, batched=batched)
    with tracing.span("charz.estimate", op=op, n=n, seed=seed, banks=banks):
        if not batched:
            tracing.count("charz.trials", trials)
            with tracing.span("charz.chip"):
                sim = BankSim(module or get_module(), row_bits=row_bits,
                              seed=seed, temp_c=temp_c, error_model="analog")
                isa = PudIsa(sim)
            rng = np.random.default_rng(seed + 1)
            ok = 0
            tot = 0
            for _t in range(trials):
                ops = [rng.integers(0, 2, isa.width).astype(np.uint8)
                       for _ in range(n)]
                got = isa.nary_op(op, ops)
                ok += int(np.sum(got == _want_nary(op, ops)))
                tot += isa.width
            return ok / tot
        tg = max(1, -(-trials // groups))
        with tracing.span("charz.chip"):
            arr = BankArray(module or get_module(), banks=banks,
                            row_bits=row_bits, seed=seed, temp_c=temp_c,
                            error_model="analog", trials=tg,
                            track_unshared=False)
        rng = np.random.default_rng(seed + 1)
        ok = 0
        tot = 0
        if _use_fused(fused, arr.module, banks, dealer):
            with tracing.span("charz.chip"):
                pairs_by_bank = [_stratified_pairs(arr.isa(b), n, n, groups,
                                                   seed=seed)
                                 for b in range(min(banks, groups))]

            def run_round(fisa, r):
                nonlocal ok, tot
                k = fisa.n_banks
                # draw per group in global round-robin order, stack
                # bank-major
                with tracing.span("charz.draw"):
                    ops = np.concatenate(
                        [_random_bits(rng, (tg, n, fisa.width))
                         for _b in range(k)])
                pairs = [pairs_by_bank[b][r] for b in range(k)]
                with tracing.span("charz.op"):
                    got = fisa.nary_op(op, ops.swapaxes(0, 1), pair=pairs)
                with tracing.span("charz.count"):
                    ok += int(np.sum(got == _want_nary(op, ops, axis=1)))
                    tot += got.size
                tracing.count("charz.trials", got.shape[0])

            _fused_mc_rounds(arr, groups, run_round)
            _fill_stats(stats, arr, groups, tg)
            return ok / tot
        for isa, pair in _bank_pair_schedule(
                arr, groups, lambda isa: _stratified_pairs(isa, n, n, groups,
                                                           seed=seed),
                dealer=dealer):
            isa.sim.recycle_rows()      # bound the hot working set to one op
            # trial-major draw: operand staging reads it contiguously
            with tracing.span("charz.draw"):
                ops = _random_bits(rng, (tg, n, isa.width))
            with tracing.span("charz.op"):
                got = isa.nary_op(op, ops.swapaxes(0, 1), pair=pair)
            with tracing.span("charz.count"):
                ok += int(np.sum(got == _want_nary(op, ops, axis=1)))
                tot += got.size
            tracing.count("charz.trials", got.shape[0])
        _fill_stats(stats, arr, groups, tg)
        return ok / tot

def mc_not_success(n_dst: int = 1, *, trials: int = 200, row_bits: int = 2048,
                   seed: int = 0, module: str | None = None,
                   batched: bool = True, banks: int = 1,
                   groups: int = MC_PAIR_GROUPS,
                   fused: bool | None = None,
                   dealer: str = "round_robin",
                   stats: dict | None = None) -> float:
    """NOT-protocol MC success; knobs as :func:`mc_boolean_success`."""
    banks = _check_banks(banks, batched=batched)
    with tracing.span("charz.estimate", op="not", n=n_dst, seed=seed,
                      banks=banks):
        if not batched:
            tracing.count("charz.trials", trials)
            with tracing.span("charz.chip"):
                sim = BankSim(module or get_module(), row_bits=row_bits,
                              seed=seed, error_model="analog")
                isa = PudIsa(sim)
            rng = np.random.default_rng(seed + 1)
            ok = 0
            tot = 0
            for _t in range(trials):
                bits = rng.integers(0, 2, isa.width).astype(np.uint8)
                got = isa.op_not(bits, n_dst=n_dst)
                ok += int(np.sum(got == 1 - bits))
                tot += isa.width
            return ok / tot
        tg = max(1, -(-trials // groups))
        with tracing.span("charz.chip"):
            arr = BankArray(module or get_module(), banks=banks,
                            row_bits=row_bits, seed=seed,
                            error_model="analog", trials=tg,
                            track_unshared=False)
        rng = np.random.default_rng(seed + 1)
        ok = 0
        tot = 0
        if _use_fused(fused, arr.module, banks, dealer):
            with tracing.span("charz.chip"):
                pairs_by_bank = [
                    _stratified_pairs(arr.isa(b),
                                      arr.isa(b).not_activation(n_dst),
                                      n_dst, groups, seed=seed)
                    for b in range(min(banks, groups))]

            def run_round(fisa, r):
                nonlocal ok, tot
                k = fisa.n_banks
                with tracing.span("charz.draw"):
                    bits = np.concatenate([_random_bits(rng, (tg, fisa.width))
                                           for _b in range(k)])
                pairs = [pairs_by_bank[b][r] for b in range(k)]
                with tracing.span("charz.op"):
                    got = fisa.op_not(bits, n_dst=n_dst, pair=pairs)
                with tracing.span("charz.count"):
                    ok += int(np.sum(got == 1 - bits))
                    tot += got.size
                tracing.count("charz.trials", got.shape[0])

            _fused_mc_rounds(arr, groups, run_round)
            _fill_stats(stats, arr, groups, tg)
            return ok / tot
        for isa, pair in _bank_pair_schedule(
                arr, groups,
                lambda isa: _stratified_pairs(isa, isa.not_activation(n_dst),
                                              n_dst, groups, seed=seed),
                dealer=dealer):
            isa.sim.recycle_rows()      # bound the hot working set to one op
            with tracing.span("charz.draw"):
                bits = _random_bits(rng, (tg, isa.width))
            with tracing.span("charz.op"):
                got = isa.op_not(bits, n_dst=n_dst, pair=pair)
            with tracing.span("charz.count"):
                ok += int(np.sum(got == 1 - bits))
                tot += got.size
            tracing.count("charz.trials", got.shape[0])
        _fill_stats(stats, arr, groups, tg)
        return ok / tot

def measure_cell_map(op: str, n: int, *, trials: int = 300,
                     row_bits: int = 2048, seed: int = 0,
                     batched: bool = True) -> np.ndarray:
    """Per-cell success map (the paper's per-cell 10k-trial protocol).

    Uses a fixed activation pair (the paper measures one row combination
    per map), so the batched path is a single vectorized episode.
    """
    if batched:
        tg = min(trials, 64)        # keep the working set cache-sized
        sim = BankSim(get_module(), row_bits=row_bits, seed=seed,
                      error_model="analog", trials=tg, track_unshared=False)
        isa = PudIsa(sim)
        rng = np.random.default_rng(seed + 1)
        hits = np.zeros(isa.width, dtype=np.int64)
        done = 0
        while done < trials:
            sim.recycle_rows()
            ops = _random_bits(rng, (tg, n, isa.width))
            got = isa.nary_op(op, ops.swapaxes(0, 1), pair_index=0)
            take = min(tg, trials - done)
            hits += np.sum((got == _want_nary(op, ops, axis=1))[:take],
                           axis=0)
            done += take
        return hits / trials
    sim = BankSim(get_module(), row_bits=row_bits, seed=seed,
                  error_model="analog")
    isa = PudIsa(sim)
    rng = np.random.default_rng(seed + 1)
    hits = np.zeros(isa.width, dtype=np.int64)
    for _t in range(trials):
        ops = [rng.integers(0, 2, isa.width).astype(np.uint8)
               for _ in range(n)]
        got = isa.nary_op(op, ops, pair_index=0)
        hits += (got == _want_nary(op, ops))
    return hits / trials


# ---------------------------------------------------------------------------
# One function per paper figure
# ---------------------------------------------------------------------------
def measure_cell_map_not(*, trials: int = 200, row_bits: int = 2048,
                         seed: int = 0, batched: bool = True) -> np.ndarray:
    """Per-cell NOT success map (Obs. 3: some cells are 100%-reliable)."""
    if batched:
        tg = min(trials, 64)
        sim = BankSim(get_module(), row_bits=row_bits, seed=seed,
                      error_model="analog", trials=tg, track_unshared=False)
        isa = PudIsa(sim)
        rng = np.random.default_rng(seed + 1)
        hits = np.zeros(isa.width, dtype=np.int64)
        done = 0
        while done < trials:
            sim.recycle_rows()
            bits = _random_bits(rng, (tg, isa.width))
            got = isa.op_not(bits, n_dst=1, pair_index=0)
            take = min(tg, trials - done)
            hits += np.sum((got == (1 - bits))[:take], axis=0)
            done += take
        return hits / trials
    sim = BankSim(get_module(), row_bits=row_bits, seed=seed,
                  error_model="analog")
    isa = PudIsa(sim)
    rng = np.random.default_rng(seed + 1)
    hits = np.zeros(isa.width, dtype=np.int64)
    for _t in range(trials):
        bits = rng.integers(0, 2, isa.width).astype(np.uint8)
        got = isa.op_not(bits, n_dst=1, pair_index=0)
        hits += (got == 1 - bits)
    return hits / trials


# ---------------------------------------------------------------------------
# Program-level Monte-Carlo (composed operations through the executor)
# ---------------------------------------------------------------------------
#: headline compiled programs for program-level characterization
PROGRAMS = ("xor", "maj3", "add4")

#: workload-level compiled programs (bloom dedup + bit-serial dot
#: product, see :mod:`repro.pud.workloads`): verified and timing-linted
#: by ``tools/lint_plans.py`` next to ``PROGRAMS``.  Bare names use the
#: default fan-in / bit width; a trailing integer parameterizes them
#: (``bloom_probe8`` = 8-hash probe, ``dot_bitserial8`` = K=8 dot).
WORKLOAD_PROGRAMS = ("bloom_probe", "bloom_insert", "dot_bitserial")


@lru_cache(maxsize=64)
def get_program(name: str) -> CC.Program:
    """Compile one of the named characterization/workload programs."""
    if name == "xor":
        return CC.compile_expr(CC.Xor(CC.Var("a"), CC.Var("b")))
    if name == "maj3":
        return CC.compile_expr(CC.Maj(CC.Var("a"), CC.Var("b"), CC.Var("c")))
    if name.startswith("bloom_probe"):
        return CC.compile_expr(
            CC.bloom_probe_exprs(int(name[11:] or 4)))
    if name.startswith("bloom_insert"):
        return CC.compile_expr(
            CC.bloom_insert_exprs(int(name[12:] or 4)))
    if name.startswith("dot_bitserial"):
        return CC.compile_expr(CC.dot_exprs(int(name[13:] or 4)))
    if name.startswith("add"):
        return CC.compile_expr(CC.adder_exprs(int(name[3:])))
    raise ValueError(f"unknown program {name!r} (want one of "
                     f"{PROGRAMS + WORKLOAD_PROGRAMS})")


def program_success_estimate(name: "str | CC.Program",
                             module: str | None = None, **kw) -> float:
    """Independent-op estimate: product of per-instruction closed-form
    success rates on the given module.  A lower bound in spirit — real
    programs do better because an op error only corrupts an output bit if
    it happens to propagate to it."""
    m = get_module(module) if module else get_module()
    kw = {"mfr": m.manufacturer.value, "density_gb": m.density_gb,
          "die_rev": m.die_rev, "speed_mts": m.speed_mts} | kw
    p = 1.0
    prog = get_program(name) if isinstance(name, str) else name
    for i in prog.instrs:
        if i.op == "not":
            p *= A.not_success(1, **kw)
        elif i.op in ("and", "or", "nand", "nor"):
            p *= A.boolean_success_avg(i.op, max(len(i.srcs), 2), **kw)
    return p


def mc_program_success(program: str | CC.Program, *, trials: int = 200,
                       row_bits: int = 2048, seed: int = 0,
                       module: str | None = None, temp_c: float = 50.0,
                       batched: bool = True,
                       resident: ResidentPolicy | bool | str | None = None,
                       banks: int = 1, groups: int = MC_PAIR_GROUPS,
                       fused: bool | None = None,
                       dealer: str = "round_robin",
                       stats: dict | None = None) -> float:
    """Bit-averaged MC success of a whole compiled program on the noisy
    simulator: every output bit of every trial is compared against
    ``compiler.run_ideal`` on the same random inputs.

    ``batched=True`` (default) splits the trials over ``groups``
    trial-batched ``compiler.run_sim`` episodes (``BankSim(trials=T/G)``);
    the ISA's scrambled pair walk advances across groups, so — like the
    raw-op MC — the estimate region-mixes its activation pairs instead of
    pinning each instruction to one pair for every trial.
    ``batched=False`` is the per-trial reference: one full program
    execution per trial on a scalar sim (same statistic; the walk then
    advances every instruction of every trial).

    ``resident`` (a :class:`~repro.core.policy.ResidentPolicy`; legacy
    bool/str spellings coerce with a one-shot DeprecationWarning) routes
    execution through the resident-register executor (RowClone-chained
    intermediates) instead of the host-staged path — the same statistic
    over a different command stream (requires ``batched=True``; rows are
    recycled between groups, not mid-program).  ``SCHEDULED`` runs the
    compile-time polarity/residency scheduler (the engine-default
    policy): the (order, form, duplication) search runs once — memoized
    per (program, isa geometry) by ``compiler.schedule_resident`` — and
    later groups replan with the frozen decisions while the
    activation-pair walk keeps sweeping; ``GREEDY`` keeps the PR-3
    reference stream.

    ``banks`` shards the trial groups across a
    :class:`~repro.core.bankarray.BankArray` — group g executes on the
    bank :func:`_deal_groups` assigns it (round-robin ``g % banks`` by
    default; ``dealer="occupancy"`` deals to the least-loaded bank), with
    its own chip identity and noise streams; under the scheduled policy
    the search runs once on bank 0 and sibling banks replay the frozen
    decisions (``compiler.shared_schedule_decisions``).  ``banks=1`` is
    bit-for-bit the single-``BankSim`` estimate.  ``fused`` (tri-state,
    as in :func:`mc_boolean_success`) runs each round of ``banks``
    host-staged groups as one bank-stacked episode — host-path only:
    resident row plans are per-bank seed-dependent, so resident policies
    always take the loop.  ``stats``, if a dict, receives the modeled
    concurrent-bank timing.
    """
    prog = get_program(program) if isinstance(program, str) else program
    pol = coerce_resident(resident, where="charz.mc_program_success")
    names = sorted({i.name for i in prog.instrs if i.op == "input"})
    rng = np.random.default_rng(seed + 1)
    ok = 0
    tot = 0
    if pol.is_resident and not batched:
        raise ValueError("resident execution requires batched=True")
    banks = _check_banks(banks, batched=batched)
    label = program if isinstance(program, str) else "custom"
    with tracing.span("charz.estimate", op="program", program=label,
                      seed=seed, banks=banks):
        if batched:
            groups = max(1, min(groups, trials))
            tg = max(1, -(-trials // groups))
            with tracing.span("charz.chip"):
                arr = BankArray(module or get_module(), banks=banks,
                                row_bits=row_bits, seed=seed, temp_c=temp_c,
                                error_model="analog", trials=tg,
                                track_unshared=False)
            if _use_fused(fused, arr.module, banks, dealer,
                          resident=pol.is_resident):

                def run_round(fisa, r):
                    nonlocal ok, tot
                    k = fisa.n_banks
                    ins = {}
                    draws = [{m: _random_bits(rng, (tg, fisa.width))
                              for m in names} for _b in range(k)]
                    for m in names:
                        ins[m] = np.concatenate([d[m] for d in draws])
                    got = CC.run_sim(prog, ins, fisa, trials=k * tg,
                                     resident=pol)
                    want = CC.run_ideal(prog, ins, width=fisa.width)
                    ok += sum(int(np.sum(got[o] == want[o]))
                              for o in prog.outputs)
                    tot += sum(got[o].size for o in prog.outputs)
                    tracing.count("charz.trials", k * tg)

                _fused_mc_rounds(arr, groups, run_round)
                _fill_stats(stats, arr, groups, tg)
                return ok / tot
            decisions = None
            for bank_g in _deal_groups(arr, groups, dealer):
                with tracing.span("charz.chip"):
                    isa = arr.isa(bank_g)
                plan = None
                if pol.is_resident:
                    isa.sim.recycle_rows()  # resident runs re-stage all state
                    if pol is ResidentPolicy.SCHEDULED:
                        if isa.bank == 0:
                            # the search result is cached: group 1 pays it,
                            # later groups replan with frozen decisions
                            plan = CC.schedule_resident(prog, isa,
                                                        policy="scheduled")
                        else:
                            # sibling banks replay bank 0's decisions (plans
                            # are seed-dependent; decisions are not)
                            if decisions is None:
                                decisions = CC.shared_schedule_decisions(
                                    prog, arr.isa(0))
                            plan = CC.schedule_resident(prog, isa,
                                                        policy="scheduled",
                                                        _fixed=decisions)
                ins = {n: _random_bits(rng, (tg, isa.width)) for n in names}
                got = CC.run_sim(prog, ins, isa, trials=tg, resident=pol,
                                 plan=plan)
                want = CC.run_ideal(prog, ins, width=isa.width)
                ok += sum(int(np.sum(got[k] == want[k]))
                          for k in prog.outputs)
                tot += sum(got[k].size for k in prog.outputs)
                tracing.count("charz.trials", tg)
            _fill_stats(stats, arr, groups, tg)
            return ok / tot
        tracing.count("charz.trials", trials)
        with tracing.span("charz.chip"):
            sim = BankSim(module or get_module(), row_bits=row_bits,
                          seed=seed, temp_c=temp_c, error_model="analog")
            isa = PudIsa(sim)
        for _t in range(trials):
            ins = {n: _random_bits(rng, (isa.width,)) for n in names}
            got = CC.run_sim(prog, ins, isa)
            want = CC.run_ideal(prog, ins, width=isa.width)
            ok += sum(int(np.sum(got[k] == want[k]))
                      for k in prog.outputs)
            tot += sum(got[k].size for k in prog.outputs)
        return ok / tot


# ---------------------------------------------------------------------------
# Workload-level Monte-Carlo (compiled application programs)
# ---------------------------------------------------------------------------
def mc_workload_success(workload: str, *, fanin: int | None = None,
                        **kw) -> float:
    """Program-level MC success of one named workload program
    (``WORKLOAD_PROGRAMS``): the per-output-bit success of the compiled
    bloom probe/insert or bit-serial dot program on the noisy simulator.
    ``fanin`` parameterizes the program (``bloom_probe`` fan-in =
    n_hashes, ``dot_bitserial`` = K bit positions); remaining kwargs are
    :func:`mc_program_success`'s (trials, banks, resident, ...)."""
    if workload not in WORKLOAD_PROGRAMS:
        raise ValueError(f"unknown workload {workload!r} "
                         f"(want one of {WORKLOAD_PROGRAMS})")
    name = workload if fanin is None else f"{workload}{fanin}"
    return mc_program_success(get_program(name), **kw)


def workload_fanin_sweep(workloads=("bloom_probe", "bloom_insert"),
                         fanins=(2, 4, 8, 16), **kw) -> dict:
    """Success vs fan-in for the bloom probe/insert programs — paper
    SS5's many-input AND/OR measured at *workload* fan-ins, with the
    closed-form independent-op estimate next to each MC number
    (the ``reliability.plan`` composition contract).

    Returns ``{f"{workload}{fanin}": {"mc_success", "estimate"}}``.
    """
    est_kw = {k: kw[k] for k in ("temp_c",) if k in kw}
    module = kw.get("module")
    out: dict[str, dict] = {}
    for wl in workloads:
        for n in fanins:
            name = f"{wl}{n}"
            out[name] = {
                "mc_success": float(mc_program_success(
                    get_program(name), **kw)),
                "estimate": float(program_success_estimate(
                    name, module=module, **est_kw)),
            }
    return out


# ---------------------------------------------------------------------------
# One-call closed-form samplers (jax, paper-scale trial counts in ms)
# ---------------------------------------------------------------------------
def model_boolean_success(op: str, n: int, *, trials: int = 10_000,
                          width: int = 1024, seed: int = 0, **kw) -> float:
    """MC over the closed-form model in one jitted call (no command-level
    simulation) — use for paper-scale (10k+) trial counts."""
    from . import analog_jax as AJ
    return AJ.sample_boolean_success(op, n, trials=trials, width=width,
                                     seed=seed, **kw)


def model_not_success(n_dst: int = 1, *, trials: int = 10_000,
                      width: int = 1024, seed: int = 0, **kw) -> float:
    from . import analog_jax as AJ
    return AJ.sample_not_success(n_dst, trials=trials, width=width,
                                 seed=seed, **kw)


def fig5_activation_coverage(module: str | None = None, seed: int = 0) -> dict:
    """Coverage of each N_RF:N_RL activation type (Fig. 5)."""
    m = get_module(module) if module else get_module()
    got = DEC.coverage(m, seed=seed)
    paper = {f"{a}:{b}": c for (a, b), c in DEC.FIG5_COVERAGE}
    return {"model": got, "paper": paper}


def fig7_not_vs_dst_rows(mc: bool = False, trials: int = 100,
                         batched: bool = True) -> dict:
    out = {}
    for d in NOT_DSTS:
        pattern = "NN" if d == 1 else "N2N"
        closed = A.not_success(d, pattern=pattern)
        row = {"closed_form": closed}
        if mc:
            row["monte_carlo"] = mc_not_success(d, trials=trials,
                                                batched=batched)
        out[d] = row
    out["paper"] = {1: 0.9837, 32: 0.0795}
    return out


def fig8_not_activation_patterns() -> dict:
    """NOT success per N_RF:N_RL type (Obs. 5)."""
    out = {}
    for n in (1, 2, 4, 8, 16):
        out[f"{n}:{n}"] = A.not_success(n, pattern="NN")
        if n >= 1:
            out[f"{n}:{2*n}"] = A.not_success(2 * n, pattern="N2N")
    adv = float(np.mean([A.not_success(d, pattern="N2N")
                         - A.not_success(d, pattern="NN")
                         for d in (2, 4, 8, 16)]))
    out["n2n_advantage"] = adv
    out["paper_n2n_advantage"] = 0.0941
    return out


def fig9_not_distance_heatmap() -> dict:
    """NOT success by (src region, dst region) (Obs. 6)."""
    grid = {}
    for rs in (CLOSE, MIDDLE, FAR):
        for rd in (CLOSE, MIDDLE, FAR):
            vals = [A.not_success(1, pattern="NN", src_region=rs,
                                  dst_region=rd)]
            vals += [A.not_success(d, pattern="N2N", src_region=rs,
                                   dst_region=rd) for d in (2, 4, 8, 16, 32)]
            grid[f"{REGION_NAMES[rs]}-{REGION_NAMES[rd]}"] = float(np.mean(vals))
    grid["paper_middle-far"] = 0.8502
    grid["paper_far-close"] = 0.4416
    return grid


def fig10_not_temperature() -> dict:
    out = {}
    for d in NOT_DSTS:
        pattern = "NN" if d == 1 else "N2N"
        out[d] = {t: A.not_success(d, pattern=pattern, temp_c=t)
                  for t in TEMPS}
    return out


def fig11_not_speed() -> dict:
    out = {}
    for d in (1, 2, 4, 8):
        out[d] = {s: A.not_success(d, pattern="NN" if d == 1 else "N2N",
                                   speed_mts=s)
                  for s in (2133, 2400, 2666)}
    return out


def fig12_not_die_revision() -> dict:
    out = {}
    for name, m in MODULE_ZOO.items():
        if not m.supports_not:
            continue
        out[name] = A.not_success(
            1, pattern="NN", mfr=m.manufacturer.value,
            density_gb=m.density_gb, die_rev=m.die_rev,
            speed_mts=m.speed_mts)
    return out


def fig15_ops_vs_inputs(mc: bool = False, trials: int = 60,
                        batched: bool = True) -> dict:
    out = {}
    for op in OPS:
        row = {}
        for n in NS:
            cell = {"closed_form": A.boolean_success_avg(op, n)}
            if mc:
                cell["monte_carlo"] = mc_boolean_success(op, n, trials=trials,
                                                         batched=batched)
            row[n] = cell
        out[op] = row
    out["paper_16"] = {"and": 0.9494, "nand": 0.9494, "or": 0.9585,
                       "nor": 0.9587}
    return out


def fig16_k_dependence() -> dict:
    out = {}
    for op, n in (("and", 4), ("and", 16), ("or", 4), ("or", 16)):
        ks = np.arange(n + 1)
        out[f"{op}{n}"] = A.boolean_success(op, n, ks).tolist()
    return out


def fig17_ops_distance_heatmap() -> dict:
    out = {}
    for op in OPS:
        g = np.mean([A.boolean_success_avg_grid(op, n) for n in NS], axis=0)
        grid = {f"{REGION_NAMES[rc]}-{REGION_NAMES[rr]}": float(g[rc, rr])
                for rc in (CLOSE, MIDDLE, FAR) for rr in (CLOSE, MIDDLE, FAR)}
        vals = list(grid.values())
        grid["spread"] = max(vals) - min(vals)
        out[op] = grid
    out["paper_spread"] = {"and": 0.2336, "nand": 0.2370, "or": 0.1042,
                           "nor": 0.1050}
    return out


def fig18_data_pattern() -> dict:
    out = {}
    for op in OPS:
        out[op] = {
            n: {"all01": A.boolean_success_avg(op, n, random_pattern=False),
                "random": A.boolean_success_avg(op, n, random_pattern=True)}
            for n in NS}
        out[op]["avg_delta"] = float(np.mean(
            [out[op][n]["all01"] - out[op][n]["random"] for n in NS]))
    out["paper_avg_delta"] = {"and": 0.0143, "nand": 0.0139, "or": 0.0198,
                              "nor": 0.0197}
    return out


def fig19_ops_temperature() -> dict:
    out = {}
    for op in OPS:
        out[op] = {n: {t: A.boolean_success_avg(op, n, temp_c=t)
                       for t in TEMPS} for n in NS}
        out[op]["max_delta"] = max(
            abs(out[op][n][95] - out[op][n][50]) for n in NS)
    out["paper_max_delta"] = {"and": 0.0166, "nand": 0.0165, "or": 0.0163,
                              "nor": 0.0164}
    return out


def fig20_ops_speed() -> dict:
    out = {}
    for op in OPS:
        out[op] = {n: {s: A.boolean_success_avg(op, n, speed_mts=s)
                       for s in (2133, 2400, 2666)} for n in NS}
    out["paper_nand4_2133_2400"] = 0.2989
    return out


def fig21_ops_die_revision() -> dict:
    out = {}
    for dens, rev in ((4, "A"), (4, "M"), (8, "A"), (8, "M")):
        out[f"hynix_{dens}gb_{rev}"] = {
            op: {n: A.boolean_success_avg(op, n, density_gb=dens, die_rev=rev)
                 for n in NS} for op in OPS}
    return out


def observation3_perfect_cells(trials: int = 300) -> dict:
    """Obs. 3: existence of 100%-success cells (MC, per-cell map)."""
    m = measure_cell_map("and", 4, trials=trials)
    return {
        "n_cells": int(m.size),
        "perfect_cells": int(np.sum(m >= 1.0)),
        "zero_cells": int(np.sum(m <= 0.0)),
        "mean": float(m.mean()),
    }


def takeaway_tables() -> dict:
    """The four headline numbers of the abstract."""
    return {
        "not_1dst": {"model": A.not_success(1), "paper": 0.9837},
        "nand16": {"model": A.boolean_success_avg("nand", 16), "paper": 0.9494},
        "nor16": {"model": A.boolean_success_avg("nor", 16), "paper": 0.9587},
        "and16": {"model": A.boolean_success_avg("and", 16), "paper": 0.9494},
        "or16": {"model": A.boolean_success_avg("or", 16), "paper": 0.9585},
    }
