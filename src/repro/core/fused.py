"""Fused bank axis: N independent banks executed as one batched episode.

``BankArray`` (PR 6) models N banks as concurrent chips but *executes*
them as N sequential Python ``BankSim`` episodes, so host wall-clock for
Monte-Carlo sweeps still grows O(banks).  This module stacks the bank
axis onto the existing trial axis: a :class:`FusedBankSim` over N banks
at T trials per bank runs every command once on a single
``(N*T, rows, row_bits)`` cell state, with per-bank chip identity and
per-bank noise streams carried as *batched parameters* along the leading
axis.  One fused episode replaces N loop episodes — the per-command
Python/numpy dispatch overhead (the actual wall-clock cost at MC sizes)
is paid once instead of N times.

Bit-exact parity with the loop path
-----------------------------------
The loop path (``fused=False``) stays the reference; the fused path is
required to reproduce it **bit for bit** per bank (gated in
``tests/test_fused.py`` and ``benchmarks/diff_bench.py``):

* *RNG consumption*: every command draws through a :class:`_FusedRng`
  that holds one ``np.random.Generator`` per bank — seeded
  ``SeedSequence([noise_seed_b, 0x7A1A1, trial_b])`` exactly like a
  per-bank ``BankSim._rng`` — and concatenates per-bank ``(T, ...)``
  draws along the trial axis.  Each bank's generator sees the identical
  call sequence it would see in its own loop episode, so the per-bank
  slices of every draw are bit-identical.
* *Chip identity*: static SA latents are evaluated per bank seed and
  stacked ``(N, w)``; decoder activations are evaluated per bank seed
  per command (the loop path's ``activation_pattern`` is pure and
  lru-cached, so this costs nothing extra).
* *Analog scalars*: the margin offset ``dv`` (distance-region and
  die-dependent) differs per bank, so the comparator threshold is
  applied per bank slice with the *same scalar expression* the loop
  path uses — identical float semantics, no array-promotion drift.
* *Row slots*: every fused ISA op recycles row slots on entry, which
  pins all banks to one shared first-touch slot order.  This is
  parity-neutral: the loop path's callers (``charz.mc_*`` per group,
  ``compiler._run_sim_once(recycle=True)`` per op, the engine per
  block) already recycle at least that often, recycling logs nothing
  and draws nothing, and every op fully re-stages the rows it reads
  under ``track_unshared=False``.  Divergent per-bank slot maps raise
  :class:`FusedExecutionError` instead of silently corrupting state.

What fuses, what falls back
---------------------------
Fusion requires every bank to run the *same command sequence with the
same activation geometry* (row counts per APA).  On simultaneous-
activation modules the pair inventory equals the decoder's activation
category, so same-bucket pairs on all banks always share geometry; on
sequential-activation modules (Samsung) decoder misses make per-bank
retries diverge, so callers (``charz.mc_*``, ``PudEngine``) keep those
on the loop path.  Per-bank *data* (operands, noise, static offsets,
regions, decoder row sets) is free to differ.  Resident-register
execution (RowClone-chained intermediates) stays loop-only: its row
plans are seed-dependent per bank.

The Pallas resolve backend folds banks*trials into the kernel's lane
axis unchanged (``senseamp_resolve_trials`` accepts a per-trial
``(N*T, w)`` static plane); the per-bank threshold shift folds into
that plane, which reassociates one float add — fused-vs-loop parity on
the pallas backend is therefore tolerance-class (like the documented
pallas-vs-numpy tolerance), while the numpy backend (the CPU default)
is bit-exact and diff-gated.
"""
from __future__ import annotations

import math

import numpy as np

from . import analog as A
from . import decoder as DEC
from .analog import ALL_OPS, _base_op
from .device import ActivationSupport, ENERGY_PJ, VIOLATED_TRAS_NS, \
    VIOLATED_TRP_NS
from .isa import CapabilityError, PudIsa, inventory_for
from .simulator import STATIC_SPLIT, BankSim, _norm_ppf


class FusedExecutionError(RuntimeError):
    """Per-bank execution diverged where fusion requires lockstep
    (row-slot allocation or noise-context sign) — a bug guard, not a
    capability limit: callers should gate fusion, not catch this."""


class FusedGeometryError(CapabilityError):
    """Banks disagree on activation geometry (row counts / fan-in), so
    the command sequence cannot run as one fused pass.  Callers fall
    back to the loop path."""


class PerBank:
    """Marker wrapper for per-bank values on :class:`FusedBankSim` APIs.

    Wraps an ``(N, ...)`` integer array (leading axis = banks).  BankSim
    methods receiving a plain row/int broadcast it to all banks; a
    ``PerBank`` carries bank-distinct rows (decoder row sets differ per
    bank seed).  Fused ISA row *handles* are ``PerBank`` too.
    """

    __slots__ = ("vals",)

    def __init__(self, vals):
        self.vals = np.asarray(vals, dtype=np.int64)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"PerBank({self.vals.tolist()})"


class _FusedRng:
    """One per-command generator per bank; draws concatenate bank-major.

    Each bank's generator is seeded exactly like the loop path's
    ``BankSim._rng`` (``SeedSequence([noise_seed, 0x7A1A1, trial])``)
    and sees the identical sequence of draw calls, so slice
    ``[b*T:(b+1)*T]`` of every fused draw is bit-identical to loop bank
    b's draw.
    """

    __slots__ = ("gens", "t")

    def __init__(self, gens: list, t: int):
        self.gens = gens
        self.t = t

    def _per_bank(self, shape: tuple) -> tuple:
        if shape[0] != self.t * len(self.gens):
            raise FusedExecutionError(
                f"fused draw of shape {shape} does not stack "
                f"{len(self.gens)} banks x {self.t} trials")
        return (self.t,) + tuple(shape[1:])

    def standard_normal(self, shape, dtype=np.float64) -> np.ndarray:
        bs = self._per_bank(tuple(shape))
        return np.concatenate([g.standard_normal(bs, dtype=dtype)
                               for g in self.gens])

    def random(self, shape, dtype=np.float64) -> np.ndarray:
        bs = self._per_bank(tuple(shape))
        return np.concatenate([g.random(bs, dtype=dtype)
                               for g in self.gens])


class FusedBankSim(BankSim):
    """N independent banks as one ``(N*T, rows, row_bits)`` episode.

    ``bank_seeds`` fixes each bank's chip identity (decoder map + static
    SA offsets); ``trials`` is the per-bank trial count T.  The base-
    class state machine runs unchanged at ``N*T`` trials — this class
    overrides only the points where banks differ: noise streams, static
    latents, analog scalars, decoder activations, and the row-address ->
    slot mapping (per-bank row maps that must agree on slots).

    ``track_unshared`` is forced off (the loop path's trial-batched MC
    sims run that way too); resident row chaining is unsupported.
    """

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
        self.n_banks = len(bank_seeds)
        self.trials_per_bank = int(trials)
        super().__init__(module, seed=bank_seeds[0],
                         trials=self.n_banks * self.trials_per_bank,
                         track_unshared=False, **kw)
        self.bank_seeds = bank_seeds
        if noise_seeds is None:
            noise_seeds = bank_seeds
        self.bank_noise_seeds = [int(s) for s in noise_seeds]
        if len(self.bank_noise_seeds) != self.n_banks:
            raise ValueError(
                f"need one noise seed per bank ({self.n_banks}), got "
                f"{len(self.bank_noise_seeds)}")
        #: per-bank command counters (the loop path's ``_trial`` per bank)
        self._bank_trial = [0] * self.n_banks
        self._param_cache: dict = {}
        self._not_z_cache: dict = {}

    # ---------------- per-bank noise streams ----------------
    def _rng(self) -> _FusedRng:
        gens = []
        for b in range(self.n_banks):
            self._bank_trial[b] += 1
            gens.append(np.random.default_rng(np.random.SeedSequence(
                [self.bank_noise_seeds[b], 0x7A1A1, self._bank_trial[b]])))
        return _FusedRng(gens, self.trials_per_bank)

    def reseed_noise(self, noise_seed) -> None:
        """Per-bank noise reseed: pass one seed per bank (an int is only
        accepted for a single-bank sim).  Counters restart, exactly like
        ``BankSim.reseed_noise`` does per bank."""
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
        self.noise_seed = seeds[0]
        self._bank_trial = [0] * self.n_banks

    def set_bank_trials(self, counters) -> None:
        """Pre-position the per-bank command counters (tail-round
        continuation: a k-bank subset sim continues the first k banks'
        streams after ``full`` rounds on the all-banks sim)."""
        counters = [int(c) for c in counters]
        if len(counters) != self.n_banks:
            raise ValueError(f"need {self.n_banks} counters, got "
                             f"{len(counters)}")
        self._bank_trial = counters

    # ---------------- per-bank chip identity ----------------
    def _static_latents(self, stripe: int):
        """(N, w) stacked per-bank latents (loop path: (w,) per bank)."""
        if stripe not in self._static:
            xs = []
            for s in self.bank_seeds:
                rng = np.random.default_rng(
                    np.random.SeedSequence([s, 0xC0FFEE, stripe]))
                xs.append((rng.random(self.shared_w),
                           rng.random(self.shared_w)))
            self._static[stripe] = (np.stack([x[0] for x in xs]),
                                    np.stack([x[1] for x in xs]))
        return self._static[stripe]

    # ---------------- per-bank row maps, shared slots ----------------
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

    def _map_rows(self, sub: int, rows) -> np.ndarray:
        if not 0 <= sub < self.geom.subarrays_per_bank:
            raise IndexError(f"subarray {sub} out of range")
        r = self._pb_vals(rows)
        if r.size and (r.min() < 0
                       or r.max() >= self.geom.rows_per_subarray):
            raise IndexError(f"row out of range in {r}")
        rmap = self._rowmap.get(sub)
        if rmap is None:
            rmap = self._rowmap[sub] = np.full(
                (self.n_banks, self.geom.rows_per_subarray), -1,
                dtype=np.int64)
            self._nrows[sub] = 0
        bidx = np.arange(self.n_banks)[:, None]
        idx = rmap[bidx, r]
        fresh = idx < 0
        if np.any(fresh):
            if not (fresh == fresh[0]).all():
                raise FusedExecutionError(
                    "per-bank first-touch order diverged (some banks have "
                    "already allocated a row others have not) — fused ops "
                    "must recycle rows so all banks allocate in lockstep")
            cols = np.nonzero(fresh[0])[0]
            start = self._nrows[sub]
            rmap[bidx, r[:, cols]] = np.arange(start, start + cols.size)
            self._nrows[sub] = start + cols.size
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
            idx = rmap[bidx, r]
        if idx.size and not (idx == idx[0]).all():
            raise FusedExecutionError(
                "per-bank slot maps diverged — banks disagree on which "
                "storage slot a row occupies")
        return idx[0]

    def global_addr(self, sub: int, row):
        if isinstance(row, PerBank):
            return PerBank(sub * self.geom.rows_per_subarray + row.vals)
        return super().global_addr(sub, row)

    def rowclone(self, sub: int, src, dst) -> None:
        pair = PerBank(np.stack([self._pb_vals(src)[:, 0],
                                 self._pb_vals(dst)[:, 0]], axis=1))
        isrc, idst = self._map_rows(sub, pair)
        arr = self._cells(sub)
        restored = (arr[:, isrc] > 0.5).astype(np.float32)
        copied = restored
        if self.error_model == "analog" and self.rowclone_fail_p > 0.0:
            rng = self._rng()
            flip = rng.random(restored.shape,
                              dtype=self._noise_dtype) < self.rowclone_fail_p
            copied = np.where(flip, 1.0 - restored, restored)
        arr[:, idst] = copied
        arr[:, isrc] = restored
        t = self.timings
        self.log.add("RC", t.tRAS + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                     2 * ENERGY_PJ["act"] + 2 * ENERGY_PJ["pre"],
                     bank=self.bank, sub=sub)

    # ---------------- per-bank analog parameters ----------------
    def _resolve_params(self, stripe: int, op: str, n: int, *,
                        regions, random_pattern: bool):
        """Fused analog scalars: ``dv`` becomes a per-bank tuple (the
        margin offset is region- and die-dependent, and regions differ
        per bank pair), ``static`` a per-trial ``(N*T, w)`` plane;
        ``s``/``shift``/``pf`` stay shared scalars.  Memoized — the
        inputs are pure functions of chip identity and the op context."""
        reg_c = tuple(int(x) for x in np.atleast_1d(regions[0]))
        reg_r = tuple(int(x) for x in np.atleast_1d(regions[1]))
        key = (stripe, op, n, random_pattern, reg_c, reg_r)
        cached = self._param_cache.get(key)
        if cached is None:
            p = self.params
            dv = tuple(
                A.margin_offset(op, p, compute_region=reg_c[b % len(reg_c)],
                                ref_region=reg_r[b % len(reg_r)],
                                mfr=self.module.manufacturer.value,
                                density_gb=self.module.density_gb,
                                die_rev=self.module.die_rev)
                for b in range(self.n_banks))
            s, _b, _wp, _wm = A.op_noise(
                op, n, p, temp_c=self.temp_c, random_pattern=random_pattern,
                speed_mts=self.module.speed_mts,
                mfr=self.module.manufacturer.value,
                density_gb=self.module.density_gb,
                die_rev=self.module.die_rev)
            shift = A.op_shift(op, n, p)
            static = self.static_offsets(
                stripe, op, n, random_pattern=random_pattern) \
                .astype(self._noise_dtype, copy=False)        # (N, w)
            static = np.repeat(static, self.trials_per_bank, axis=0)
            pf = A.op_pfloor(op, n, p, temp_c=self.temp_c,
                             random_pattern=random_pattern,
                             speed_mts=self.module.speed_mts)
            cached = self._param_cache[key] = (dv, s, shift, static, pf)
        return cached

    def _resolve(self, margin: np.ndarray, stripe: int, op: str, n: int, *,
                 regions, random_pattern: bool, rng) -> np.ndarray:
        p = self.params
        if self.error_model in ("ideal", "none", "mean"):
            return margin > 0.0
        dv, s, shift, static, pf = self._resolve_params(
            stripe, op, n, regions=regions, random_pattern=random_pattern)
        acc = rng.standard_normal(margin.shape, dtype=self._noise_dtype)
        acc *= math.sqrt(max(1.0 - STATIC_SPLIT ** 2, 0.0)) * s
        acc += margin
        acc += static
        # per-bank threshold, applied with the loop path's exact scalar
        # expression per slice (no float-promotion drift)
        out = np.empty(margin.shape, dtype=bool)
        t = self.trials_per_bank
        for b, dv_b in enumerate(dv):
            sl = slice(b * t, (b + 1) * t)
            out[sl] = acc[sl] > -(dv_b - shift - p.delta_v)
        u = rng.random(margin.shape, dtype=self._noise_dtype)
        return np.where(u < pf, u < 0.5 * pf, out)

    def _resolve_pallas(self, com_cells, ref_cells, u_com, u_ref,
                        stripe: int, op: str, n: int, *, regions,
                        random_pattern: bool, rng) -> np.ndarray:
        from ..kernels import ops as kops
        p = self.params
        dv, s, shift, static, pf = self._resolve_params(
            stripe, op, n, regions=regions, random_pattern=random_pattern)
        shape = com_cells.shape[:1] + com_cells.shape[2:]      # (N*T, w)
        nz = rng.standard_normal(shape, dtype=self._noise_dtype)
        u = rng.random(shape, dtype=self._noise_dtype)
        coin = np.where(u < 0.5 * pf, np.float32(0.0), np.float32(1.0))
        un = np.stack([u.astype(np.float32, copy=False), coin])
        trial_sigma = math.sqrt(max(1.0 - STATIC_SPLIT ** 2, 0.0)) * s
        # per-bank threshold shift folded into the per-trial static plane
        # (kernel margin: v_com - v_ref - shift + static + noise)
        shift_col = np.repeat(
            np.asarray([shift + p.delta_v - dv_b for dv_b in dv],
                       dtype=np.float32), self.trials_per_bank)
        static_eff = static.astype(np.float32, copy=False) \
            - shift_col[:, None]
        out = kops.senseamp_resolve_trials(
            com_cells, ref_cells, static_eff,
            nz.astype(np.float32, copy=False), un,
            u_com=float(u_com), u_ref=float(u_ref), shift=0.0,
            pf=float(pf), trial_sigma=float(trial_sigma))
        return np.asarray(out)

    # ---------------- fused APA ----------------
    def apa(self, rf_global, rl_global, *, first_act_restored: bool = False,
            random_pattern: bool = True) -> "FusedActivation":
        rps = self.geom.rows_per_subarray
        rfv = self._pb_vals(rf_global)[:, 0]
        rlv = self._pb_vals(rl_global)[:, 0]
        f_subs, f_rows = np.divmod(rfv, rps)
        l_subs, l_rows = np.divmod(rlv, rps)
        if not ((f_subs == f_subs[0]).all() and (l_subs == l_subs[0]).all()):
            raise FusedGeometryError(
                "fused APA needs one subarray pair shared by all banks")
        f_sub, l_sub = int(f_subs[0]), int(l_subs[0])
        acts = [DEC.activation_pattern(self.module, int(f_rows[b]),
                                       int(l_rows[b]),
                                       seed=self.bank_seeds[b])
                for b in range(self.n_banks)]
        a0 = acts[0]
        if any(a.n_rf != a0.n_rf or a.n_rl != a0.n_rl for a in acts[1:]):
            raise FusedGeometryError(
                "activation geometry differs across banks: "
                f"{[(a.n_rf, a.n_rl) for a in acts]}")
        t = self.timings
        t_first = t.tRAS if first_act_restored else VIOLATED_TRAS_NS
        self.log.add("APA", t_first + VIOLATED_TRP_NS + t.tRAS + t.tRP,
                     (a0.n_rf + a0.n_rl) * ENERGY_PJ["act"]
                     + 2 * ENERGY_PJ["pre"],
                     bank=self.bank, sub=f_sub)
        fact = FusedActivation(
            a0.n_rf, a0.n_rl, a0.kind,
            np.asarray([a.rows_f for a in acts], dtype=np.int64),
            np.asarray([a.rows_l for a in acts], dtype=np.int64))
        if fact.n_rf == 0:
            return fact
        if self.module.activation is ActivationSupport.SEQUENTIAL \
                and not first_act_restored:
            return fact
        stripe, f_cols, l_cols = self._col_slices(f_sub, l_sub)
        rows_f = self._map_rows(f_sub, PerBank(fact.rows_f))
        rows_l = self._map_rows(l_sub, PerBank(fact.rows_l))
        arr_f, arr_l = self._cells(f_sub), self._cells(l_sub)
        rng = self._rng()
        geom = self.geom
        reg_f = np.atleast_1d(geom.distance_regions(
            f_rows, toward_upper=f_sub > l_sub))
        reg_l = np.atleast_1d(geom.distance_regions(
            l_rows, toward_upper=l_sub > f_sub))
        t_per = self.trials_per_bank

        if first_act_restored:
            # ---- NOT protocol: per-bank success probability / latents ----
            n_src = fact.n_rf
            u = A.u_n(n_src, self.params)
            v_src = 0.5 + u * (np.sum(arr_f[:, rows_f, f_cols], axis=1)
                               - 0.5 * n_src)
            src_bit = v_src > 0.5                       # (N*T, w)
            if self.error_model == "analog":
                spread = 0.75
                xi1, _xi2 = self._static_latents(stripe)       # (N, w)
                zs = []
                for b in range(self.n_banks):
                    key = (b, stripe, fact.n_rl, fact.kind,
                           int(reg_f[b]), int(reg_l[b]))
                    z_b = self._not_z_cache.get(key)
                    if z_b is None:
                        p_ok = A.not_success(
                            fact.n_rl,
                            pattern=("N2N" if fact.kind == "N:2N" else "NN"),
                            p=self.params, temp_c=self.temp_c,
                            src_region=int(reg_f[b]),
                            dst_region=int(reg_l[b]),
                            speed_mts=self.module.speed_mts,
                            mfr=self.module.manufacturer.value,
                            density_gb=self.module.density_gb,
                            die_rev=self.module.die_rev)
                        a = _norm_ppf(np.clip(p_ok, 1e-9, 1 - 1e-9)) \
                            * math.sqrt(1.0 + spread ** 2)
                        z_b = A.phi(a + spread * _norm_ppf(xi1[b])) \
                            .astype(self._noise_dtype, copy=False)
                        self._not_z_cache[key] = z_b
                    zs.append(z_b)
                z = np.repeat(np.stack(zs), t_per, axis=0)     # (N*T, w)
                ok = rng.random(src_bit.shape, dtype=self._noise_dtype) < z
            else:
                ok = np.ones(src_bit.shape, dtype=bool)
            dst_bit = np.where(ok, ~src_bit, src_bit).astype(np.float32)
            src_f = src_bit.astype(np.float32)
            arr_l[:, rows_l, l_cols] = dst_bit[:, None, :]
            arr_f[:, rows_f, f_cols] = src_f[:, None, :]
        else:
            # ---- Boolean-op protocol ----
            n_f, n_l = fact.n_rf, fact.n_rl
            u_f = A.u_n(n_f, self.params)
            u_l = A.u_n(n_l, self.params)
            v_f = u_f * (np.sum(arr_f[:, rows_f, f_cols], axis=1)
                         - 0.5 * n_f)
            # the noise context (AND- vs OR-family common mode) must be
            # uniform: banks run the same op with same-sign references
            ctx = np.asarray([float(np.mean(v_f[b * t_per:(b + 1) * t_per]))
                              >= 0.0 for b in range(self.n_banks)])
            if not (ctx == ctx[0]).all():
                raise FusedExecutionError(
                    "reference common-mode sign differs across banks")
            op_ctx = "and" if bool(ctx[0]) else "or"
            if self.error_model == "analog" \
                    and self._resolve_backend() == "pallas":
                out = self._resolve_pallas(
                    arr_l[:, rows_l, l_cols], arr_f[:, rows_f, f_cols],
                    u_l, u_f, stripe, op_ctx, n_l, regions=(reg_l, reg_f),
                    random_pattern=random_pattern, rng=rng)
            else:
                v_l = u_l * (np.sum(arr_l[:, rows_l, l_cols], axis=1)
                             - 0.5 * n_l)
                margin = v_l - v_f                      # (N*T, w)
                out = self._resolve(margin, stripe, op_ctx, n_l,
                                    regions=(reg_l, reg_f),
                                    random_pattern=random_pattern, rng=rng)
            outf = out.astype(np.float32)
            arr_l[:, rows_l, l_cols] = outf[:, None, :]
            arr_f[:, rows_f, f_cols] = (1.0 - outf)[:, None, :]
        # track_unshared is forced False: no non-shared-column restore,
        # and (like the loop path) its noise draws are skipped too
        return fact


class FusedActivation:
    """Per-bank activation sets of one fused APA (uniform geometry)."""

    __slots__ = ("n_rf", "n_rl", "kind", "rows_f", "rows_l")

    def __init__(self, n_rf: int, n_rl: int, kind: str,
                 rows_f: np.ndarray, rows_l: np.ndarray):
        self.n_rf = n_rf
        self.n_rl = n_rl
        self.kind = kind
        self.rows_f = rows_f     # (N, n_rf)
        self.rows_l = rows_l     # (N, n_rl)


class FusedPudIsa(PudIsa):
    """PudIsa over a :class:`FusedBankSim`: per-bank pair inventories and
    cursors, ``PerBank`` row handles, uniform-geometry planning.

    Pair-walk parity: bank b's cursor/scramble stream is exactly the one
    its loop-path ``PudIsa`` would run (cursor keyed per (n_rf, n_rl),
    scrambled with bank b's seed against bank b's inventory), so default
    pair selection matches the loop path per bank.  Every ``exec_*``
    recycles row slots on entry (see the module doc: parity-neutral and
    required for lockstep slot allocation).
    """

    def __init__(self, sim: FusedBankSim, *, f_sub: int = 0,
                 l_sub: int | None = None, bank: int = 0):
        if not isinstance(sim, FusedBankSim):
            raise TypeError("FusedPudIsa requires a FusedBankSim")
        super().__init__(sim, f_sub=f_sub, l_sub=l_sub, bank=bank)
        self.invs = [inventory_for(sim.module, s) for s in sim.bank_seeds]
        self._bank_cursors: list[dict] = [{} for _ in sim.bank_seeds]

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

    # ---------------- per-bank pair selection ----------------
    def _next_pair_bank(self, b: int, n_rf: int, n_rl: int):
        key = (n_rf, n_rl)
        cur = self._bank_cursors[b]
        k = cur.get(key, 0)
        cur[key] = k + 1
        inv = self.invs[b]
        n_pairs = max(len(inv.pairs(n_rf, n_rl)), 1)
        scrambled = DEC._mix64(k * 0x9E3779B97F4A7C15
                               + self.sim.bank_seeds[b])
        return inv.choose(n_rf, n_rl, scrambled % n_pairs)

    def _per_bank_pairs(self, pair) -> list:
        if isinstance(pair, PerBank):
            pair = pair.vals
        pair = list(pair)
        if len(pair) == 2 and all(
                isinstance(x, (int, np.integer)) for x in pair):
            return [(int(pair[0]), int(pair[1]))] * self.n_banks
        if len(pair) != self.n_banks:
            raise ValueError(f"need one (rf, rl) pair per bank "
                             f"({self.n_banks}), got {len(pair)}")
        return [(int(rf), int(rl)) for rf, rl in pair]

    def _acts_for(self, pairs: list) -> list:
        return [DEC.activation_pattern(self.sim.module, rf, rl,
                                       seed=self.sim.bank_seeds[b])
                for b, (rf, rl) in enumerate(pairs)]

    @staticmethod
    def _uniform_fact(acts: list) -> FusedActivation:
        a0 = acts[0]
        if any(a.n_rf != a0.n_rf or a.n_rl != a0.n_rl for a in acts[1:]):
            raise FusedGeometryError(
                "activation geometry differs across banks: "
                f"{[(a.n_rf, a.n_rl) for a in acts]}")
        return FusedActivation(
            a0.n_rf, a0.n_rl, a0.kind,
            np.asarray([a.rows_f for a in acts], dtype=np.int64),
            np.asarray([a.rows_l for a in acts], dtype=np.int64))

    # ---------------- logical ops ----------------
    def not_activation(self, n_dst: int) -> int:
        n_rfs = []
        for b in range(self.n_banks):
            for n_rf in (max(n_dst // 2, 1), n_dst):
                if len(self.invs[b].pairs(n_rf, n_dst)):
                    n_rfs.append(n_rf)
                    break
            else:
                raise CapabilityError(
                    f"no activation with {n_dst} dst rows")
        if len(set(n_rfs)) != 1:
            raise FusedGeometryError(
                f"NOT source-row count differs across banks: {n_rfs}")
        return n_rfs[0]

    def plan_not(self, n_dst: int = 1, *, pair_index: int | None = None,
                 pair=None):
        n_rf = self.not_activation(n_dst)
        if pair is not None:
            pairs = self._per_bank_pairs(pair)
        elif pair_index is not None:
            pairs = [self.invs[b].choose(n_rf, n_dst, pair_index)
                     for b in range(self.n_banks)]
        else:
            pairs = [self._next_pair_bank(b, n_rf, n_dst)
                     for b in range(self.n_banks)]
        acts = self._acts_for(pairs)
        if pair is None and pair_index is None:
            # per-bank decoder-miss retries (sequential modules), exactly
            # the loop path's per-bank 63-step sweep
            for b in range(self.n_banks):
                if acts[b].n_rf == 0:
                    for _ in range(63):
                        pairs[b] = self._next_pair_bank(b, n_rf, n_dst)
                        acts[b] = DEC.activation_pattern(
                            self.sim.module, *pairs[b],
                            seed=self.sim.bank_seeds[b])
                        if acts[b].n_rf:
                            break
        for b, a in enumerate(acts):
            if a.n_rf == 0:
                raise CapabilityError(
                    f"address pair {pairs[b]} yields no simultaneous "
                    f"activation on {self.sim.module.name} (bank {b})")
        fact = self._uniform_fact(acts)
        rf = PerBank([p[0] for p in pairs])
        rl = PerBank([p[1] for p in pairs])
        return rf, rl, fact

    def exec_not(self, rf, rl, act: FusedActivation, source):
        kind, payload = source
        if kind != "write":
            raise NotImplementedError(
                "fused execution stages operands from the host "
                "(resident row chaining is loop-path only)")
        self.sim.recycle_rows()     # lockstep slot allocation (module doc)
        self.sim.write_cols_multi(
            self.f_sub, PerBank(act.rows_f), self._f_sl,
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
        return PerBank(act.rows_l[:, 0]), PerBank(act.rows_f[:, 0])

    def plan_nary(self, op: str, n: int, *, pair_index: int | None = None,
                  pair=None):
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
        for b in range(self.n_banks):
            n_hw = n
            while n_hw <= 16 and len(self.invs[b].pairs(n_hw, n_hw)) == 0:
                n_hw += n_hw % 2 or 1
            if len(self.invs[b].pairs(n_hw, n_hw)) == 0:
                raise CapabilityError(f"no >= {n}:{n} pairs on this module")
            n_hws.append(n_hw)
        if len(set(n_hws)) != 1:
            raise FusedGeometryError(
                f"hardware fan-in differs across banks: {n_hws}")
        n_hw = n_hws[0]
        if pair is not None:
            pairs = self._per_bank_pairs(pair)
        elif pair_index is not None:
            pairs = [self.invs[b].choose(n_hw, n_hw, pair_index)
                     for b in range(self.n_banks)]
        else:
            pairs = [self._next_pair_bank(b, n_hw, n_hw)
                     for b in range(self.n_banks)]
        acts = self._acts_for(pairs)
        for b, a in enumerate(acts):
            if a.n_rf != n_hw or a.n_rl != n_hw:
                raise FusedGeometryError(
                    f"pair {pairs[b]} activates {a.n_rf}:{a.n_rl} on bank "
                    f"{b}, wanted {n_hw}:{n_hw}")
        fact = self._uniform_fact(acts)
        rf = PerBank([p[0] for p in pairs])
        rl = PerBank([p[1] for p in pairs])
        return n_hw, rf, rl, fact

    def exec_nary(self, op: str, rf, rl, act: FusedActivation, sources, *,
                  ref_row=None, random_pattern: bool = True):
        if ref_row is not None:
            raise NotImplementedError(
                "fused execution host-fills reference rows "
                "(resident constant rows are loop-path only)")
        if not (isinstance(sources, tuple) and sources[0] == "write_stack"):
            raise NotImplementedError(
                "fused execution stages operands with ('write_stack', ops)")
        self.sim.recycle_rows()     # lockstep slot allocation (module doc)
        n = act.n_rf
        base, _is_ref = _base_op(op.lower())
        const = 1.0 if base == "and" else 0.0
        self.sim.fill_rows(self.f_sub, PerBank(act.rows_f[:, :-1]), const,
                           cols=self._f_sl)
        self.stats.writes += n - 1
        self.stats.cost = self.stats.cost \
            + self.cost_model.write_row().scaled(n - 1)
        self.sim.frac_row(self.f_sub, PerBank(act.rows_f[:, -1]))
        self.stats.fracs += 1
        stack = self._stack_words(sources[1])
        n_wr = stack.shape[-2]
        self.sim.write_cols_multi(self.l_sub, PerBank(act.rows_l[:, :n_wr]),
                                  self._l_sl, stack)
        self.stats.writes += n_wr
        self.sim.op_boolean(op, self.sim.global_addr(self.f_sub, rf),
                            self.sim.global_addr(self.l_sub, rl),
                            random_pattern=random_pattern)
        self.stats.apas += 1
        self.stats.ops += 1
        self.stats.cost = self.stats.cost + self.cost_model.boolean(n) \
            + self.cost_model.write_row().scaled(n_wr)
        return PerBank(act.rows_l[:, 0]), PerBank(act.rows_f[:, 0])

    # ---------------- result splitting ----------------
    def split_banks(self, word: np.ndarray) -> list[np.ndarray]:
        """(N*T, w) fused result -> one (T, w) array per bank."""
        t = self.sim.trials_per_bank
        return [word[b * t:(b + 1) * t] for b in range(self.n_banks)]
