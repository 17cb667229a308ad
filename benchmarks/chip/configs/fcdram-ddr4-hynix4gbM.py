"""Plain reference for the FCDRAM characterization cells.

Independent of the program.  The sense-amp comparator of one Boolean APA
is written out from its definition (charge sharing over the activated
cells, static offset, per-trial noise, threshold shift, activation-failure
coin), with every analog scalar derived here from the calibration the
configuration states (its ``calibration`` block), the module, the
temperature, the data pattern and the row addresses of the APA.  From the
program the reference takes only the cell contents and the random draws
(standard normals, uniforms and the chip's per-sense-amp latent uniforms),
never a scale, a threshold or a table it made.

:func:`resolve` is the same comparator at the interface of the timed
path's resolve entry (``kernels.ops.senseamp_resolve_trials``), written
against an array namespace: the control puts it in the kernel's place in
bfloat16, the precision below the float32 the configuration states.
"""
from __future__ import annotations

import math

import numpy as np
from scipy.special import ndtri


def charge(cells) -> np.ndarray:
    """Summed charge of the activated cells of each bitline, in cells:
    ``sum(cell - 1/2)`` over the rows of a ``(T, rows, W)`` slab, float64."""
    return np.sum(np.asarray(cells, dtype=np.float64) - 0.5, axis=1)


class Model:
    """The configuration's analog model of one Boolean APA."""

    def __init__(self, config: dict):
        self.c = config["calibration"]
        self.temp_c = float(config["temp_c"])
        self.random = config["data_pattern"] == "random"
        self.rows_per_subarray = int(config["rows_per_subarray"])

    def u(self, n: int) -> float:
        """Bitline swing per activated cell [V] with ``n`` cells shared."""
        return 1.0 / (self.c["r_blcap"] + n)

    def noise(self, sgn: float, n: int) -> tuple[float, float, float, float]:
        """-> (sigma, spike, w_plus, w_minus) of the static offset mixture
        for the op family ``sgn`` (+1 AND, -1 OR) at ``n`` compute rows."""
        c, u = self.c, self.u(n)
        s = math.sqrt(c["sigma_sa"] ** 2 + (c["eta_cell"] * u) ** 2)
        s *= c["speed_sigma_mult"] * c["die_sigma_mult"]
        if self.random:
            s = math.sqrt(s ** 2 + c["sigma_dp"] ** 2)
        s *= 1.0 + c["temp_sig"] * max(self.temp_c - 50.0, 0.0)
        x = c["w_a"] * math.log(n) + c["w_b"] + c["w_c"] * sgn
        w = 0.5 / (1.0 + math.exp(-x))
        skew = max(min(c["w_skew"] * sgn, 0.9), -0.9)
        w_plus = min(w * (1.0 + skew), 0.95)
        w_minus = max(min(w * (1.0 - skew), 0.95), 0.0)
        if w_plus + w_minus > 0.98:
            scale = 0.98 / (w_plus + w_minus)
            w_plus, w_minus = w_plus * scale, w_minus * scale
        return s, c["b_u"] * u, w_plus, w_minus

    def floor(self, sgn: float, n: int) -> float:
        """Activation-failure probability (a failed APA gives a coin)."""
        c = self.c
        cm = sgn * self.u(n) * (n - 1) / 2.0
        pf = c["pf_a"] * (2.0 * n) ** c["pf_b"] * math.exp(c["c_pf_cm"] * cm)
        pf *= c["speed_pf_mult"]
        if self.random:
            pf *= 1.0 + c["dp_pf"] * math.exp(c["dp_cm"] * cm)
        pf *= 1.0 + c["temp_pf"] * max(self.temp_c - 50.0, 0.0)
        return min(max(pf, 0.0), 0.75)

    def region(self, row: np.ndarray, toward_upper: np.ndarray) -> np.ndarray:
        """Distance region of a row from the sense-amp stripe: thirds of
        the subarray, 0 close, 1 middle, 2 far."""
        n = self.rows_per_subarray
        pos = np.where(toward_upper, row, n - 1 - row)
        return np.minimum(pos // (n // 3), 2)

    def offset(self, sgn: float, rf: np.ndarray, rl: np.ndarray) -> np.ndarray:
        """Margin offset [V] of each bank's APA from its rows' regions and
        the die, ``rf``/``rl`` the global addresses of the first (reference)
        and last (compute) row."""
        c, rps = self.c, self.rows_per_subarray
        f_sub, f_row = np.divmod(rf, rps)
        l_sub, l_row = np.divmod(rl, rps)
        reg_ref = self.region(f_row, f_sub > l_sub)
        reg_com = self.region(l_row, l_sub > f_sub)
        scale = c["dist_scale_and"] if sgn > 0 else c["dist_scale_or"]
        return scale * (np.asarray(c["dist_com_v"])[reg_com]
                        + np.asarray(c["dist_ref_v"])[reg_ref]) \
            + c["die_margin_offset_v"]

    def decide(self, q_com, q_ref, n_com: int, n_ref: int, normals,
               uniform, xi1, xi2, rf, rl) -> np.ndarray:
        """Resolved bit of every (trial, shared column), ``(T, W)`` bool.

        q_com, q_ref ``(T, W)``: :func:`charge` of the compute and
        reference slabs; normals, uniform ``(T, W)``: the trial noise and
        the activation-failure draw; xi1, xi2 ``(W,)`` or ``(B, W)``: the
        chip's per-sense-amp latent uniforms of each of ``B`` banks stacked
        bank-major on the trial axis; rf, rl ``(B,)``: each bank's row
        addresses.  The op family is the reference level's side of VDD/2:
        above it AND (and NAND), below it OR (and NOR).
        """
        q_com, q_ref = np.asarray(q_com), np.asarray(q_ref)
        sgn = 1.0 if float(np.mean(q_ref)) >= 0.0 else -1.0
        s, spike, w_plus, w_minus = self.noise(sgn, n_com)
        split = self.c["static_split"]
        xi1, xi2 = np.atleast_2d(xi1), np.atleast_2d(xi2)
        comp = np.where(xi1 < w_minus, -1.0, np.where(xi1 > 1.0 - w_plus,
                                                      1.0, 0.0))
        static = comp * spike + split * s * ndtri(xi2)         # (B, W)
        per_bank = q_com.shape[0] // static.shape[0]
        static = np.repeat(static, per_bank, axis=0)
        dv = np.repeat(self.offset(sgn, np.asarray(rf), np.asarray(rl)),
                       q_com.shape[0] // len(np.atleast_1d(rf)))[:, None]
        shift = self.c["frac_drift"] * self.u(n_com) * sgn
        margin = self.u(n_com) * q_com - self.u(n_ref) * q_ref \
            + static + math.sqrt(1.0 - split ** 2) * s \
            * np.asarray(normals, dtype=np.float64) \
            - (shift + self.c["delta_v"] - dv)
        pf = self.floor(sgn, n_com)
        uniform = np.asarray(uniform, dtype=np.float64)
        return np.where(uniform < pf, uniform < 0.5 * pf, margin > 0)


def resolve(com_cells, ref_cells, static, normals, uniforms, *, u_com,
            u_ref, shift, pf, trial_sigma, xp=np, dtype=np.float64):
    """The comparator at the kernel's interface: ``(T, W)`` bool.

    com_cells ``(T, N_com, W)``, ref_cells ``(T, N_ref, W)``: cell voltages
    of the compute and reference rows; static ``(W,)`` or ``(T, W)``: the
    sense amps' static offsets; normals ``(T, W)``: standard normal draws;
    uniforms ``(2, T, W)``: the activation-failure draw and the coin.
    """
    def f(x):
        return xp.asarray(x).astype(dtype)

    half = f(0.5)
    v_com = f(u_com) * xp.sum(f(com_cells) - half, axis=1)
    v_ref = f(u_ref) * xp.sum(f(ref_cells) - half, axis=1)
    margin = v_com - v_ref - f(shift) + f(static) \
        + f(trial_sigma) * f(normals)
    un = f(uniforms)
    return xp.where(un[0] < f(pf), un[1] < half, margin > 0)


def ideal(op: str, com_cells) -> np.ndarray:
    """What the APA computes without noise: AND (AND-family) or OR
    (OR-family) over the compute rows' bits, ``(T, W)`` bool.  NAND and NOR
    read the complement from the reference rows, so their result agrees
    with the ideal exactly where the AND/OR result does."""
    bits = np.asarray(com_cells) > 0.5
    return bits.all(axis=1) if op in ("and", "nand") else bits.any(axis=1)


def add(ins: dict, k: int) -> dict:
    """``k``-bit addition over bit planes: ``a0..a{k-1}`` and
    ``b0..b{k-1}`` (least significant first) give ``s0..s{k-1}`` and the
    carry ``cout``."""
    a = sum(np.asarray(ins[f"a{i}"], np.int64) << i for i in range(k))
    b = sum(np.asarray(ins[f"b{i}"], np.int64) << i for i in range(k))
    total = a + b
    out = {f"s{i}": ((total >> i) & 1).astype(np.uint8) for i in range(k)}
    out["cout"] = ((total >> k) & 1).astype(np.uint8)
    return out
