"""Pallas TPU kernel: fused charge-share + sense-amp Monte-Carlo resolver.

The hot loop of the FCDRAM analog simulator, vectorized: given the cell
voltages of the activated compute / reference rows, produce the resolved
logic values of every shared column in one pass — charge sharing (mean over
activated cells), static per-SA offset, per-trial Gaussian noise, threshold
shift (Frac drift) and the activation-failure coin flip.

Wired into the simulator as ``BankSim(resolve_backend="pallas")``: every
Boolean-protocol APA routes its comparator resolve through this kernel
(via :func:`senseamp_resolve_trials`, which folds the Monte-Carlo trial
axis into the lane axis), Mosaic-compiled on TPU and interpret-mode on
CPU.  Matches ``repro.kernels.ref.senseamp_resolve`` and the numpy
``BankSim._resolve`` semantics.

Inputs (W = number of shared columns):
  com_cells: (N_com, W) f32 — compute-side cell voltages in [0,1]
  ref_cells: (N_ref, W) f32 — reference-side voltages (constants + Frac)
  static:    (W,) f32       — per-SA static offsets [V]
  normals:   (W,) f32       — standard normal draws (trial noise)
  uniforms:  (2, W) f32     — floor flip + coin draws
Scalars (compile-time): u_com, u_ref (charge-share swing), shift, pf,
  trial_sigma.
Output: (W,) bool resolved values.

Layout: the column axis is padded to a whole number of (TILE_R, LANES)
tiles and folded into 2-D ``(rows, LANES)`` planes, so every block is
aligned to the VPU's (8, 128) tiling.  The kernel stores int32 (a dtype
Mosaic writes); the cast to ``bool`` happens outside it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_R = 8
LANES = 1024


def _senseamp_kernel(com_ref, rf_ref, st_ref, nz_ref, un_ref, o_ref, *,
                     n_com: int, n_ref: int, u_com: float, u_ref: float,
                     shift: float, pf: float, trial_sigma: float):
    v_com = jnp.zeros((TILE_R, LANES), jnp.float32)
    for i in range(n_com):
        v_com = v_com + (com_ref[i] - 0.5)
    v_com = v_com * u_com
    v_ref = jnp.zeros((TILE_R, LANES), jnp.float32)
    for i in range(n_ref):
        v_ref = v_ref + (rf_ref[i] - 0.5)
    v_ref = v_ref * u_ref
    margin = (v_com - v_ref - shift + st_ref[...]
              + trial_sigma * nz_ref[...])
    one, zero = jnp.int32(1), jnp.int32(0)
    out = jnp.where(margin > 0.0, one, zero)
    coin = jnp.where(un_ref[1] < 0.5, one, zero)
    o_ref[...] = jnp.where(un_ref[0] < pf, coin, out)


@functools.partial(jax.jit,
                   static_argnames=("u_com", "u_ref", "shift", "pf",
                                    "trial_sigma", "interpret"))
def senseamp_resolve(com_cells: jax.Array, ref_cells: jax.Array,
                     static: jax.Array, normals: jax.Array,
                     uniforms: jax.Array, *, u_com: float, u_ref: float,
                     shift: float, pf: float, trial_sigma: float,
                     interpret: bool = False) -> jax.Array:
    n_com, w = com_cells.shape
    n_ref = ref_cells.shape[0]
    pw = (-w) % (TILE_R * LANES)
    rows = (w + pw) // LANES

    def fold(x):            # (..., W) -> (..., rows, LANES), zero-padded
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pw),))
        return x.reshape(*x.shape[:-1], rows, LANES)

    out = pl.pallas_call(
        functools.partial(_senseamp_kernel, n_com=n_com, n_ref=n_ref,
                          u_com=u_com, u_ref=u_ref, shift=shift, pf=pf,
                          trial_sigma=trial_sigma),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        grid=(rows // TILE_R,),
        in_specs=[
            pl.BlockSpec((n_com, TILE_R, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((n_ref, TILE_R, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((TILE_R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((TILE_R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((2, TILE_R, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_R, LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(fold(com_cells), fold(ref_cells), fold(static), fold(normals),
      fold(uniforms))
    return out.reshape(-1)[:w].astype(bool)


@functools.partial(jax.jit,
                   static_argnames=("u_com", "u_ref", "shift", "pf",
                                    "trial_sigma", "interpret"))
def senseamp_resolve_trials(com_cells: jax.Array, ref_cells: jax.Array,
                            static: jax.Array, normals: jax.Array,
                            uniforms: jax.Array, *, u_com: float,
                            u_ref: float, shift: float, pf: float,
                            trial_sigma: float,
                            interpret: bool = False) -> jax.Array:
    """Trial-batched front end: fold the Monte-Carlo trial axis into lanes.

    com_cells: (T, N_com, W) f32 — per-trial compute-side cell voltages
    ref_cells: (T, N_ref, W) f32 — per-trial reference-side voltages
    static:    (W,) f32           — per-SA offsets, shared across trials —
               or (T, W) f32 for a per-trial static plane (the fused bank
               axis stacks banks onto T, and each bank has its own chip's
               offsets and margin shift folded into this plane)
    normals:   (T, W) f32         — per-trial standard normal draws
    uniforms:  (2, T, W) f32      — per-trial floor flip + coin draws
    -> (T, W) bool.  Every (trial, column) pair is an independent sense
    amp, so trials (and fused banks x trials) flatten losslessly into the
    kernel's lane axis (one pallas_call for the whole Monte-Carlo batch).
    """
    t, n_com, w = com_cells.shape
    com2 = jnp.moveaxis(com_cells, 1, 0).reshape(n_com, t * w)
    ref2 = jnp.moveaxis(ref_cells, 1, 0).reshape(ref_cells.shape[1], t * w)
    st2 = static.reshape(t * w) if static.ndim == 2 else jnp.tile(static, t)
    out = senseamp_resolve(
        com2, ref2, st2, normals.reshape(t * w),
        uniforms.reshape(2, t * w), u_com=u_com, u_ref=u_ref, shift=shift,
        pf=pf, trial_sigma=trial_sigma, interpret=interpret)
    return out.reshape(t, w)
