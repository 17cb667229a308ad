"""Pallas TPU kernel: 1-bit (packed) GEMM via AND/XNOR + popcount.

The end-to-end use of the paper's substrate: binary-quantized linear layers
(repro.models.quant) compute ``Y = X_b . W_b^T`` where both operands are
{0,1}- or {-1,+1}-valued and bit-packed.  In DRAM the same product is a
sequence of many-input ANDs + a bit-serial popcount tree
(repro.core.compiler.popcount_exprs); on the TPU it is this VPU kernel.

TPU adaptation note: the MXU has no 1-bit mode, so the inner product is
computed on the VPU as popcount(AND/XOR) accumulated in int32 — with a
(M_TILE, N_TILE) output tile per grid step and the K (packed-words) axis
innermost.  The K block is the whole KB when KB <= K_TILE, else K_TILE
words (a multiple of 128, as the TPU's lane tiling requires); ``w`` is
transposed to (KB, N) outside the kernel so each packed word of it is a
sublane row broadcast across the output tile.

x: (M, KB) uint32, w: (N, KB) uint32 -> (M, N) int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

M_TILE = 128
N_TILE = 128
K_TILE = 128         # packed words per step: 128*32 = 4096 logical bits


def _pc_gemm_kernel(x_ref, wt_ref, o_ref, *, kt: int, nk: int, kind: str,
                    k_logical: int):
    """Grid: (M/M_TILE, N/N_TILE, KB/kt); K innermost for accumulation."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jnp.zeros((M_TILE, N_TILE), jnp.int32)
    for b in range(kt):
        xv = x_ref[:, b:b + 1]                # (M_TILE, 1)
        wv = wt_ref[b:b + 1, :]               # (1, N_TILE)
        m = xv & wv if kind == "and" else xv ^ wv
        acc = acc + jax.lax.population_count(m).astype(jnp.int32)
    o_ref[...] = o_ref[...] + acc

    if kind == "xnor":
        @pl.when(kk == nk - 1)
        def _finish():
            o_ref[...] = k_logical - 2 * o_ref[...]


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def popcount_gemm(x: jax.Array, w: jax.Array, *, kind: str = "and",
                  interpret: bool = False) -> jax.Array:
    """x: (M, KB) uint32, w: (N, KB) uint32 -> (M, N) int32."""
    m, kb = x.shape
    n, kb2 = w.shape
    assert kb == kb2
    kt = min(kb, K_TILE)
    pm, pn, pk = (-m) % M_TILE, (-n) % N_TILE, (-kb) % kt
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    wt = jnp.pad(w, ((0, pn), (0, pk))).T
    kbp = kb + pk
    nk = kbp // kt
    out = pl.pallas_call(
        functools.partial(_pc_gemm_kernel, kt=kt, nk=nk, kind=kind,
                          k_logical=kbp * 32),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), jnp.int32),
        grid=((m + pm) // M_TILE, (n + pn) // N_TILE, nk),
        in_specs=[
            pl.BlockSpec((M_TILE, kt), lambda i, j, k: (i, k)),
            pl.BlockSpec((kt, N_TILE), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((M_TILE, N_TILE), lambda i, j, k: (i, j)),
        interpret=interpret,
    )(xp, wt)
    if kind == "xnor":
        # padding contributed (pk*32) zero-bits: xnor counts them as
        # matches; correct by the K delta
        out = out - 32 * pk
    return out[:m, :n]
