"""jit'd public wrappers for the Pallas kernels.

On CPU the kernels execute with ``interpret=True`` — the kernel body runs
under the Pallas interpreter with identical semantics, which is how the
tests run them; on TPU the same calls compile to Mosaic and never run
interpreted.  ``repro.pud.engine`` and ``repro.models.quant`` call through
this module only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import bitserial as _bitserial
from . import bitwise as _bitwise
from . import popcount_gemm as _pcg
from . import senseamp as _senseamp
from . import ref as ref  # re-exported for tests/oracles
from .ref import pack_bits, unpack_bits


@functools.lru_cache(maxsize=1)
def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def nary_bitwise(planes: jax.Array, op: str, *,
                 interpret: bool | None = None) -> jax.Array:
    """(N, R, C) packed uint32 -> (R, C); op in {and,or,nand,nor,xor}."""
    it = _interpret_default() if interpret is None else interpret
    return _bitwise.nary_bitwise(planes, op=op, interpret=it)


def bitwise_not(plane: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    it = _interpret_default() if interpret is None else interpret
    return _bitwise.bitwise_not(plane, interpret=it)


def maj3(a: jax.Array, b: jax.Array, c: jax.Array, *,
         interpret: bool | None = None) -> jax.Array:
    it = _interpret_default() if interpret is None else interpret
    return _bitwise.maj3(a, b, c, interpret=it)


def add_planes(a: jax.Array, b: jax.Array, *,
               interpret: bool | None = None) -> jax.Array:
    """(K, R, C) + (K, R, C) packed planes -> (K+1, R, C)."""
    it = _interpret_default() if interpret is None else interpret
    return _bitserial.add_planes(a, b, interpret=it)


def bitcount_planes(planes: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """(N, R, C) -> (ceil(log2(N+1)), R, C) per-bit popcount (bit-sliced)."""
    it = _interpret_default() if interpret is None else interpret
    return _bitserial.bitcount_planes(planes, interpret=it)


def popcount_gemm(x: jax.Array, w: jax.Array, *, kind: str = "and",
                  interpret: bool | None = None) -> jax.Array:
    """(M, KB) x (N, KB) packed uint32 -> (M, N) int32 binary GEMM."""
    it = _interpret_default() if interpret is None else interpret
    return _pcg.popcount_gemm(x, w, kind=kind, interpret=it)


def senseamp_resolve(com_cells, ref_cells, static, normals, uniforms, *,
                     u_com: float, u_ref: float, shift: float, pf: float,
                     trial_sigma: float,
                     interpret: bool | None = None) -> jax.Array:
    it = _interpret_default() if interpret is None else interpret
    return _senseamp.senseamp_resolve(
        com_cells, ref_cells, static, normals, uniforms, u_com=u_com,
        u_ref=u_ref, shift=shift, pf=pf, trial_sigma=trial_sigma,
        interpret=it)


def senseamp_resolve_trials(com_cells, ref_cells, static, normals,
                            uniforms, *, u_com: float, u_ref: float,
                            shift: float, pf: float, trial_sigma: float,
                            interpret: bool | None = None) -> jax.Array:
    """Trial-batched resolve: (T, N, W) cell slabs -> (T, W) bool.

    The entry point ``BankSim(resolve_backend="pallas")`` calls per APA.
    """
    it = _interpret_default() if interpret is None else interpret
    return _senseamp.senseamp_resolve_trials(
        com_cells, ref_cells, static, normals, uniforms, u_com=u_com,
        u_ref=u_ref, shift=shift, pf=pf, trial_sigma=trial_sigma,
        interpret=it)


# ---------------------------------------------------------------------------
# Convenience: unpacked-bit entry points (uint8 vectors)
# ---------------------------------------------------------------------------
def nary_bitwise_bits(bit_vectors: jax.Array, op: str) -> jax.Array:
    """(N, W) uint8 in {0,1} -> (W,) uint8. Pads W to a multiple of 32."""
    n, w = bit_vectors.shape
    pw = (-w) % 32
    bv = jnp.pad(bit_vectors, ((0, 0), (0, pw)))
    packed = pack_bits(bv)[:, None, :]          # (N, 1, B)
    out = nary_bitwise(packed, op)              # (1, B)
    return unpack_bits(out)[0, :w]


def popcount_gemm_bits(x_bits, w_bits, *, kind: str = "and",
                       interpret: bool | None = None) -> jax.Array:
    """Binary GEMM over unpacked {0,1} matrices: (M, K) x (N, K) -> (M, N).

    Packs both operands to uint32 (K zero-padded to a multiple of 32)
    and calls :func:`popcount_gemm`.  ``kind="and"`` is padding-safe as
    packed (AND with 0 contributes nothing); ``kind="xnor"`` gets the
    same padding correction the quantized matmul applies (each zero pad
    bit XNORs to 1 on both sides).  The golden reference the dram
    workload twin (``pud.workloads.dot_bitserial``) is validated against.
    """
    x = jnp.asarray(x_bits, jnp.uint8)
    w = jnp.asarray(w_bits, jnp.uint8)
    k = x.shape[1]
    pk = (-k) % 32
    xq = pack_bits(jnp.pad(x, ((0, 0), (0, pk))))
    wq = pack_bits(jnp.pad(w, ((0, 0), (0, pk))))
    out = popcount_gemm(xq, wq, kind=kind, interpret=interpret)
    if kind == "xnor" and pk:
        out = out - pk
    return out
