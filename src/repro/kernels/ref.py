"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; the kernels must match them bit-exactly
(integer ops) or to float tolerance (senseamp margins).  Tests sweep
shapes/dtypes and assert_allclose kernel-vs-ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# N-ary bitwise ops on packed uint32 bit-planes
# ---------------------------------------------------------------------------


def nary_bitwise(op: str, planes: jax.Array) -> jax.Array:
    """planes: (N, ...) packed uint32. -> (...) uint32.

    op in {and, or, nand, nor, xor}.  The TPU twin of the paper's
    many-input in-DRAM ops (NOT = nand with N=1 conceptually; see ``not_``).
    """
    n = planes.shape[0]
    if op in ("and", "nand"):
        acc = planes[0]
        for i in range(1, n):
            acc = acc & planes[i]
        return ~acc if op == "nand" else acc
    if op in ("or", "nor"):
        acc = planes[0]
        for i in range(1, n):
            acc = acc | planes[i]
        return ~acc if op == "nor" else acc
    if op == "xor":
        acc = planes[0]
        for i in range(1, n):
            acc = acc ^ planes[i]
        return acc
    raise ValueError(op)


def not_(plane: jax.Array) -> jax.Array:
    return ~plane


def maj3(a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    return (a & b) | (c & (a | b))


def select_mask(mask: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Bitwise mux: mask ? a : b (per bit)."""
    return (mask & a) | (~mask & b)


def bitcount_planes(planes: jax.Array) -> jax.Array:
    """Per-bit-position popcount across N planes -> bit-sliced counter.

    planes: (N, ...) uint32 -> (ceil(log2(N+1)), ...) uint32 binary counter
    planes, LSB first.  This is the bit-sliced adder network the in-DRAM
    compiler also synthesizes (repro.core.compiler.popcount_exprs).
    """
    n = planes.shape[0]
    k = max(1, (n).bit_length())
    slices = [jnp.zeros_like(planes[0]) for _ in range(k)]
    for i in range(n):
        carry = planes[i]
        for j in range(k):
            new = slices[j] ^ carry
            carry = slices[j] & carry
            slices[j] = new
    return jnp.stack(slices)


# ---------------------------------------------------------------------------
# Bit-serial ripple-carry adder over packed planes
# ---------------------------------------------------------------------------
def add_planes(a: jax.Array, b: jax.Array) -> jax.Array:
    """(K, ...) + (K, ...) packed uint32 planes, LSB first -> (K+1, ...)."""
    k = a.shape[0]
    outs = []
    carry = jnp.zeros_like(a[0])
    for i in range(k):
        s = a[i] ^ b[i] ^ carry
        carry = (a[i] & b[i]) | (carry & (a[i] ^ b[i]))
        outs.append(s)
    outs.append(carry)
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# 1-bit (packed) GEMM: AND / XNOR + popcount
# ---------------------------------------------------------------------------
def popcount_gemm(x: jax.Array, w: jax.Array, kind: str = "and") -> jax.Array:
    """x: (M, KB) uint32, w: (N, KB) uint32 -> (M, N) int32.

    kind="and":  out[m,n] = sum_b popcount(x[m,b] & w[n,b])
    kind="xnor": out[m,n] = K - 2 * sum_b popcount(x[m,b] ^ w[n,b])
    (the standard binary-network dot products; K = 32*KB logical bits).
    """
    xa = x[:, None, :]
    wa = w[None, :, :]
    if kind == "and":
        return jnp.sum(jax.lax.population_count(xa & wa), axis=-1,
                       dtype=jnp.int32)
    if kind == "xnor":
        k = 32 * x.shape[-1]
        pc = jnp.sum(jax.lax.population_count(xa ^ wa), axis=-1,
                     dtype=jnp.int32)
        return k - 2 * pc
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Sense-amp Monte-Carlo resolver (the analog twin)
# ---------------------------------------------------------------------------
def senseamp_resolve(v_com: jax.Array, v_ref: jax.Array,
                     static_off: jax.Array, noise: jax.Array,
                     u_float: jax.Array, *, shift: float, pf: float,
                     trial_sigma: float) -> jax.Array:
    """Vectorized sense-amp decision (matches BankSim._resolve semantics).

    v_com, v_ref: per-column charge-shared voltages [V]
    static_off:   per-column static SA offset [V]
    noise:        per-column standard normal draw (trial noise)
    u_float:      per-column uniform(0,1) draws, shape (2, W): floor flip + coin
    -> uint8 resolved logic value per column.
    """
    margin = v_com - v_ref - shift + static_off + trial_sigma * noise
    out = (margin > 0.0)
    flip = u_float[0] < pf
    coin = u_float[1] < 0.5
    return jnp.where(flip, coin, out).astype(jnp.uint8)


def senseamp_resolve_trials(com_cells: jax.Array, ref_cells: jax.Array,
                            static: jax.Array, normals: jax.Array,
                            uniforms: jax.Array, *, u_com: float,
                            u_ref: float, shift: float, pf: float,
                            trial_sigma: float) -> jax.Array:
    """Trial-batched oracle of the fused charge-share + resolve kernel.

    com_cells/ref_cells: (T, N, W) cell voltages; static (W,) shared across
    trials, or (T, W) per trial; normals (T, W); uniforms (2, T, W)
    -> (T, W) uint8.
    """
    v_com = jnp.sum(com_cells - 0.5, axis=1) * u_com       # (T, W)
    v_ref = jnp.sum(ref_cells - 0.5, axis=1) * u_ref
    return senseamp_resolve(v_com, v_ref, static, normals, uniforms,
                            shift=shift, pf=pf, trial_sigma=trial_sigma)


# ---------------------------------------------------------------------------
# packing helpers (shared by ops + tests)
# ---------------------------------------------------------------------------
def pack_bits(bits: jax.Array) -> jax.Array:
    """(..., W) uint8/bool -> (..., W//32) uint32, bit i -> word i//32 bit i%32."""
    *lead, w = bits.shape
    assert w % 32 == 0, "width must be a multiple of 32"
    b = bits.reshape(*lead, w // 32, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array) -> jax.Array:
    """(..., B) uint32 -> (..., B*32) uint8."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], words.shape[-1] * 32).astype(jnp.uint8)
