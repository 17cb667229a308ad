"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _planes(n, r, c):
    return jnp.asarray(RNG.integers(0, 2 ** 32, (n, r, c), dtype=np.uint32))


@pytest.mark.parametrize("op", ["and", "or", "nand", "nor", "xor"])
@pytest.mark.parametrize("shape", [(2, 8, 512), (3, 5, 130), (16, 16, 1024),
                                   (1, 1, 32)])
def test_nary_bitwise(op, shape):
    p = _planes(*shape)
    assert (ops.nary_bitwise(p, op) == ref.nary_bitwise(op, p)).all()


@pytest.mark.parametrize("shape", [(8, 512), (3, 70), (17, 1025)])
def test_bitwise_not(shape):
    p = _planes(1, *shape)[0]
    assert (ops.bitwise_not(p) == ~p).all()


def test_maj3():
    a, b, c = _planes(3, 9, 600)
    assert (ops.maj3(a, b, c) == ref.maj3(a, b, c)).all()


@pytest.mark.parametrize("k", [1, 4, 9, 16])
def test_add_planes(k):
    a = _planes(k, 8, 512)
    b = _planes(k, 8, 512)
    assert (ops.add_planes(a, b) == ref.add_planes(a, b)).all()


def test_add_planes_is_integer_addition():
    k = 8
    a = _planes(k, 2, 32)
    b = _planes(k, 2, 32)
    out = ops.add_planes(a, b)
    ab = np.asarray(ref.unpack_bits(jnp.moveaxis(a, 0, -1).reshape(2, -1)))
    # direct integer check on a few random bit positions
    au = np.asarray(jax.vmap(ref.unpack_bits)(a))   # (k, 2, 32*32)
    bu = np.asarray(jax.vmap(ref.unpack_bits)(b))
    ou = np.asarray(jax.vmap(ref.unpack_bits)(out))
    av = sum(au[i].astype(np.int64) << i for i in range(k))
    bv = sum(bu[i].astype(np.int64) << i for i in range(k))
    ov = sum(ou[i].astype(np.int64) << i for i in range(k + 1))
    assert np.array_equal(ov, av + bv)


@pytest.mark.parametrize("n", [1, 5, 16, 33])
def test_bitcount_planes(n):
    p = _planes(n, 8, 512)
    got = ops.bitcount_planes(p)
    want = ref.bitcount_planes(p)
    assert (got == want).all()
    # semantic check: counter equals per-bit popcount
    pu = np.asarray(jax.vmap(ref.unpack_bits)(p))
    gu = np.asarray(jax.vmap(ref.unpack_bits)(got))
    val = sum(gu[i].astype(np.int64) << i for i in range(got.shape[0]))
    assert np.array_equal(val, pu.sum(0))


@pytest.mark.parametrize("kind", ["and", "xnor"])
@pytest.mark.parametrize("m,n,kb", [(8, 8, 2), (100, 70, 40), (128, 128, 64),
                                    (130, 50, 65), (9, 5, 1), (9, 5, 63),
                                    (9, 5, 130)])
def test_popcount_gemm(kind, m, n, kb):
    x = jnp.asarray(RNG.integers(0, 2 ** 32, (m, kb), dtype=np.uint32))
    w = jnp.asarray(RNG.integers(0, 2 ** 32, (n, kb), dtype=np.uint32))
    got = ops.popcount_gemm(x, w, kind=kind)
    want = ref.popcount_gemm(x, w, kind=kind)
    assert (got == want).all()


def test_popcount_gemm_matches_pm1_matmul():
    """xnor-popcount == {-1,+1} integer GEMM."""
    m, n, k = 16, 12, 96
    xb = RNG.integers(0, 2, (m, k)).astype(np.uint8)
    wb = RNG.integers(0, 2, (n, k)).astype(np.uint8)
    xq = ref.pack_bits(jnp.asarray(xb))
    wq = ref.pack_bits(jnp.asarray(wb))
    got = ops.popcount_gemm(xq, wq, kind="xnor")
    pm1 = lambda b: 2.0 * b - 1.0
    want = pm1(xb) @ pm1(wb).T
    assert np.array_equal(np.asarray(got), want.astype(np.int32))


@given(seed=st.integers(0, 2 ** 16), w=st.integers(1, 400))
@settings(max_examples=30, deadline=None)
def test_pack_unpack_roundtrip(seed, w):
    rng = np.random.default_rng(seed)
    w32 = ((w + 31) // 32) * 32
    bits = jnp.asarray(rng.integers(0, 2, (3, w32), dtype=np.uint8))
    assert (ref.unpack_bits(ref.pack_bits(bits)) == bits).all()


def test_senseamp_matches_ref_and_sim_semantics():
    w = 2500
    com = jnp.asarray(RNG.random((4, w), dtype=np.float32))
    rfc = jnp.asarray(RNG.random((4, w), dtype=np.float32))
    st_ = jnp.asarray(RNG.normal(0, .02, w).astype(np.float32))
    nz = jnp.asarray(RNG.normal(0, 1, w).astype(np.float32))
    un = jnp.asarray(RNG.random((2, w), dtype=np.float32))
    got = ops.senseamp_resolve(com, rfc, st_, nz, un, u_com=.1, u_ref=.1,
                               shift=.02, pf=.05, trial_sigma=.012)
    want = ref.senseamp_resolve(
        (com - 0.5).sum(0) * .1, (rfc - 0.5).sum(0) * .1, st_, nz, un,
        shift=.02, pf=.05, trial_sigma=.012)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_senseamp_resolve_trials_matches_ref():
    """Trial axis folded into lanes == per-trial reference semantics."""
    t, n, w = 5, 3, 700
    com = jnp.asarray(RNG.random((t, n, w), dtype=np.float32))
    rfc = jnp.asarray(RNG.random((t, n, w), dtype=np.float32))
    st_ = jnp.asarray(RNG.normal(0, .02, w).astype(np.float32))
    nz = jnp.asarray(RNG.normal(0, 1, (t, w)).astype(np.float32))
    un = jnp.asarray(RNG.random((2, t, w), dtype=np.float32))
    kw = dict(u_com=.09, u_ref=.11, shift=.015, pf=.03, trial_sigma=.01)
    got = ops.senseamp_resolve_trials(com, rfc, st_, nz, un, **kw)
    want = ref.senseamp_resolve_trials(com, rfc, st_, nz, un, **kw)
    assert got.shape == (t, w)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("w", [1, 1000, 8191, 8193])
def test_senseamp_padded_widths(w):
    """Widths that are not a whole number of (8, 1024) tiles pad and fold
    back to exactly the reference decisions."""
    com = jnp.asarray(RNG.random((3, w), dtype=np.float32))
    rfc = jnp.asarray(RNG.random((2, w), dtype=np.float32))
    st_ = jnp.asarray(RNG.normal(0, .02, w).astype(np.float32))
    nz = jnp.asarray(RNG.normal(0, 1, w).astype(np.float32))
    un = jnp.asarray(RNG.random((2, w), dtype=np.float32))
    kw = dict(shift=.01, pf=.04, trial_sigma=.012)
    got = ops.senseamp_resolve(com, rfc, st_, nz, un, u_com=.1, u_ref=.08,
                               **kw)
    want = ref.senseamp_resolve(
        (com - 0.5).sum(0) * .1, (rfc - 0.5).sum(0) * .08, st_, nz, un, **kw)
    assert got.shape == (w,) and got.dtype == jnp.bool_
    assert (np.asarray(got) == np.asarray(want)).all()


def test_senseamp_resolve_trials_per_trial_static():
    """The fused path's (T, W) static plane (one chip per stacked bank)."""
    t, n, w = 3, 2, 900
    com = jnp.asarray(RNG.random((t, n, w), dtype=np.float32))
    rfc = jnp.asarray(RNG.random((t, n, w), dtype=np.float32))
    st_ = jnp.asarray(RNG.normal(0, .02, (t, w)).astype(np.float32))
    nz = jnp.asarray(RNG.normal(0, 1, (t, w)).astype(np.float32))
    un = jnp.asarray(RNG.random((2, t, w), dtype=np.float32))
    kw = dict(u_com=.09, u_ref=.11, shift=.015, pf=.03, trial_sigma=.01)
    got = ops.senseamp_resolve_trials(com, rfc, st_, nz, un, **kw)
    want = ref.senseamp_resolve_trials(com, rfc, st_, nz, un, **kw)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_senseamp_degenerate_floor():
    """pf=1 -> pure coin flip from uniforms."""
    w = 1024
    z = jnp.zeros((1, w), jnp.float32)
    un = jnp.asarray(RNG.random((2, w), dtype=np.float32))
    got = ops.senseamp_resolve(z, z, jnp.zeros(w), jnp.zeros(w), un,
                               u_com=.1, u_ref=.1, shift=0., pf=1.0,
                               trial_sigma=0.)
    assert (np.asarray(got) == np.asarray(un[1] < 0.5)).all()


def test_nary_bitwise_bits_entry_point():
    bits = jnp.asarray(RNG.integers(0, 2, (4, 77), dtype=np.uint8))
    got = ops.nary_bitwise_bits(bits, "nor")
    want = 1 - np.bitwise_or.reduce(np.asarray(bits))
    assert np.array_equal(np.asarray(got), want)
