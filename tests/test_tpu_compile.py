"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The chip is described, not attached: JAX's TPU compiler lowers each kernel
at native widths (8192-bit DDR4 rows, 4096 shared columns) for one device
of a ``v5e:2x2`` topology, so Mosaic rejects what it would reject on the
chip — misaligned blocks, unsupported casts — without one.  Nothing runs,
so these say nothing about results or times.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitserial, bitwise, popcount_gemm, senseamp

#: shared sense-amp columns of one native 8192-bit row, Monte-Carlo trials
W, T = 4096, 64
#: packed plane of 512 rows x 8192 bits
ROWS, COLS = 512, 8192 // 32
#: operand rows of a 16-input APA
N_ROWS = 16
_SA = dict(u_com=0.1, u_ref=0.1, shift=0.02, pf=0.05, trial_sigma=0.012)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _senseamp(static_shape):
    f32 = jnp.float32

    def fn(com, ref, static, normals, uniforms):
        return senseamp.senseamp_resolve_trials(com, ref, static, normals,
                                                uniforms, **_SA)

    return fn, [((T, N_ROWS, W), f32), ((T, N_ROWS, W), f32),
                (static_shape, f32), ((T, W), f32), ((2, T, W), f32)]


def _planes(n):
    return ((n, ROWS, COLS), jnp.uint32)


CASES = {
    "senseamp_static_w": lambda: _senseamp((W,)),
    "senseamp_static_tw_fused": lambda: _senseamp((T, W)),
    "nary_bitwise_16": lambda: (
        lambda p: bitwise.nary_bitwise(p, op="and"), [_planes(16)]),
    "add_planes_8": lambda: (bitserial.add_planes, [_planes(8), _planes(8)]),
    "bitcount_planes_16": lambda: (bitserial.bitcount_planes, [_planes(16)]),
    "popcount_gemm_and_kb128": lambda: (
        lambda x, w: popcount_gemm.popcount_gemm(x, w, kind="and"),
        [((256, 128), jnp.uint32), ((256, 128), jnp.uint32)]),
    "popcount_gemm_xnor_kb128": lambda: (
        lambda x, w: popcount_gemm.popcount_gemm(x, w, kind="xnor"),
        [((256, 128), jnp.uint32), ((256, 128), jnp.uint32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)
