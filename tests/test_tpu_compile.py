"""Ahead-of-time compiles for a TPU v5e at the Thermal2 analogue's shapes.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and refuses what the chip would refuse (unaligned blocks, gathers
Mosaic cannot lower, too much VMEM or HBM).  The shapes are those of
``build_plan(thermal2_analogue(1108), dtype=float32)`` — n = 1,227,664,
128 rounds of 18806 lanes, K = 4 — with the lanes padded to whole (8, 128)
tiles for the Pallas kernels, as a compiled Pallas plan pads them.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

S_ROUNDS, K_TRI = 128, 4         # rounds per sweep, factor slots per row
R_XLA = 18806                    # lanes per round as packed
R_PALLAS = 19456                 # ... padded to a multiple of 8 * 128
N_SLICES, K_SELL, W = 311296, 5, 8   # SELL-8 operand of the padded plan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without the chip: keep the cache off
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tables(sharding, r):
    s2 = 2 * S_ROUNDS
    return (_spec(sharding, (s2, r, K_TRI), jnp.int32),
            _spec(sharding, (s2, r, K_TRI), jnp.float32),
            _spec(sharding, (s2, r), jnp.float32),
            _spec(sharding, (S_ROUNDS, r), jnp.float32))


def test_fused_trisolve_kernel_compiles(one_chip):
    from repro.kernels.hbmc_trisolve import hbmc_trisolve_fused
    fn = jax.jit(lambda c, v, d, q: hbmc_trisolve_fused(c, v, d, q,
                                                        interpret=False))
    compiled = fn.lower(*_tables(one_chip, R_PALLAS)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_trisolve_kernel_refuses_unaligned_rounds(one_chip):
    from repro.kernels.hbmc_trisolve import hbmc_trisolve_fused
    fn = jax.jit(lambda c, v, d, q: hbmc_trisolve_fused(c, v, d, q,
                                                        interpret=False))
    with pytest.raises(ValueError, match="lane_multiple=1024"):
        fn.lower(*_tables(one_chip, R_XLA))


def test_sell_spmv_kernel_compiles(one_chip):
    from repro.kernels.sell_spmv import sell_spmv
    fn = jax.jit(lambda v, c, x: sell_spmv(v, c, x, interpret=False))
    compiled = fn.lower(
        _spec(one_chip, (N_SLICES, K_SELL, W), jnp.float32),
        _spec(one_chip, (N_SLICES, K_SELL, W), jnp.int32),
        _spec(one_chip, (S_ROUNDS * R_PALLAS,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_fused_sweep_compiles(one_chip):
    from repro.core.trisolve import DeviceFusedTables, fused_solve
    cols, vals, dinv, q = _tables(one_chip, R_XLA)
    compiled = fused_solve.lower(DeviceFusedTables(cols, vals, dinv),
                                 q).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_xla_ell_spmv_is_lane_dense(one_chip):
    """The (K, n) ELL operand gathers without padding K to 128 lanes: the
    program's scratch stays far below the (n, 128) f32 a padded gather
    would need."""
    from repro.core.iccg import spmv_ell
    n, k = S_ROUNDS * R_XLA, 5
    compiled = jax.jit(spmv_ell).lower(
        _spec(one_chip, (k, n), jnp.float32),
        _spec(one_chip, (k, n), jnp.int32),
        _spec(one_chip, (n,), jnp.float32)).compile()
    padded = n * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < padded // 8
