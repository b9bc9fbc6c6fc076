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


# (rounds, lanes, forward K, backward K) of each segment: one segment at
# the analogue's shapes, the six of the heat2d benchmark plan (525,625
# rows, HBMC block 32, w 8), whose colors differ in width and K, and the
# four of the poisson2d benchmark plan (65,536 rows), one width cut where
# K parts
SEGMENTS = {"uniform": ((S_ROUNDS, R_XLA, K_TRI, K_TRI),),
            "segmented": ((31, 7991, 1, 4), (3, 7991, 3, 3),
                          (30, 7908, 4, 3), (32, 434, 4, 3),
                          (31, 99, 3, 1), (1, 99, 4, 1)),
            "poisson2d": ((1, 1024, 1, 4), (31, 1024, 1, 3),
                          (31, 1024, 3, 1), (1, 1024, 4, 1))}


def _sweep_tables(sharding, segs):
    from repro.core.trisolve import DeviceFusedTables, DeviceSweep

    def half(n, r, k):
        return DeviceSweep(_spec(sharding, (n, k, r), jnp.int32),
                           _spec(sharding, (n, k, r), jnp.float32),
                           _spec(sharding, (n, r), jnp.float32))

    return (DeviceFusedTables(
        fwd=tuple(half(n, r, kf) for n, r, kf, _ in segs),
        bwd=tuple(half(n, r, kb) for n, r, _, kb in segs)),
        sum(n * r for n, r, _, _ in segs))


@pytest.mark.parametrize("shape", sorted(SEGMENTS))
def test_xla_fused_sweep_compiles(one_chip, shape):
    from repro.core.trisolve import fused_solve
    segs = SEGMENTS[shape]
    tables, m = _sweep_tables(one_chip, segs)
    compiled = fused_solve.lower(
        tables, _spec(one_chip, (m,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    # one loop per segment and half (with 64-bit loop counters a loop of
    # one trip stays a loop)
    assert text.count(" while(") == 2 * len(segs)


def test_pcg_loop_keeps_the_sweep_tables_lane_dense(one_chip):
    """Carried through the PCG loop, a sweep table may not be re-laid with
    its K (1 to 4) on the 128-lane axis.  At heat2d's segments, tables
    packed ``(n, R, K)`` compiled to 816 MB of temporary buffers that way;
    packed ``(n, K, R)`` the whole solve's temporaries stay below the 68
    MB its tables and ELL operand take."""
    from repro.core.iccg import _pcg_device
    from repro.core.plan import _make_spmv
    from repro.core.trisolve import RoundMajorPreconditioner
    tables, m = _sweep_tables(one_chip, SEGMENTS["segmented"])

    def solve(tables, vals, cols, b):
        spmv = _make_spmv("ell", m, vals, cols, False)
        return _pcg_device(spmv, RoundMajorPreconditioner(tables), b,
                           rtol=1e-6, maxiter=1000)

    compiled = jax.jit(solve).lower(
        tables, _spec(one_chip, (5, m), jnp.float32),
        _spec(one_chip, (5, m), jnp.int32),
        _spec(one_chip, (m,), jnp.float32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes, mem


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



def _bench_plan(relpath, fn, m):
    """The benchmark's plan of the generator ``fn`` in ``relpath`` at
    grid ``m`` (HBMC block 32, w 8)."""
    import importlib.util
    from pathlib import Path

    from repro.core import build_plan
    path = Path(__file__).resolve().parents[1] / relpath
    spec = importlib.util.spec_from_file_location(f"bench_{fn}", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    return build_plan(getattr(gen, fn)(m), method="hbmc", block_size=32,
                      w=8)


@pytest.fixture(scope="module")
def hpcg3d_plan():
    """The hpcg3d benchmark plan: HPCG's 27-point operator on a 48^3 grid
    (bench/configs/hpcg3d.py), fourteen segments of 502 lanes down to 1,
    K up to 26 on the sublane axis of every sweep table."""
    return _bench_plan("bench/configs/hpcg3d.py", "hpcg_27pt", 48)


@pytest.fixture(scope="module")
def poisson2d_plan():
    """The poisson2d benchmark plan: gallery('poisson', 256)
    (bench/matrices.py), 64 rounds of 1,024 lanes in four segments, two of
    them one round long."""
    return _bench_plan("bench/matrices.py", "poisson_2d", 256)


def _scratch_and_arguments(one_chip, plan, what, dtype):
    """Compiled memory of the plan's sweep or whole PCG in ``dtype``."""
    from repro.core.iccg import _pcg_device
    from repro.core.plan import _make_spmv
    from repro.core.trisolve import RoundMajorPreconditioner, fused_solve

    host = plan._precond.tables
    tables = jax.tree.map(lambda x: _spec(one_chip, x.shape, (
        dtype if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype)), host)
    m, k_ell = host.m, plan._spmv_cols.shape[0]
    q = _spec(one_chip, (m,), dtype)
    if what == "sweep":
        compiled = fused_solve.lower(tables, q).compile()
    else:
        def solve(tables, vals, cols, b):
            spmv = _make_spmv("ell", m, vals, cols, False)
            return _pcg_device(spmv, RoundMajorPreconditioner(tables), b,
                               rtol=1e-7, maxiter=2000)

        compiled = jax.jit(solve).lower(
            tables, _spec(one_chip, (k_ell, m), dtype),
            _spec(one_chip, (k_ell, m), jnp.int32), q).compile()
    return compiled.memory_analysis()


@pytest.mark.parametrize("what, dtype", [("pcg", jnp.float32),
                                         ("sweep", jnp.float64)])
def test_hpcg3d_shapes_keep_scratch_below_the_arguments(
        one_chip, hpcg3d_plan, what, dtype):
    """At K = 26 the sweep tables stay lane-dense inside the PCG loop: the
    whole PCG at hpcg3d's shapes keeps its scratch below its arguments.
    In float64 that holds for the sweep; the float64 PCG's scratch is
    over its arguments at every size, poisson2d's too (24 MB against 14
    MB), because the chip's float64 emulation of the ELL SpMV's
    contraction holds 8 float32 planes of the (K, m) operand (504 MB
    against 113 MB here)."""
    host = hpcg3d_plan._precond.tables
    assert max(t.cols.shape[1] for t in host.fwd + host.bwd) == 26
    assert [r for _, r in host.segments][-3:] == [23, 1, 1]
    assert hpcg3d_plan._spmv_cols.shape[0] == 27
    mem = _scratch_and_arguments(one_chip, hpcg3d_plan, what, dtype)
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes, mem


@pytest.mark.parametrize("what, dtype", [("pcg", jnp.float32),
                                         ("sweep", jnp.float64)])
def test_poisson2d_shapes_keep_scratch_below_the_arguments(
        one_chip, poisson2d_plan, what, dtype):
    """The poisson2d plan's four loops a half, two of them one round long
    and inlined, keep the sweep tables lane-dense inside the PCG loop:
    scratch stays below the arguments, as for one segment (the float64
    PCG is over them for its SpMV, as above)."""
    host = poisson2d_plan._precond.tables
    assert host.segments == ((1, 1024), (31, 1024), (31, 1024), (1, 1024))
    mem = _scratch_and_arguments(one_chip, poisson2d_plan, what, dtype)
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes, mem
