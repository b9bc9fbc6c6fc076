"""Round-major-native hot loop: layout contract, oracles, zero permutations.

The tentpole claims, each pinned by a test here:
  1. the fused fwd/bwd solve matches the sequential scipy oracle for every
     ordering x dtype x single/batched combination;
  2. the round-major-native PCG loop reproduces the index-space path's
     iteration counts one for one (round-major is an equivalent reordering);
  3. the per-iteration apply performs ZERO full-vector permutations — no
     scatter primitive appears in the jaxpr of the native preconditioner or
     SpMV, while the index-space path's jaxpr does scatter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.analysis import ROUND_MAJOR_APPLY, lint, primitives
from repro.core import (build_plan, build_preconditioner_from_rounds,
                        build_round_major_preconditioner_from_rounds,
                        fuse_round_major, ic0, pack_ell, pack_factor,
                        permute_round_major, solve_iccg,
                        solve_iccg_batched, spmv_ell)
from repro.core.ic0 import sequential_ic_solve
from repro.core.matrices import laplace_2d
from repro.core.solvers import _order_system
from repro.kernels.config import default_interpret

ORDERINGS = ("mc", "bmc", "hbmc", "natural")


def _native_system(method, nx=13, ny=11, bs=8, w=4):
    """Ordered+padded system, factor, fused preconditioner inputs."""
    a = laplace_2d(nx, ny)
    sysd = _order_system(sp.csr_matrix(a), None, method, bs, w)
    l_bar = ic0(sysd.a_bar)
    return a, sysd, l_bar


# ---------------------------------------------------------------------------
# 1. Fused solve vs the sequential scipy oracle:
#    orderings x {f32, f64} x {single, batched}.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ORDERINGS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
def test_fused_matches_sequential_oracle(method, dtype, batched):
    a, sysd, l_bar = _native_system(method)
    pre, lay = build_round_major_preconditioner_from_rounds(
        l_bar, sysd.fwd_rounds, sysd.bwd_rounds, drop_mask=sysd.drop,
        dtype=dtype, backend="xla")
    rng = np.random.default_rng(0)
    shape = (sysd.n_padded, 3) if batched else (sysd.n_padded,)
    r = rng.normal(size=shape)
    if sysd.drop is not None:
        r[sysd.drop] = 0.0
    apply_fn = pre.apply_batched if batched else pre
    q = jnp.asarray(lay.embed(r.astype(np.dtype(jnp.dtype(dtype)))))
    z = lay.extract(np.asarray(apply_fn(q))).astype(np.float64)
    live = ~sysd.drop if sysd.drop is not None else np.ones(sysd.n_padded,
                                                           bool)
    tol = 2e-4 if dtype == jnp.float32 else 1e-11
    cols = range(r.shape[1]) if batched else [None]
    for j in cols:
        rj = r[:, j] if j is not None else r
        zj = z[:, j] if j is not None else z
        z_ref = sequential_ic_solve(l_bar, rj)
        np.testing.assert_allclose(zj[live], z_ref[live], rtol=tol, atol=tol)


@pytest.mark.parametrize("method", ORDERINGS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_pallas_kernel_matches_oracle_bitwise(method, dtype):
    """The Pallas fused kernel agrees with its jnp oracle bit for bit, and
    with the sequential oracle to dtype tolerance.  The kernel takes
    uniform tables: one segment, the halves stacked."""
    from repro.core.trisolve import DeviceFusedTables
    from repro.kernels.hbmc_trisolve import hbmc_trisolve_fused
    from repro.kernels.ref import hbmc_trisolve_fused_ref
    a, sysd, l_bar = _native_system(method)
    fwd_h, bwd_h = pack_factor(l_bar, sysd.fwd_rounds, sysd.bwd_rounds,
                               sysd.drop)
    fused = fuse_round_major(fwd_h, bwd_h, max_segments=1)
    cols, vals, dinv = DeviceFusedTables.from_host(fused,
                                                   dtype=dtype).stacked()
    r = np.random.default_rng(1).normal(size=sysd.n_padded)
    if sysd.drop is not None:
        r[sysd.drop] = 0.0
    lay = fused.layout
    q = jnp.asarray(lay.embed(r), dtype=dtype).reshape(lay.segments[0])
    z_k = np.asarray(hbmc_trisolve_fused(cols, vals, dinv, q,
                                         interpret=True))
    z_r = np.asarray(hbmc_trisolve_fused_ref(cols, vals, dinv, q))
    np.testing.assert_array_equal(z_k, z_r)
    z = lay.extract(z_k).astype(np.float64)
    z_ref = sequential_ic_solve(l_bar, r)
    live = ~sysd.drop if sysd.drop is not None else np.ones(sysd.n_padded,
                                                           bool)
    tol = 2e-4 if dtype == jnp.float32 else 1e-11
    np.testing.assert_allclose(z[live], z_ref[live], rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# 2. Native loop == index-space loop, iteration for iteration.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ORDERINGS)
@pytest.mark.parametrize("backend", [
    "xla", pytest.param("pallas", marks=pytest.mark.slow)])
def test_native_iteration_counts_match_index_layout(method, backend):
    """Acceptance: the fused round-major-native solve reproduces the
    pre-refactor (two-call, per-apply-permutation) path's PCG iteration
    counts exactly."""
    a = laplace_2d(14, 12)
    b = np.random.default_rng(2).normal(size=a.shape[0])
    r_new = solve_iccg(a, b, method=method, block_size=8, w=4,
                       backend=backend, layout="round_major")
    r_old = solve_iccg(a, b, method=method, block_size=8, w=4,
                       backend=backend, layout="index")
    assert r_new.result.iterations == r_old.result.iterations
    assert r_new.result.converged
    np.testing.assert_allclose(r_new.x, r_old.x, rtol=1e-9, atol=1e-9)


def test_native_batched_matches_singles():
    a = laplace_2d(12, 12)
    bb = np.random.default_rng(3).normal(size=(a.shape[0], 4))
    rb = solve_iccg_batched(a, bb, method="hbmc", block_size=8, w=4)
    assert rb.layout == "round_major"
    assert rb.result.converged.all()
    singles = [solve_iccg(a, bb[:, j], method="hbmc", block_size=8,
                          w=4).result.iterations for j in range(4)]
    np.testing.assert_array_equal(rb.result.iterations, singles)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                        (jnp.float64, 1e-7)])
def test_dtype_end_to_end(dtype, rtol):
    """f32 stays f32 from the host conversion onward (no f64 intermediate)."""
    a = laplace_2d(12, 10)
    b = np.random.default_rng(4).normal(size=a.shape[0])
    rep = solve_iccg(a, b, method="hbmc", block_size=8, w=4, dtype=dtype,
                     rtol=rtol)
    assert rep.result.converged
    assert rep.x.dtype == np.dtype(jnp.dtype(dtype))
    err = np.linalg.norm(a @ rep.x - b) / np.linalg.norm(b)
    assert err < 10 * rtol
    bb = np.stack([b, 2.0 * b], axis=1)
    rep_b = solve_iccg_batched(a, bb, method="hbmc", block_size=8, w=4,
                               dtype=dtype, rtol=rtol)
    assert rep_b.result.converged.all()
    assert rep_b.x.dtype == np.dtype(jnp.dtype(dtype))


def test_unknown_layout_rejected():
    a = laplace_2d(8, 8)
    b = np.ones(a.shape[0])
    with pytest.raises(ValueError, match="layout"):
        solve_iccg(a, b, method="hbmc", block_size=4, w=2, layout="banana")


# ---------------------------------------------------------------------------
# 3. Zero full-vector permutations in the hot loop.
# ---------------------------------------------------------------------------

def test_native_apply_has_no_scatter():
    """Layout contract, enforced on the jaxpr: the index-space apply
    scatters (y.at[rows].set per round, plus the solution scatter-back);
    the native apply's stores are dynamic_update_slice only."""
    a, sysd, l_bar = _native_system("hbmc")
    pre_rm, lay = build_round_major_preconditioner_from_rounds(
        l_bar, sysd.fwd_rounds, sysd.bwd_rounds, drop_mask=sysd.drop)
    pre_ix = build_preconditioner_from_rounds(
        l_bar, sysd.fwd_rounds, sysd.bwd_rounds, drop_mask=sysd.drop)
    r_rm = jnp.zeros((lay.m,))
    r_ix = jnp.zeros((sysd.n_padded,))
    assert lint(pre_rm, r_rm, budget=ROUND_MAJOR_APPLY) == []
    prims_ix = primitives(pre_ix, r_ix)
    assert any("scatter" in p for p in prims_ix)
    assert "dynamic_update_slice" in primitives(pre_rm, r_rm)
    # batched applies obey the same contract
    assert lint(pre_rm.apply_batched, jnp.zeros((lay.m, 3)),
                budget=ROUND_MAJOR_APPLY) == []


def test_native_spmv_has_no_scatter():
    a, sysd, l_bar = _native_system("hbmc")
    lay = fuse_round_major(*pack_factor(l_bar, sysd.fwd_rounds,
                                        sysd.bwd_rounds, sysd.drop)).layout
    a_rm = permute_round_major(sysd.a_bar, lay)
    cols_h, vals_h = pack_ell(a_rm)
    vals, cols = jnp.asarray(vals_h), jnp.asarray(cols_h)
    assert lint(lambda x: spmv_ell(vals, cols, x), jnp.zeros((lay.m,)),
                budget=ROUND_MAJOR_APPLY) == []


# ---------------------------------------------------------------------------
# Layout / packing invariants.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ORDERINGS)
def test_fused_layout_contract(method):
    a, sysd, l_bar = _native_system(method)
    fwd_h, bwd_h = pack_factor(l_bar, sysd.fwd_rounds, sysd.bwd_rounds,
                               sysd.drop)
    fused = fuse_round_major(fwd_h, bwd_h)
    lay = fused.layout
    assert sum(h.cols.shape[0] for h in fused.fwd) == lay.n_steps
    assert [h.dinv.shape for h in fused.bwd] == list(lay.segments)
    # every live unknown has exactly one round-major position, and
    # embed/extract invert each other on live unknowns
    flat = lay.rows
    live = flat != lay.n_slots - 1
    assert len(np.unique(flat[live])) == live.sum()
    v = np.random.default_rng(5).normal(size=lay.n_slots - 1)
    if sysd.drop is not None:
        v[sysd.drop] = 0.0
    np.testing.assert_array_equal(lay.extract(lay.embed(v)), v)
    # forward half gathers strictly below the destination slice, backward
    # half strictly above (triangular in execution order)
    for f, b, off, (n, r) in zip(fused.fwd, fused.bwd, lay.offsets,
                                 lay.segments):
        dest = off + np.arange(n * r).reshape(n, 1, r)     # (n, K, R)
        fwd_nz = f.vals != 0.0
        bwd_nz = b.vals != 0.0
        assert (f.cols < dest)[fwd_nz].all()
        assert (b.cols > dest[::-1])[bwd_nz].all()


def test_fuse_rejects_mismatched_rounds():
    a, sysd, l_bar = _native_system("hbmc")
    fwd_h, bwd_h = pack_factor(l_bar, sysd.fwd_rounds, sysd.bwd_rounds,
                               sysd.drop)
    with pytest.raises(ValueError, match="reversed"):
        fuse_round_major(fwd_h, fwd_h)


def test_default_interpret_tracks_backend():
    assert default_interpret() == (jax.default_backend() != "tpu")


def test_compiled_pallas_plan_pads_rounds_and_refuses_f64():
    """A plan whose kernels will be compiled pads each round to whole
    (8, 128) tiles and refuses f64, which Mosaic cannot lower; plans with
    interpreted kernels keep the packed width."""
    a = laplace_2d(14, 12)
    knobs = dict(method="hbmc", block_size=8, w=4)
    compiled = build_plan(a, backend="pallas", interpret=False,
                          dtype=jnp.float32, **knobs)
    interpreted = build_plan(a, backend="pallas", interpret=True, **knobs)
    assert compiled._precond.tables.segments[0][1] % 1024 == 0
    assert interpreted._precond.tables.segments[0][1] < 1024
    # the fused kernel takes uniform tables: Pallas plans keep one segment
    assert compiled.n_segments == interpreted.n_segments == 1
    with pytest.raises(ValueError, match="float64"):
        build_plan(a, backend="pallas", interpret=False, **knobs)
    with pytest.raises(ValueError, match="float64"):
        build_plan(a, spmv_format="sell", spmv_backend="pallas",
                   interpret=False, **knobs)
