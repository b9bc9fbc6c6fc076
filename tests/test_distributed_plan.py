"""In-process coverage of the distribution layer (single-device host).

The full multi-device parity matrix lives in tests/test_multidevice.py
(subprocesses with forced host device counts).  Everything here runs the
SAME distributed machinery — shard_map fused sweep, sharded SpMV, mesh
plan — on a 1-device mesh, where it must be bitwise identical to the
plain single-device path, plus the satellite regressions (PCG-iteration
pairings, dtype preservation through padding/packing).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.analysis import PRECONDITIONED_ITERATION, lint
from repro.core import (build_plan, ic0, pcg, pcg_iteration, solve_iccg,
                        spmv_ell, spmv_sell)
from repro.core import sell
from repro.core.coloring import block_multicolor_ordering, pad_system
from repro.core.hbmc import hbmc_from_bmc, pad_system_hbmc
from repro.core.iccg import make_sharded_spmv
from repro.core.matrices import laplace_2d
from repro.core.plan import _order_system
from repro.core.trisolve import (DistributedRoundMajorPreconditioner,
                                 fused_solve, shard_fused_tables)


def _mesh1():
    return jax.make_mesh((1,), ("data",))


# ---------------------------------------------------------------------------
# 1. Distributed machinery on a 1-device mesh == plain single-device path.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["hbmc", "bmc"])
def test_mesh_plan_bitwise_on_one_device(method):
    a = laplace_2d(13, 17)
    n = a.shape[0]
    rng = np.random.default_rng(0)
    b = rng.normal(size=n)
    bb = rng.normal(size=(n, 3))
    ref = build_plan(a, method=method, block_size=8, w=4)
    dist = build_plan(a, method=method, block_size=8, w=4, mesh=_mesh1())
    r_ref, r = ref.solve(b), dist.solve(b)
    assert r.result.iterations == r_ref.result.iterations
    np.testing.assert_array_equal(r.x, r_ref.x)
    rb_ref, rb = ref.solve_batched(bb), dist.solve_batched(bb)
    np.testing.assert_array_equal(rb.result.iterations,
                                  rb_ref.result.iterations)
    np.testing.assert_array_equal(rb.x, rb_ref.x)


@pytest.mark.parametrize("fmt", ["ell", "sell"])
def test_sharded_spmv_matches_plain(fmt):
    a = sp.csr_matrix(laplace_2d(12, 11))
    n = a.shape[0]
    mesh = _mesh1()
    x = jnp.asarray(np.random.default_rng(1).normal(size=n))
    xb = jnp.asarray(np.random.default_rng(2).normal(size=(n, 3)))
    if fmt == "ell":
        cols, vals = sell.pack_ell(a)
        vals_d, cols_d = jnp.asarray(vals), jnp.asarray(cols)
        ref = spmv_ell(vals_d, cols_d, x)
    else:
        sm = sell.pack_sell(a, 4)
        vals_d, cols_d = jnp.asarray(sm.vals), jnp.asarray(sm.cols)
        ref = spmv_sell(vals_d, cols_d, x, n)
    f = make_sharded_spmv(fmt, n, mesh, "data", vals_d, cols_d,
                          batched=False)
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(ref))
    fb = make_sharded_spmv(fmt, n, mesh, "data", vals_d, cols_d,
                           batched=True)
    got_b = np.asarray(fb(xb))
    singles = np.stack([np.asarray(f(xb[:, j])) for j in range(3)], axis=1)
    np.testing.assert_allclose(got_b, singles, rtol=0, atol=1e-14)


def test_distributed_preconditioner_matches_fused_solve():
    a = laplace_2d(11, 9)
    sysd = _order_system(sp.csr_matrix(a), None, "hbmc", 8, 4)
    from repro.core.trisolve import \
        build_round_major_preconditioner_from_rounds
    pre, rm = build_round_major_preconditioner_from_rounds(
        ic0(sysd.a_bar), sysd.fwd_rounds, sysd.bwd_rounds,
        drop_mask=sysd.drop)
    mesh = _mesh1()
    dpre = DistributedRoundMajorPreconditioner(
        tables=shard_fused_tables(pre.tables, mesh, "data"),
        mesh=mesh, axis="data")
    r = jnp.asarray(np.random.default_rng(3).normal(size=rm.m))
    want = fused_solve(pre.tables, r)
    np.testing.assert_array_equal(np.asarray(dpre(r)), np.asarray(want))
    rb = jnp.asarray(np.random.default_rng(4).normal(size=(rm.m, 2)))
    want_b = np.stack([np.asarray(dpre(rb[:, j])) for j in range(2)],
                      axis=1)
    np.testing.assert_allclose(np.asarray(dpre.apply_batched(rb)), want_b,
                               rtol=0, atol=1e-14)


# ---------------------------------------------------------------------------
# 2. Lane padding (the mesh divisibility contract).
# ---------------------------------------------------------------------------

def test_lane_multiple_pads_and_converges_identically():
    a = laplace_2d(13, 11)
    b = np.random.default_rng(5).normal(size=a.shape[0])
    base = build_plan(a, method="hbmc", block_size=8, w=4)
    for mult in (3, 8):
        plan = build_plan(a, method="hbmc", block_size=8, w=4,
                          lane_multiple=mult)
        assert all(r % mult == 0 for _, r in plan._precond.tables.segments)
        r, rb = plan.solve(b), base.solve(b)
        # lane padding only adds inert lanes: same Krylov process up to
        # reduction-order rounding of the dots over the padded vector
        assert abs(r.result.iterations - rb.result.iterations) <= 1
        np.testing.assert_allclose(r.x, rb.x, rtol=0, atol=1e-9)


def test_mesh_plan_validation_errors():
    a = laplace_2d(8, 8)
    mesh = _mesh1()
    with pytest.raises(ValueError, match="round_major"):
        build_plan(a, mesh=mesh, layout="index")
    with pytest.raises(ValueError, match="xla"):
        build_plan(a, mesh=mesh, backend="pallas")
    with pytest.raises(ValueError, match="axis"):
        build_plan(a, mesh=mesh, mesh_axis="model")


# ---------------------------------------------------------------------------
# 3. PCG-iteration pairings (the roofline dry-run bugfix).
# ---------------------------------------------------------------------------

def _index_operators(a, method="hbmc"):
    sysd = _order_system(sp.csr_matrix(a), None, method, 8, 4)
    from repro.core.trisolve import build_preconditioner_from_rounds
    pre = build_preconditioner_from_rounds(
        ic0(sysd.a_bar), sysd.fwd_rounds, sysd.bwd_rounds,
        drop_mask=sysd.drop)
    cols, vals = sell.pack_ell(sysd.a_bar)
    vals_d, cols_d = jnp.asarray(vals), jnp.asarray(cols)
    spmv = lambda v: spmv_ell(vals_d, cols_d, v)
    return sysd, spmv, pre


def test_pcg_iteration_reproduces_pcg_iterates():
    """The carried (x, r, p, rz) step must replay ``pcg`` exactly — the
    seed-era ``(r, r)`` pairings diverge from it on the very first step."""
    a = laplace_2d(10, 9)
    sysd, spmv, pre = _index_operators(a)
    b = jnp.asarray(np.random.default_rng(6).normal(size=sysd.n_padded))
    k = 4
    ref = pcg(spmv, pre, b, rtol=0.0, maxiter=k)   # exactly k iterations
    step = pcg_iteration(spmv, pre)
    x = jnp.zeros_like(b)
    r = b
    z = pre(r)
    p = z
    rz = jnp.vdot(r, z)
    for _ in range(k):
        x, r, p, rz = step(x, r, p, rz)
    np.testing.assert_allclose(np.asarray(x), ref.x, rtol=0, atol=1e-12)

    # and the wrong pairings really are wrong (guards against the fix
    # regressing to plain-CG dots)
    def wrong_step(x, r, p):
        ap = spmv(p)
        alpha = jnp.vdot(r, r) / jnp.vdot(p, ap)
        x = x + alpha * p
        r2 = r - alpha * ap
        z = pre(r2)
        beta = jnp.vdot(r2, z) / jnp.vdot(r, r)
        return x, r2, z + beta * p
    xw, rw, pw = jnp.zeros_like(b), b, pre(b)
    for _ in range(k):
        xw, rw, pw = wrong_step(xw, rw, pw)
    assert not np.allclose(np.asarray(xw), ref.x, atol=1e-10)


def test_pcg_iteration_jaxpr_contains_both_sweeps():
    """The lowered iteration must contain the fwd AND bwd substitution
    loops — the seed-era (r, r) pairings never called the preconditioner,
    so the dry-run roofline accounted a plain-CG kernel."""
    a = laplace_2d(9, 8)
    sysd, spmv, pre = _index_operators(a)
    step = pcg_iteration(spmv, pre)
    v = jnp.zeros((sysd.n_padded,))
    assert lint(step, v, v, v, jnp.asarray(1.0),
                budget=PRECONDITIONED_ITERATION) == []


# ---------------------------------------------------------------------------
# 4. Dtype preservation through padding and host pack buffers.
# ---------------------------------------------------------------------------

def test_pad_system_preserves_matrix_dtype():
    a = sp.csr_matrix(laplace_2d(9, 9)).astype(np.float32)
    bmc = block_multicolor_ordering(a, 8)
    a_bar, _ = pad_system(a, None, bmc)
    assert a_bar.dtype == np.float32
    hb = hbmc_from_bmc(bmc, 4)
    a_hb, b_hb = pad_system_hbmc(a, np.ones(a.shape[0], np.float32), hb)
    assert a_hb.dtype == np.float32
    assert b_hb.dtype == np.float32
    # non-floating inputs still promote (1/diag must be exact)
    ai = sp.csr_matrix((np.ones(a.nnz, dtype=np.int64),
                        a.indices.copy(), a.indptr.copy()), shape=a.shape)
    a_bar_i, _ = pad_system(ai, None, bmc)
    assert a_bar_i.dtype == np.float64


def test_pack_buffers_preserve_dtype():
    a = sp.csr_matrix(laplace_2d(9, 9)).astype(np.float32)
    cols, vals = sell.pack_ell(a)
    assert vals.dtype == np.float32
    sm = sell.pack_sell(a, 4)
    assert sm.vals.dtype == np.float32
    sysd = _order_system(sp.csr_matrix(laplace_2d(9, 9)), None, "hbmc", 8, 4)
    l32 = sp.csr_matrix(ic0(sysd.a_bar)).astype(np.float32)
    diag = l32.diagonal()
    tri = sp.tril(l32, k=-1, format="csr")
    t = sell.pack_steps(tri, diag, sysd.fwd_rounds, sysd.drop)
    assert t.vals.dtype == np.float32
    assert t.dinv.dtype == np.float32
    fwd, bwd = sell.pack_factor(l32, sysd.fwd_rounds, sysd.bwd_rounds,
                                sysd.drop)
    fused = sell.fuse_round_major(fwd, bwd)
    for half in fused.fwd + fused.bwd:
        assert half.vals.dtype == np.float32
        assert half.dinv.dtype == np.float32


def test_f32_matrix_end_to_end_solve():
    """An f32 system stays f32 through padding + packing and still solves
    (previously the padding silently promoted the matrix to f64)."""
    a = sp.csr_matrix(laplace_2d(12, 10)).astype(np.float32)
    b = np.random.default_rng(7).normal(size=a.shape[0]).astype(np.float32)
    rep = solve_iccg(a, b, method="hbmc", block_size=8, w=4,
                     dtype=jnp.float32, rtol=1e-4)
    assert rep.result.converged
    assert rep.x.dtype == np.float32
    res = np.linalg.norm(a @ rep.x - b) / np.linalg.norm(b)
    assert res < 1e-3
