"""Round-parallel setup pipeline + SolverPlan (factor once, solve many).

Pins the tentpole claims:
  1. ``ic0_rounds`` matches the sequential ``ic0`` (tight tolerance) across
     mc/bmc/hbmc/natural x two generators, with unchanged PCG iterations;
  2. vectorized ``pack_steps``/``pack_ell``/``pack_sell`` reproduce the
     per-row reference packing exactly;
  3. plan reuse is bitwise-identical to ``solve_iccg``, and a warm
     ``plan.solve`` performs ZERO host-side setup (asserted by making every
     setup entry point explode);
  4. ``refactor`` on perturbed values matches a cold solve;
and the satellite bugfixes: ``result.x`` lives in the caller's space
(padded-state leak regression), shifted-IC semantics on the Ieej generator,
and batched ``record_history`` parity.
"""
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import (build_plan, ic0, ic0_refactor, ic0_rounds,
                        ic0_structure, solve_iccg, solve_iccg_batched)
from repro.core import plan as plan_mod
from repro.core import sell
from repro.core.matrices import (PAPER_SHIFTS, graph_laplacian, laplace_2d,
                                 paper_problem)
from repro.core.solvers import _order_system

ORDERINGS = ("mc", "bmc", "hbmc", "natural")
GENERATORS = [
    ("lap2d", lambda: laplace_2d(13, 11)),
    ("graph", lambda: graph_laplacian(300, avg_degree=5, seed=2)),
]


# ---------------------------------------------------------------------------
# 1. Round-parallel IC(0) == sequential IC(0).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ORDERINGS)
@pytest.mark.parametrize("gen_name,gen", GENERATORS, ids=[g[0] for g in
                                                          GENERATORS])
def test_ic0_rounds_matches_sequential(gen_name, gen, method):
    a = gen()
    sysd = _order_system(sp.csr_matrix(a), None, method, 8, 4)
    l_seq = ic0(sysd.a_bar)
    l_rnd = ic0_rounds(sysd.a_bar, sysd.fwd_rounds)
    assert np.array_equal(l_seq.indptr, l_rnd.indptr)
    assert np.array_equal(l_seq.indices, l_rnd.indices)
    # bitwise: the pair accumulation order reproduces the sequential merge
    np.testing.assert_array_equal(l_rnd.data, l_seq.data)


@pytest.mark.parametrize("method", ORDERINGS)
def test_ic0_rounds_unchanged_pcg_iterations(method):
    """The plan path (ic0_rounds) reproduces the paper iteration counts —
    here cross-checked against a solve over the sequential factor."""
    from repro.core.iccg import pcg
    from repro.core.trisolve import \
        build_round_major_preconditioner_from_rounds
    a = laplace_2d(14, 12)
    b = np.random.default_rng(0).normal(size=a.shape[0])
    rep = solve_iccg(a, b, method=method, block_size=8, w=4)
    sysd = _order_system(sp.csr_matrix(a), b, method, 8, 4)
    pre, rm = build_round_major_preconditioner_from_rounds(
        ic0(sysd.a_bar), sysd.fwd_rounds, sysd.bwd_rounds,
        drop_mask=sysd.drop)
    a_rm = sell.permute_round_major(sysd.a_bar, rm)
    cols, vals = sell.pack_ell(a_rm)
    vals_d, cols_d = jnp.asarray(vals), jnp.asarray(cols)
    res = pcg(lambda x: jnp.einsum("kr,kr->r", vals_d, x[cols_d]), pre,
              jnp.asarray(rm.embed(sysd.b_bar)))
    assert rep.result.iterations == res.iterations
    assert rep.result.converged


def test_ic0_structure_rejects_bad_rounds():
    a = laplace_2d(8, 8)
    sysd = _order_system(sp.csr_matrix(a), None, "hbmc", 4, 2)
    with pytest.raises(ValueError, match="dependency-ordered"):
        # natural rounds reversed put every dependency in a LATER round
        n = sysd.n_padded
        ic0_structure(sysd.a_bar, [np.array([i]) for i in
                                   range(n - 1, -1, -1)])
    with pytest.raises(ValueError, match="partition"):
        ic0_structure(sysd.a_bar, sysd.fwd_rounds[:-1])


def test_ic0_refactor_rejects_pattern_change():
    a = laplace_2d(9, 7)
    sysd = _order_system(sp.csr_matrix(a), None, "mc", 4, 2)
    st = ic0_structure(sysd.a_bar, sysd.fwd_rounds)
    other = _order_system(sp.csr_matrix(laplace_2d(7, 9)), None, "mc", 4, 2)
    with pytest.raises(ValueError, match="pattern"):
        ic0_refactor(st, other.a_bar)


# ---------------------------------------------------------------------------
# 2. Vectorized packing == per-row reference packing.
# ---------------------------------------------------------------------------

def _pack_steps_reference(tri, diag, rounds, drop_mask=None):
    """The pre-vectorization per-row loop, kept as the packing oracle."""
    tri = sp.csr_matrix(tri)
    tri.sort_indices()
    n = tri.shape[0]
    n_slots = n + 1
    if drop_mask is not None:
        rounds = [r[~drop_mask[r]] for r in rounds]
        rounds = [r for r in rounds if len(r)]
    S = len(rounds)
    R = max(len(r) for r in rounds)
    K = max(int(np.diff(tri.indptr).max(initial=0)), 1)
    rows = np.full((S, R), n_slots - 1, dtype=np.int32)
    cols = np.full((S, R, K), n_slots - 1, dtype=np.int32)
    vals = np.zeros((S, R, K))
    dinv = np.zeros((S, R))
    live = np.zeros(S, dtype=np.int32)
    for s, rset in enumerate(rounds):
        live[s] = len(rset)
        rows[s, :len(rset)] = rset
        dinv[s, :len(rset)] = 1.0 / diag[rset]
        for t, r in enumerate(rset):
            lo, hi = tri.indptr[r], tri.indptr[r + 1]
            cols[s, t, :hi - lo] = tri.indices[lo:hi]
            vals[s, t, :hi - lo] = tri.data[lo:hi]
    return rows, cols, vals, dinv, live


@pytest.mark.parametrize("method", ORDERINGS)
def test_pack_steps_matches_reference(method):
    a = laplace_2d(11, 9)
    sysd = _order_system(sp.csr_matrix(a), None, method, 8, 4)
    l = ic0(sysd.a_bar)
    diag = l.diagonal()
    tri = sp.tril(l, k=-1, format="csr")
    got = sell.pack_steps(tri, diag, sysd.fwd_rounds, sysd.drop)
    rows, cols, vals, dinv, live = _pack_steps_reference(
        tri, diag, sysd.fwd_rounds, sysd.drop)
    np.testing.assert_array_equal(got.rows, rows)
    np.testing.assert_array_equal(got.cols, cols)
    np.testing.assert_array_equal(got.vals, vals)
    np.testing.assert_array_equal(got.dinv, dinv)
    np.testing.assert_array_equal(got.live, live)


def test_pack_ell_and_sell_match_reference():
    a = sp.csr_matrix(graph_laplacian(200, avg_degree=5, seed=3))
    a.sort_indices()
    cols, vals = sell.pack_ell(a)
    n, k = a.shape[0], cols.shape[0]
    cols_ref = np.zeros((n, k), dtype=np.int32)
    vals_ref = np.zeros((n, k))
    for r in range(n):
        lo, hi = a.indptr[r], a.indptr[r + 1]
        cols_ref[r, :hi - lo] = a.indices[lo:hi]
        vals_ref[r, :hi - lo] = a.data[lo:hi]
    np.testing.assert_array_equal(cols, cols_ref.T)
    np.testing.assert_array_equal(vals, vals_ref.T)

    w = 4
    sm = sell.pack_sell(a, w)
    for r in range(n):
        lo, hi = a.indptr[r], a.indptr[r + 1]
        s, lane = divmod(r, w)
        np.testing.assert_array_equal(sm.cols[s, :hi - lo, lane],
                                      a.indices[lo:hi])
        np.testing.assert_array_equal(sm.vals[s, :hi - lo, lane],
                                      a.data[lo:hi])
        assert not sm.vals[s, hi - lo:, lane].any()


# ---------------------------------------------------------------------------
# 3. Plan reuse: identical to solve_iccg, zero warm setup.
# ---------------------------------------------------------------------------

def test_plan_reuse_bitwise_identical_to_solve_iccg():
    a = laplace_2d(16, 14)
    b = np.random.default_rng(1).normal(size=a.shape[0])
    plan = build_plan(a, method="hbmc", block_size=8, w=4)
    cold = solve_iccg(a, b, method="hbmc", block_size=8, w=4)
    r1 = plan.solve(b)
    r2 = plan.solve(b)
    assert r1.result.iterations == cold.result.iterations
    assert r2.result.iterations == cold.result.iterations
    np.testing.assert_array_equal(r1.x, cold.x)
    np.testing.assert_array_equal(r1.x, r2.x)


def test_warm_plan_solve_performs_zero_host_setup(monkeypatch):
    """Acceptance: after the first solve, plan.solve touches NO setup entry
    point — ordering, factorization, packing and operator builds are all
    poisoned and the warm solve must still succeed, bitwise identically."""
    a = laplace_2d(12, 10)
    b = np.random.default_rng(2).normal(size=a.shape[0])
    plan = build_plan(a, method="hbmc", block_size=8, w=4)
    warm_ref = plan.solve(b)
    count = plan.setup_count

    def boom(*a_, **k_):
        raise AssertionError("host-side setup ran during a warm plan.solve")

    for name in ("_order_system", "ic0_structure", "ic0_refactor",
                 "_build_spmv_ops", "_pack_spmv", "_build_preconditioner"):
        monkeypatch.setattr(plan_mod, name, boom)
    monkeypatch.setattr(plan_mod.sell, "pack_steps", boom)
    monkeypatch.setattr(plan_mod.sell, "pack_factor", boom)
    monkeypatch.setattr(plan_mod.sell, "pack_ell", boom)
    monkeypatch.setattr(plan_mod.sell, "pack_sell", boom)
    monkeypatch.setattr(plan_mod.sell, "fuse_round_major", boom)

    warm = plan.solve(b)
    bb = np.stack([b, 0.5 * b], axis=1)
    warm_b = plan.solve_batched(bb)
    assert plan.setup_count == count
    np.testing.assert_array_equal(warm.x, warm_ref.x)
    assert warm_b.result.converged.all()


def test_plan_solve_batched_matches_front_end():
    a = laplace_2d(12, 12)
    bb = np.random.default_rng(3).normal(size=(a.shape[0], 3))
    plan = build_plan(a, method="hbmc", block_size=8, w=4)
    rp = plan.solve_batched(bb)
    rf = solve_iccg_batched(a, bb, method="hbmc", block_size=8, w=4)
    np.testing.assert_array_equal(rp.result.iterations,
                                  rf.result.iterations)
    np.testing.assert_array_equal(rp.x, rf.x)


# ---------------------------------------------------------------------------
# 4. Refactor: numeric-only renewal matches a cold solve.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ("hbmc", "mc"))
def test_refactor_matches_cold_solve(method):
    a = laplace_2d(14, 12)
    b = np.random.default_rng(4).normal(size=a.shape[0])
    plan = build_plan(a, method=method, block_size=8, w=4)
    plan.solve(b)
    # perturb values, keep the pattern (implicit-time-step-style change)
    a2 = (a + 0.37 * sp.diags(a.diagonal())).tocsr()
    a2.sort_indices()
    timings = plan.refactor(a2)
    assert timings.ordering == 0.0        # ordering is never redone
    warm = plan.solve(b)
    cold = solve_iccg(a2, b, method=method, block_size=8, w=4)
    assert warm.result.iterations == cold.result.iterations
    np.testing.assert_allclose(warm.x, cold.x, rtol=1e-12, atol=1e-12)
    assert plan.refactor_count == 1


def test_refactor_does_not_retrace_pcg():
    """The jitted PCG takes the factor/SpMV operands as traced arguments
    (round_major and index+xla paths), so a refactor swaps arrays of
    identical shape without recompiling anything."""
    a = laplace_2d(12, 10)
    b = np.random.default_rng(9).normal(size=a.shape[0])
    plan = build_plan(a, method="hbmc", block_size=8, w=4)
    plan.solve(b)
    assert plan._trace_count == 1
    plan.solve(b)
    assert plan._trace_count == 1          # warm solve: no retrace
    a2 = (a + 0.2 * sp.diags(a.diagonal())).tocsr()
    plan.refactor(a2)
    rep = plan.solve(b)
    assert plan._trace_count == 1          # refactor: still no retrace
    cold = solve_iccg(a2, b, method="hbmc", block_size=8, w=4)
    assert rep.result.iterations == cold.result.iterations
    np.testing.assert_allclose(rep.x, cold.x, rtol=1e-12, atol=1e-12)


def test_refactor_rejects_different_pattern():
    a = laplace_2d(10, 10)
    plan = build_plan(a, method="hbmc", block_size=8, w=4)
    with pytest.raises(ValueError, match="structure-identical"):
        plan.refactor(laplace_2d(11, 10))
    a_denser = (a + sp.diags(np.ones(a.shape[0] - 2), 2)).tocsr()
    with pytest.raises(ValueError, match="structure-identical"):
        plan.refactor(a_denser)


# ---------------------------------------------------------------------------
# Satellite: result.x padded-state-leak regression.
# ---------------------------------------------------------------------------

def test_result_x_in_caller_space_padded_round_major():
    """Regression: result.x used to leak the internal padded round-major
    vector (shape (3264,) on this n=2021 system)."""
    a = laplace_2d(47, 43)
    n = a.shape[0]
    b = np.random.default_rng(5).normal(size=n)
    rep = solve_iccg(a, b, method="hbmc", block_size=16, w=8)
    assert rep.n_padded > n                   # genuinely padded
    assert rep.result.x.shape == (n,)
    np.testing.assert_array_equal(rep.result.x, rep.x)
    err = np.linalg.norm(a @ rep.result.x - b) / np.linalg.norm(b)
    assert err < 1e-6

    bb = np.random.default_rng(6).normal(size=(n, 3))
    rb = solve_iccg_batched(a, bb, method="hbmc", block_size=16, w=8)
    assert rb.result.x.shape == (n, 3)
    np.testing.assert_array_equal(rb.result.x, rb.x)


# ---------------------------------------------------------------------------
# Satellite: shifted-IC semantics on the Ieej generator (paper §5.1).
# ---------------------------------------------------------------------------

def test_shifted_ic_semantics_ieej():
    """shift=alpha factorizes A + alpha*diag(A); equivalently the diagonally
    scaled formulation: L(D^{-1/2}(A + alpha D)D^{-1/2}) == D^{-1/2} L."""
    a, _ = paper_problem("ieej", "tiny")
    alpha = PAPER_SHIFTS["ieej"]
    sysd = _order_system(sp.csr_matrix(a), None, "hbmc", 8, 4)
    a_bar = sysd.a_bar

    l_shift = ic0(a_bar, shift=alpha)
    # 1. explicit shifted matrix, unshifted factorization -> same factor
    a_explicit = (a_bar + alpha * sp.diags(a_bar.diagonal())).tocsr()
    l_explicit = ic0(a_explicit)
    np.testing.assert_allclose(l_shift.toarray(), l_explicit.toarray(),
                               rtol=1e-14, atol=0.0)
    # 2. round-parallel path agrees
    l_rounds = ic0_rounds(a_bar, sysd.fwd_rounds, shift=alpha)
    np.testing.assert_allclose(l_rounds.toarray(), l_shift.toarray(),
                               rtol=1e-14, atol=0.0)
    # 3. diag-scaled equivalence from the docstring
    dinv_sqrt = sp.diags(1.0 / np.sqrt(a_bar.diagonal()))
    b_scaled = (dinv_sqrt @ a_explicit @ dinv_sqrt).tocsr()
    l_scaled = ic0(b_scaled)
    np.testing.assert_allclose(l_scaled.toarray(),
                               (dinv_sqrt @ l_shift).toarray(),
                               rtol=1e-10, atol=1e-12)
    # 4. the shifted solve converges on the semi-definite-ish system
    b = np.random.default_rng(7).normal(size=a.shape[0])
    rep = solve_iccg(a, b, method="hbmc", block_size=8, w=4, shift=alpha)
    assert rep.result.converged


# ---------------------------------------------------------------------------
# Satellite: batched record_history parity.
# ---------------------------------------------------------------------------

def test_batched_history_matches_singles():
    a = laplace_2d(13, 12)
    n = a.shape[0]
    bb = np.random.default_rng(8).normal(size=(n, 4))
    bb[:, 2] *= 1e3                       # spread the iteration counts
    kw = dict(method="hbmc", block_size=8, w=4)
    rb = solve_iccg_batched(a, bb, record_history=True, **kw)
    hist = rb.result.history
    assert hist.shape[1] == 4
    for j in range(4):
        single = solve_iccg(a, bb[:, j], record_history=True, **kw)
        hs = single.result.history
        hj = hist[:len(hs), j]
        # same NaN pattern: column j's history freezes at convergence
        np.testing.assert_array_equal(np.isnan(hj), np.isnan(hs))
        m = ~np.isnan(hs)
        np.testing.assert_allclose(hj[m], hs[m], rtol=1e-10)
        assert rb.result.iterations[j] == single.result.iterations


def test_batched_history_empty_by_default():
    a = laplace_2d(8, 8)
    bb = np.ones((a.shape[0], 2))
    rb = solve_iccg_batched(a, bb, method="hbmc", block_size=4, w=2)
    assert rb.result.history.size == 0
