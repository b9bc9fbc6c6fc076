"""HPCG's 27-point problem through ``build_plan`` -> ``SolverPlan.solve``,
against a plain host reference written here.

The reference takes nothing from the program but the ordering: scipy
CSR, a textbook row-by-row IC(0) of ``A[perm][:, perm]``, sparse
triangular substitutions and a textbook PCG, all in float64.  The plan
runs the same preconditioned iteration in another order of arithmetic,
so iteration counts agree to one and solutions to rounding.

The matrix is the benchmark's own generator of HPCG's operator
(``bench/configs/hpcg3d.py``).  At m = 20 the plan is cut into ten
segments with 9- and 5-lane tails and K = 26, the shape of the hpcg3d
cell's plan at a size the CPU solves in seconds.
"""
import importlib.util
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.linalg import spsolve_triangular

from repro.core import build_plan

RTOL = 1e-7
GENERATOR = Path(__file__).resolve().parents[1] / "bench/configs/hpcg3d.py"


def _generator():
    spec = importlib.util.spec_from_file_location("hpcg3d_generator",
                                                  GENERATOR)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.hpcg_27pt


hpcg_27pt = _generator()


def ic0(a: sp.csr_matrix) -> sp.csr_matrix:
    """Textbook IC(0), row by row: L has A's lower pattern and
    L L^T = A on that pattern."""
    a = sp.csr_matrix(a)
    a.sort_indices()
    rows = []          # rows[i]: {column: value} of L's row i, j <= i
    for i in range(a.shape[0]):
        lo, hi = a.indptr[i], a.indptr[i + 1]
        row = {}
        for j, v in zip(a.indices[lo:hi], a.data[lo:hi]):
            if j < i:
                s = sum(lij * rows[j].get(k, 0.0) for k, lij in row.items())
                row[j] = (v - s) / rows[j][j]
            elif j == i:
                row[i] = np.sqrt(v - sum(x * x for x in row.values()))
        rows.append(row)
    ri = [i for i, r in enumerate(rows) for _ in r]
    ci = [j for r in rows for j in r]
    vals = [v for r in rows for v in r.values()]
    return sp.csr_matrix((vals, (ri, ci)), shape=a.shape)


def pcg(a, l, b, rtol=RTOL, maxiter=1000):
    """Textbook PCG from x = 0 with M = L L^T, stopped when the updated
    residual's norm falls below rtol * ||b||."""
    lt = sp.csr_matrix(l.T)

    def prec(r):
        return spsolve_triangular(lt, spsolve_triangular(l, r, lower=True),
                                  lower=False)
    x = np.zeros_like(b)
    r = b.copy()
    z = prec(r)
    p = z.copy()
    rz = r @ z
    bnorm = np.linalg.norm(b)
    it = 0
    while np.linalg.norm(r) / bnorm >= rtol and it < maxiter:
        q = a @ p
        alpha = rz / (p @ q)
        x += alpha * p
        r -= alpha * q
        z = prec(r)
        rz, rz_old = r @ z, rz
        p = z + (rz / rz_old) * p
        it += 1
    return x, it


def reference_solve(a, plan, b):
    """The reference in the plan's ordering, mapped back."""
    perm = plan._sysd.perm          # original index -> ordered position
    order = np.argsort(perm)        # ordered sequence of original indices
    a_o = sp.csr_matrix(a[order][:, order])
    y, it = pcg(a_o, ic0(a_o), b[order])
    x = np.empty_like(y)
    x[order] = y
    return x, it


@pytest.mark.parametrize("m, colours, segments", [(12, 5, 6), (16, 4, 6),
                                                  (20, 6, 10)])
def test_plan_matches_the_plain_reference(m, colours, segments):
    a = hpcg_27pt(m)
    plan = build_plan(a, method="hbmc", block_size=32, w=8)
    assert (plan.n_colors, plan.n_segments) == (colours, segments)
    b = np.random.default_rng(m).standard_normal(a.shape[0])
    rep = plan.solve(b, rtol=RTOL, maxiter=1000)
    x_ref, it_ref = reference_solve(a, plan, b)
    assert rep.result.status == "CONVERGED"
    assert abs(rep.result.iterations - it_ref) <= 1
    assert (np.linalg.norm(rep.x - x_ref)
            <= 1e-9 * np.linalg.norm(x_ref))


def test_hpcg_rhs_solves_to_ones():
    """HPCG's own right-hand side b = A * ones has the solution ones."""
    a = hpcg_27pt(20)
    plan = build_plan(a, method="hbmc", block_size=32, w=8)
    ones = np.ones(a.shape[0])
    rep = plan.solve(a @ ones, rtol=RTOL, maxiter=1000)
    assert rep.result.status == "CONVERGED"
    assert np.max(np.abs(rep.x - ones)) <= 1e-6


def test_multi_segment_plan_has_the_cells_shape():
    """m = 20: 9- and 5-lane tail segments, each tail cut once more where
    K parts, and K = 26 on both halves."""
    plan = build_plan(hpcg_27pt(20), method="hbmc", block_size=32, w=8)
    tables = plan._precond.tables
    lanes = [r for _, r in tables.segments]
    assert lanes[-4:] == [9, 9, 5, 5]
    ks = {t.cols.shape[1] for t in tables.fwd + tables.bwd}
    assert max(ks) == 26


@pytest.mark.parametrize("where, length", [("corner", 8), ("edge", 12),
                                           ("face", 18), ("interior", 27)])
def test_generator_is_hpcgs_operator(where, length):
    """26 on the diagonal, -1 per in-grid neighbour, natural order (x
    fastest), as HPCG's GenerateProblem builds it."""
    m = 5
    a = hpcg_27pt(m)
    point = {"corner": (0, 0, 0), "edge": (2, 0, 0), "face": (2, 2, 0),
             "interior": (2, 2, 2)}[where]
    ix, iy, iz = point
    i = iz * m * m + iy * m + ix
    row = a.getrow(i)
    assert row.nnz == length
    expect = {}
    for sz in (-1, 0, 1):
        for sy in (-1, 0, 1):
            for sx in (-1, 0, 1):
                jx, jy, jz = ix + sx, iy + sy, iz + sz
                if 0 <= jx < m and 0 <= jy < m and 0 <= jz < m:
                    j = jz * m * m + jy * m + jx
                    expect[j] = 26.0 if j == i else -1.0
    assert dict(zip(row.indices.tolist(), row.data.tolist())) == expect
    assert (a != a.T).nnz == 0
    assert a.nnz == (3 * m - 2) ** 3


@pytest.mark.parametrize("m, layout", [(12, "round_major"), (20, "round_major"),
                                       (12, "index")])
def test_sweep_counters_count_the_tables(m, layout):
    """``sweep_gather_slots`` is rounds x K x lanes over every segment and
    half, ``sweep_live_entries`` the slots that point at a real position
    (the factor's off-diagonal entries, once per half), and every report
    carries them with ``sweep_steps``."""
    a = hpcg_27pt(m)
    plan = build_plan(a, method="hbmc", block_size=32, w=8, layout=layout)
    if layout == "round_major":
        tables = plan._precond.tables
        halves = tables.fwd + tables.bwd
        hole = tables.m
    else:
        halves = (plan._precond.fwd, plan._precond.bwd)
        hole = plan._precond.fwd.n_slots - 1
    assert plan.sweep_gather_slots == sum(t.cols.size for t in halves)
    assert plan.sweep_live_entries == sum(
        int(np.count_nonzero(np.asarray(t.cols) != hole)) for t in halves)
    assert plan.sweep_live_entries == a.nnz - a.shape[0]
    rep = plan.solve(np.ones(a.shape[0]), rtol=RTOL)
    batched = plan.solve_batched(np.ones((a.shape[0], 2)), rtol=RTOL)
    for r in (rep, batched):
        assert (r.sweep_steps, r.sweep_gather_slots, r.sweep_live_entries) \
            == (2 * plan.n_rounds, plan.sweep_gather_slots,
                plan.sweep_live_entries)
