"""Static analysis tier: race detector witnesses, contracts, kernel checks.

Three layers of evidence, mirroring ``src/repro/analysis``:

  1. **soundness** — every mutation of a legal schedule (rows swapped
     across rounds, colors merged, IC(0) steps reordered, tables tampered)
     is rejected with a witness naming the exact offending DAG edge;
  2. **completeness** — all four orderings over all five paper generators
     (and the Laplacians) pass ``validate="full"``, and the same proof
     gates ``build_plan`` and ``PlanCache`` admission;
  3. **packing hardening** — corrupted CSR indices raise
     ``PackingIndexError`` on the host instead of packing garbage tables.

The PR-9 analyzers (dtype flow, collective structure, traffic model,
bench gate — including ``validate="deep"``) have their own mutation
tier in ``tests/test_numerics_analysis.py``.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.analysis import (FULL_PALLAS_ITERATION, PALLAS_SPMV,
                            ContractError, PrimitiveBudget, ScheduleError,
                            assert_budget, assert_plan_valid,
                            check_fused_tables, check_ic0_structure,
                            check_plan_kernels, check_reversed_rounds,
                            check_rounds,
                            check_sell_spmv, check_step_tables,
                            check_trisolve_fused, lint, retraces,
                            validate_plan)
from repro.analysis.__main__ import main as analysis_main
from repro.core import (PackingIndexError, build_plan, fuse_round_major,
                        ic0, pack_ell, pack_factor, pack_sell, pack_steps)
from repro.core.ic0 import ic0_structure
from repro.core.matrices import (PAPER_PROBLEMS, PAPER_SHIFTS, laplace_2d,
                                 paper_problem)
from repro.core.solvers import _order_system
from repro.serve.solver import PlanCache

ORDERINGS = ("mc", "bmc", "hbmc", "natural")


def _system(method, nx=13, ny=11, bs=8, w=4):
    a = laplace_2d(nx, ny)
    sysd = _order_system(sp.csr_matrix(a), None, method, bs, w)
    return a, sysd, ic0(sysd.a_bar)


def _dependent_pair(sysd):
    """A DAG edge (j -> i) whose endpoints sit in different rounds."""
    low = sp.tril(sp.csr_matrix(sysd.a_bar), k=-1).tocoo()
    round_of = {}
    for s, r in enumerate(sysd.fwd_rounds):
        for row in r:
            round_of[int(row)] = s
    for j, i, v in zip(low.col, low.row, low.data):
        j, i = int(j), int(i)
        if v != 0 and j in round_of and i in round_of \
                and round_of[j] != round_of[i]:
            return j, i
    raise AssertionError("no cross-round dependency edge found")


def _swap_rows_in_place(rounds, i, j):
    for r in rounds:
        mi, mj = r == i, r == j
        r[mi] = j
        r[mj] = i


# ---------------------------------------------------------------------------
# 1. Soundness: mutations are rejected with the exact witness.
# ---------------------------------------------------------------------------

def test_row_swap_across_rounds_pins_exact_edge():
    """Swapping a dependent pair across rounds must produce a
    cross-round-order witness naming exactly that DAG edge."""
    _, sysd, _ = _system("mc")
    j, i = _dependent_pair(sysd)
    _swap_rows_in_place(sysd.fwd_rounds, i, j)
    vio = check_rounds(sysd.a_bar, sysd.fwd_rounds, drop_mask=sysd.drop)
    assert any(v.kind == "cross-round-order" and v.edge == (j, i)
               for v in vio), [str(v) for v in vio]


def test_merged_colors_break_the_antichain():
    _, sysd, _ = _system("mc")
    merged = [np.concatenate(sysd.fwd_rounds[:2])] + sysd.fwd_rounds[2:]
    vio = check_rounds(sysd.a_bar, merged, drop_mask=sysd.drop)
    kinds = {v.kind for v in vio}
    assert "intra-round-edge" in kinds, [str(v) for v in vio]
    # the witness pins a real edge of the merged round
    v = next(v for v in vio if v.kind == "intra-round-edge")
    assert v.round == 0 and v.edge is not None
    src, dst = v.edge
    assert sysd.a_bar[dst, src] != 0


def test_duplicate_and_unscheduled_rows_are_witnessed():
    _, sysd, _ = _system("mc")
    rounds = [r.copy() for r in sysd.fwd_rounds]
    dropped = int(rounds[0][0])
    rounds[0] = rounds[0][1:]                  # row now in no round
    rounds[1] = np.concatenate([rounds[1], [int(rounds[1][0])]])
    vio = check_rounds(sysd.a_bar, rounds, drop_mask=sysd.drop)
    kinds = {v.kind for v in vio}
    assert "duplicate-row" in kinds
    assert any(v.kind == "unscheduled-row" and v.rows == (dropped, dropped)
               for v in vio)


def test_backward_must_reverse_forward():
    _, sysd, _ = _system("hbmc")
    assert check_reversed_rounds(sysd.fwd_rounds, sysd.bwd_rounds) == []
    vio = check_reversed_rounds(sysd.fwd_rounds, sysd.bwd_rounds[::-1])
    assert vio and vio[0].kind == "backward-not-reversed"


def test_step_table_premature_read_is_witnessed():
    _, sysd, l_bar = _system("hbmc")
    fwd, _ = pack_factor(l_bar, sysd.fwd_rounds, sysd.bwd_rounds, sysd.drop)
    late_row = int(np.asarray(sysd.fwd_rounds[-1])[0])
    fwd.cols[0, 0, 0] = late_row            # step 0 reads a last-round row
    fwd.vals[0, 0, 0] = 1.0
    vio = check_step_tables(fwd)
    assert any(v.kind == "premature-read" and v.edge[0] == late_row
               and v.round == 0 for v in vio), [str(v) for v in vio]


def test_step_table_dropped_dependency_is_witnessed():
    _, sysd, l_bar = _system("mc")
    tri = sp.tril(sp.csr_matrix(l_bar), k=-1, format="csr")
    fwd, _ = pack_factor(l_bar, sysd.fwd_rounds, sysd.bwd_rounds, sysd.drop)
    assert check_step_tables(fwd, tri=tri) == []
    live = np.argwhere(fwd.vals != 0)
    s, t, k = (int(x) for x in live[0])
    fwd.vals[s, t, k] = 0.0                 # silently drop one dependency
    vio = check_step_tables(fwd, tri=tri)
    assert any(v.kind == "dropped-dependency" for v in vio)


def test_fused_table_self_read_is_witnessed():
    _, sysd, l_bar = _system("hbmc")
    fused = fuse_round_major(*pack_factor(l_bar, sysd.fwd_rounds,
                                          sysd.bwd_rounds, sysd.drop))
    assert check_fused_tables(fused) == []
    lay = fused.layout
    g, t = 1, 0
    pos = g * lay.segments[0][1] + t        # segment 0 starts at 0
    assert lay.rows[pos] != lay.n_slots - 1
    fused.fwd[0].cols[g, 0, t] = pos        # forward half reads its own slot
    fused.fwd[0].vals[g, 0, t] = 1.0
    vio = check_fused_tables(fused)
    assert any(v.kind == "premature-read" and v.edge == (pos, pos)
               for v in vio), [str(v) for v in vio]


def test_ic0_step_reorder_is_witnessed():
    _, sysd, _ = _system("mc")
    st = ic0_structure(sysd.a_bar, sysd.fwd_rounds)
    assert check_ic0_structure(st) == []
    bad = dataclasses.replace(st, steps=list(reversed(st.steps)))
    vio = check_ic0_structure(bad)
    assert any(v.kind == "premature-read" for v in vio)


# ---------------------------------------------------------------------------
# 2. Completeness: the paper's orderings prove clean, and the proof gates
#    build_plan and PlanCache admission.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ORDERINGS)
@pytest.mark.parametrize("problem", PAPER_PROBLEMS)
def test_paper_generators_prove_race_free(problem, method):
    a, _ = paper_problem(problem, "tiny")
    plan = build_plan(a, method=method,
                      shift=PAPER_SHIFTS.get(problem, 0.0),
                      validate="full")     # raises ScheduleError on a race
    assert plan.validate == "full"
    assert validate_plan(plan, "cheap") == []


@pytest.mark.parametrize("method", ORDERINGS)
def test_validate_full_passes_all_layouts(method):
    a = laplace_2d(13, 11)
    for layout in ("index", "round_major"):
        plan = build_plan(a, method=method, block_size=8, w=4,
                          layout=layout, validate="full")
        assert validate_plan(plan, "full") == []


def test_build_plan_rejects_unknown_validate_mode():
    with pytest.raises(ValueError, match="validate"):
        build_plan(laplace_2d(6, 5), method="mc", validate="banana")


def test_tampered_plan_fails_validation():
    plan = build_plan(laplace_2d(13, 11), method="mc", validate="full")
    j, i = _dependent_pair(plan._sysd)
    _swap_rows_in_place(plan._sysd.fwd_rounds, i, j)
    _swap_rows_in_place(plan._sysd.bwd_rounds, i, j)
    with pytest.raises(ScheduleError) as exc:
        assert_plan_valid(plan, "cheap", context="tampered")
    assert any(v.kind == "cross-round-order" and v.edge == (j, i)
               for v in exc.value.violations)
    assert "tampered" in str(exc.value)


def test_plan_cache_admission_rejects_racy_plans():
    a = laplace_2d(9, 8)

    def sabotaged_build(a_, **knobs):
        plan = build_plan(a_, **knobs)
        j, i = _dependent_pair(plan._sysd)
        _swap_rows_in_place(plan._sysd.fwd_rounds, i, j)
        _swap_rows_in_place(plan._sysd.bwd_rounds, i, j)
        return plan

    cache = PlanCache(capacity=2, build=sabotaged_build, validate="full")
    with pytest.raises(ScheduleError):
        cache.get(a, method="mc")
    # the racy plan never entered the cache: no later hit can dispatch it
    assert len(cache) == 0

    clean = PlanCache(capacity=2, validate="full")
    plan, status = clean.get(a, method="mc")
    assert status == "miss" and len(clean) == 1
    _, status = clean.get(a, method="mc")
    assert status == "hit"                   # admission runs on misses only

    with pytest.raises(ValueError, match="validate"):
        PlanCache(validate="banana")


def test_analysis_cli_clean_run_exits_zero(capsys):
    rc = analysis_main(["--problems", "laplace2d,thermal2",
                        "--methods", "hbmc,mc", "--scale", "tiny",
                        "--contracts"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "all 4 audits clean" in out


# ---------------------------------------------------------------------------
# 3. Contract linter and kernel checks.
# ---------------------------------------------------------------------------

def test_lint_flags_forbidden_required_and_exact():
    gatherful = lambda x: x[jnp.array([0, 2, 1])]           # noqa: E731
    v = jnp.arange(4.0)
    findings = lint(gatherful, v, budget=PALLAS_SPMV)
    assert any("gather" in f for f in findings)
    assert any("pallas_call" in f for f in findings)        # required, absent
    with pytest.raises(ContractError, match="gather"):
        assert_budget(gatherful, v, budget=PALLAS_SPMV, context="spmv")
    exact = PrimitiveBudget(name="exact", exact=(("sin", 2),))
    assert lint(jnp.sin, v, budget=exact) != []
    assert lint(lambda x: jnp.sin(jnp.sin(x)), v, budget=exact) == []
    loops = PrimitiveBudget(name="loops", min_loops=2)
    assert any("loop" in f for f in lint(jnp.sin, v, budget=loops))


def test_full_pallas_budget_enforced_on_plan():
    plan = build_plan(laplace_2d(10, 8), method="hbmc", block_size=8, w=4,
                      spmv_format="sell", backend="pallas",
                      spmv_backend="pallas", interpret=True,
                      validate="full")
    pre = plan._precond
    assert lint(pre, jnp.zeros((plan.slab_m,)),
                budget=FULL_PALLAS_ITERATION) == []
    assert retraces(plan, lambda: None) == 0
    # the backend selection implies static kernel contracts — all clean
    assert check_plan_kernels(plan) == []


def test_kernel_checks_catch_corruption_and_vmem():
    plan = build_plan(laplace_2d(10, 8), method="hbmc", block_size=8, w=4,
                      spmv_format="sell", backend="pallas",
                      spmv_backend="pallas", interpret=True)
    cols, vals, dinv = (np.array(x) for x in plan._precond.tables.stacked())
    m = (cols.shape[0] // 2) * cols.shape[1]
    assert check_trisolve_fused(cols, vals, dinv) == []
    vio = check_trisolve_fused(cols, vals, dinv, vmem_budget=1024)
    assert any(v.kind == "vmem-budget" for v in vio)
    cols_bad = cols.copy()
    cols_bad[0, 0, 0] = m + 5
    vio = check_trisolve_fused(cols_bad, vals, dinv)
    assert any(v.kind == "index-bounds" for v in vio)
    vals_bad = vals.copy()
    vals_bad[np.asarray(cols) == m] = 1.0    # live value on the pad slot
    vio = check_trisolve_fused(cols, vals_bad, dinv)
    assert any(v.kind == "index-bounds" for v in vio)
    # odd step axis cannot split into fwd/bwd sweeps
    vio = check_trisolve_fused(cols[:-1], vals[:-1], dinv[:-1])
    assert any(v.kind == "grid-divisibility" for v in vio)


def test_kernel_vmem_budget_is_the_kernels_limit():
    """The static budget is the scoped VMEM the kernels request, and the
    Thermal2 analogue at its row count (the compiled plan's padded
    shapes) fits it."""
    from repro.analysis import (VMEM_BUDGET_BYTES, sell_spmv_vmem_bytes,
                                trisolve_fused_vmem_bytes)
    from repro.kernels.config import VMEM_LIMIT_BYTES
    assert VMEM_BUDGET_BYTES == VMEM_LIMIT_BYTES
    assert trisolve_fused_vmem_bytes(256, 19456, 4, 4) <= VMEM_BUDGET_BYTES
    assert (sell_spmv_vmem_bytes(256, 5, 8, 128 * 19456, 4)
            <= VMEM_BUDGET_BYTES)


def test_sell_kernel_checks():
    a = laplace_2d(10, 8)
    sm = pack_sell(a, 4)
    n_pad = sm.cols.shape[0] * sm.w
    assert check_sell_spmv(sm.vals, sm.cols, n_pad=n_pad) == []
    cols_bad = sm.cols.copy()
    live = np.argwhere(sm.vals != 0)
    s, k, w = (int(x) for x in live[0])
    cols_bad[s, k, w] = 10**6
    vio = check_sell_spmv(sm.vals, cols_bad, n_pad=n_pad)
    assert any(v.kind == "index-bounds" for v in vio)
    vio = check_sell_spmv(sm.vals, sm.cols, n_pad=n_pad, vmem_budget=256)
    assert any(v.kind == "vmem-budget" for v in vio)


# ---------------------------------------------------------------------------
# 4. Packing hardening: corrupted CSR never reaches a packed table.
# ---------------------------------------------------------------------------

def test_pack_ell_and_sell_reject_corrupt_indices():
    a = sp.csr_matrix(laplace_2d(6, 5))
    a.indices[3] = 10_000
    with pytest.raises(PackingIndexError, match="pack_ell"):
        pack_ell(a)
    with pytest.raises(PackingIndexError, match="pack_sell"):
        pack_sell(a, 4)
    a.indices[3] = -2
    with pytest.raises(PackingIndexError, match="pack_ell"):
        pack_ell(a)


def test_pack_steps_rejects_corrupt_inputs():
    _, sysd, l_bar = _system("mc", nx=6, ny=5)
    l_bar = sp.csr_matrix(l_bar)
    diag = l_bar.diagonal()
    tri = sp.tril(l_bar, k=-1, format="csr")
    n = tri.shape[0]
    bad_rounds = [r.copy() for r in sysd.fwd_rounds]
    bad_rounds[0] = np.concatenate([bad_rounds[0], [n + 7]])
    with pytest.raises(PackingIndexError, match="round"):
        pack_steps(tri, diag, bad_rounds)
    tri.indices[0] = n + 3
    with pytest.raises(PackingIndexError, match="pack_steps"):
        pack_steps(tri, diag, sysd.fwd_rounds)
