"""Distribution of the HBMC ICCG solver over a device mesh.

THIS MODULE IS A THIN COMPATIBILITY SHIM.  The distribution layer proper
lives in the plan stack:

    core/plan.py        ``build_plan(a, ..., mesh=, mesh_axis=)`` — a
                        mesh-aware ``SolverPlan`` (factor once, solve many,
                        refactor without retrace), whose preconditioner
                        apply is the fused round-major sweep with ONE
                        collective per round
    core/trisolve.py    ``DistributedRoundMajorPreconditioner`` /
                        ``_dist_substitute_fused`` — the sharded fwd+bwd
                        substitution (``shard_map`` over every segment's
                        lane axis)
    core/iccg.py        ``make_sharded_spmv`` — row/slice-sharded ELL/SELL
                        SpMV with one all-gather per apply

Parallel-ordering semantics map onto the mesh exactly as the paper maps
them onto threads (§4.4.3), one level up:

    color      -> sequential rounds (the fori_loop over fused steps)
    level-1 blocks of a color -> *devices* (the mesh axis): every
                  segment's lane axis is sharded, so each device owns a
                  contiguous batch of level-1 blocks
    w lanes    -> VPU vector lanes within a device

Per round, every device solves its lanes locally (gathering from its
replica of y) and the lane updates are all-gathered — the distributed
analogue of the "one synchronization per color" property.  The state
vectors are replicated; the tables (the heavy data: vals/cols) are fully
sharded.

``distributed_iccg`` / ``lower_solver_step`` below are wrappers kept for
the pre-plan call sites; ``shard_tables`` is the legacy index-layout
sharding util (the seed's two-pass path), superseded by the fused plan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .iccg import pcg_iteration, spmv_ell
from .plan import BatchedICCGReport, ICCGReport, build_plan
from .trisolve import DeviceTables, auto_mesh, backward_solve, forward_solve


def distributed_iccg(a: sp.spmatrix, b: np.ndarray, mesh: Mesh, *,
                     axis: str = "data", method: str = "hbmc",
                     block_size: int = 32, w: int = 8, shift: float = 0.0,
                     rtol: float = 1e-7, maxiter: int = 10_000,
                     spmv_format: str = "ell", dtype=jnp.float64,
                     record_history: bool = False) -> ICCGReport:
    """One-shot distributed solve: mesh-aware plan, solve, report.

    Takes the ORIGINAL system (``a``, ``b``) — ordering, padding and the
    round-major embedding happen inside the plan, and ``report.x`` /
    ``report.result.x`` carry the solution in the caller's ordering.  (The
    seed-era version consumed a pre-padded HBMC system and returned the
    internal padded/permuted vector — the padded-state leak fixed
    everywhere else in PR3; regression-tested in tests/test_multidevice.py.)

    Workloads solving against one matrix repeatedly should hold the plan:
    ``build_plan(a, ..., mesh=mesh)`` then ``plan.solve(...)`` /
    ``plan.refactor(...)``.
    """
    plan = build_plan(a, method=method, block_size=block_size, w=w,
                      shift=shift, spmv_format=spmv_format, dtype=dtype,
                      mesh=mesh, mesh_axis=axis)
    rep = plan.solve(np.asarray(b), rtol=rtol, maxiter=maxiter,
                     record_history=record_history)
    rep.setup_seconds += plan.timings.total
    return rep


def distributed_iccg_batched(a: sp.spmatrix, b: np.ndarray, mesh: Mesh, *,
                             axis: str = "data", method: str = "hbmc",
                             block_size: int = 32, w: int = 8,
                             shift: float = 0.0, rtol: float = 1e-7,
                             maxiter: int = 10_000,
                             spmv_format: str = "ell", dtype=jnp.float64,
                             record_history: bool = False
                             ) -> BatchedICCGReport:
    """Multi-RHS variant of ``distributed_iccg`` (``b``: (n, B))."""
    plan = build_plan(a, method=method, block_size=block_size, w=w,
                      shift=shift, spmv_format=spmv_format, dtype=dtype,
                      mesh=mesh, mesh_axis=axis)
    rep = plan.solve_batched(np.asarray(b), rtol=rtol, maxiter=maxiter,
                             record_history=record_history)
    rep.setup_seconds += plan.timings.total
    return rep


# ---------------------------------------------------------------------------
# Legacy index-layout sharding (the seed's two-pass path).  Kept because the
# roofline dry-run lowers against it; the production distributed apply is
# the fused round-major sweep above.
# ---------------------------------------------------------------------------

def shard_tables(tables: DeviceTables, mesh: Mesh, axis: str = "data"
                 ) -> DeviceTables:
    """Shard the lane axis (R) of index-layout step tables over ``axis``.

    R is padded to a multiple of the axis size (padding lanes follow the
    scratch-slot convention and are inert).
    """
    n_dev = mesh.shape[axis]
    s, r = tables.dinv.shape
    rpad = (-r) % n_dev
    if rpad:
        pad2 = lambda a, fill: jnp.pad(a, ((0, 0), (0, rpad)),
                                       constant_values=fill)
        pad3 = lambda a, fill: jnp.pad(a, ((0, 0), (0, rpad), (0, 0)),
                                       constant_values=fill)
        tables = DeviceTables(
            rows=pad2(tables.rows, tables.n_slots - 1),
            cols=pad3(tables.cols, tables.n_slots - 1),
            vals=pad3(tables.vals, 0.0),
            dinv=pad2(tables.dinv, 0.0),
            n_slots=tables.n_slots)
    sh2 = NamedSharding(mesh, P(None, axis))
    sh3 = NamedSharding(mesh, P(None, axis, None))
    return DeviceTables(
        rows=jax.device_put(tables.rows, sh2),
        cols=jax.device_put(tables.cols, sh3),
        vals=jax.device_put(tables.vals, sh3),
        dinv=jax.device_put(tables.dinv, sh2),
        n_slots=tables.n_slots)


def lower_solver_step(fwd: DeviceTables, bwd: DeviceTables,
                      a_ell_cols, a_ell_vals, mesh: Mesh, axis="data"):
    """Lower one PCG iteration on the production mesh (dry-run bonus cell:
    the paper's own kernel under the multi-pod roofline).

    The iteration is ``iccg.pcg_iteration`` — the PRECONDITIONED pairings
    (``alpha = (r,z)/(p,Ap)``, ``beta = (r2,z2)/(r,z)``), carrying ``rz``
    between steps, so the lowered HLO contains BOTH triangular sweeps (the
    seed-era version used ``(r,r)`` pairings, which lowered a plain-CG
    kernel with no trisolve traffic at all — asserted against in
    tests/test_multidevice.py).

    Requires n and R to be multiples of the axis size (arrange via the HBMC
    block/w parameters).
    """
    mesh = auto_mesh(mesh)
    rep = NamedSharding(mesh, P())
    n = fwd.n_slots - 1
    assert a_ell_cols.shape[1] == n

    def one_iteration(x, r, p, rz, vals, cols, fwd_t, bwd_t):
        spmv = lambda v: spmv_ell(vals, cols, v)
        precond = lambda v: backward_solve(bwd_t, forward_solve(fwd_t, v))
        return pcg_iteration(spmv, precond)(x, r, p, rz)

    sds = lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    row_sh = NamedSharding(mesh, P(None, axis))
    sh2 = NamedSharding(mesh, P(None, axis))
    sh3 = NamedSharding(mesh, P(None, axis, None))
    vec = jax.ShapeDtypeStruct((n,), fwd.vals.dtype, sharding=rep)
    scalar = jax.ShapeDtypeStruct((), fwd.vals.dtype, sharding=rep)

    with mesh:
        jitted = jax.jit(one_iteration)
        lowered = jitted.lower(
            vec, vec, vec, scalar,
            sds(a_ell_vals, row_sh), sds(a_ell_cols, row_sh),
            _abstract_tables(fwd, sh2, sh3),
            _abstract_tables(bwd, sh2, sh3))
    return lowered


def _abstract_tables(t: DeviceTables, sh2, sh3) -> DeviceTables:
    sds = lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    return DeviceTables(rows=sds(t.rows, sh2), cols=sds(t.cols, sh3),
                        vals=sds(t.vals, sh3), dinv=sds(t.dinv, sh2),
                        n_slots=t.n_slots)
