"""Preconditioned conjugate gradient (ICCG when preconditioner = IC(0)).

Device-side PCG with a ``lax.while_loop``; every kernel other than the
triangular solver (SpMV, dots, axpys) is embarrassingly parallel, exactly as
the paper notes in §2.  SpMV comes in the paper's two flavours:

  * ``spmv_ell``  — row-major gather (the paper's "crs_spmv" analogue)
  * ``spmv_sell`` — slice-packed SELL-w (the paper's "sell_spmv")

Convergence criterion: relative residual 2-norm < rtol (paper: 1e-7).
"""
from __future__ import annotations

import dataclasses
from functools import partial, wraps
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------------------
# Solve-status taxonomy.
#
# Every PCG front end (single-RHS, batched, slab) reports how it terminated
# as one of these codes instead of a bare converged bool.  Small ints so the
# codes live inside the jitted loops (int32 state) and cross the host
# boundary cheaply; ``STATUS_NAMES`` maps code -> name for reports.
#
#   RUNNING    — still iterating (only ever visible mid-slab, between
#                dispatch quanta; never a final status of pcg/pcg_batched)
#   CONVERGED  — relative residual dropped below rtol
#   MAXITER    — iteration budget exhausted with a finite, healthy state
#   BREAKDOWN  — non-positive curvature (p^T A p <= 0: the matrix is not
#                SPD on this Krylov space) or a non-finite residual /
#                pairing (NaN/Inf input, overflow, poisoned factor); the
#                reported iterate is the last *finite* one
#   DIVERGED   — relres grew past ``divergence_factor`` times its best
#   STAGNATED  — no new best relres for ``stagnation_window`` iterations
#
# Detection is select-based (``jnp.where``): on healthy inputs every guard
# selects the identical update the unguarded loop computed, so the float
# sequences — and therefore all parity/iteration-count pins — are
# bitwise-unchanged.
# ---------------------------------------------------------------------------

RUNNING, CONVERGED, MAXITER, BREAKDOWN, DIVERGED, STAGNATED = range(6)
STATUS_NAMES = ("RUNNING", "CONVERGED", "MAXITER", "BREAKDOWN", "DIVERGED",
                "STAGNATED")
#: statuses that mean "stop — more iterations cannot help" (the serving
#: layer quarantines slab columns that reach one of these)
UNHEALTHY_STATUSES = ("BREAKDOWN", "DIVERGED", "STAGNATED")

#: default divergence band: relres > factor * best-so-far trips DIVERGED.
#: PCG residuals oscillate, so the band is wide; healthy solves never
#: wander eight orders of magnitude above their best.
DIVERGENCE_FACTOR = 1e8
#: default stagnation window: iterations without a new best relres before
#: STAGNATED trips.  Healthy ICCG improves its best every few iterations.
STAGNATION_WINDOW = 1000


# ---------------------------------------------------------------------------
# Program scopes of the PCG's device work.
#
# ``jax.named_scope`` puts these names into the ``op_name`` metadata of every
# HLO instruction the traced code emits, which names the ops of a device
# trace: the SpMV, the preconditioner apply (the fused sweep) and the
# vector work (dots, axpys, the norm, the health-guard selects) of the PCG
# loop.  Scopes change metadata only, never an instruction or an answer.
# The preconditioner and SpMV scopes nest inside the vector scope, so an
# op's scope is the innermost of these names in its ``op_name``.
# ---------------------------------------------------------------------------

SPMV_SCOPE = "pcg.spmv"
SWEEP_SCOPE = "pcg.sweep"
VECTOR_SCOPE = "pcg.vector"
PCG_SCOPES = (SPMV_SCOPE, SWEEP_SCOPE, VECTOR_SCOPE)


def _in_scope(name: str, fn: Callable) -> Callable:
    def scoped(*args):
        with jax.named_scope(name):
            return fn(*args)
    return scoped


def _scoped_pcg(core: Callable) -> Callable:
    """Run a PCG core under ``VECTOR_SCOPE`` with its ``spmv`` under
    ``SPMV_SCOPE`` and its ``precond`` under ``SWEEP_SCOPE``."""
    @wraps(core)
    def run(spmv, precond, *args, **kwargs):
        with jax.named_scope(VECTOR_SCOPE):
            return core(_in_scope(SPMV_SCOPE, spmv),
                        _in_scope(SWEEP_SCOPE, precond), *args, **kwargs)
    return run


def status_name(code) -> str:
    """Human-readable name of a solve-status code."""
    return STATUS_NAMES[int(code)]


def spmv_ell(vals: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    """(K, n) column-major ELL SpMV: y_i = sum_k vals[k,i] * x[cols[k,i]]."""
    return jnp.einsum("kr,kr->r", vals, x[cols])


def k_sum(vals: jax.Array, g: jax.Array, axis: int) -> jax.Array:
    """``sum_k vals * g`` over ``axis`` as one multiply-reduce — the Pallas
    kernels' formulation.  Under jit the product fuses into the reduction
    exactly as in an interpret-mode kernel, so kernels, their oracles and
    the XLA SELL path agree bit for bit; eager execution, or an unrolled
    chain of adds, rounds differently on the CPU."""
    return jnp.sum(vals * g, axis=axis)


@partial(jax.jit, static_argnames=("n",))
def spmv_sell(vals: jax.Array, cols: jax.Array, x: jax.Array,
              n: int) -> jax.Array:
    """SELL-w SpMV.  vals/cols: (n_slices, max_k, w).  Jitted, so the
    multiply-reduce fuses as it does inside the SELL kernel (bitwise
    parity)."""
    g = x[cols]                              # (n_slices, max_k, w)
    return k_sum(vals, g, axis=1).reshape(-1)[:n]


def spmv_ell_batched(vals: jax.Array, cols: jax.Array,
                     x: jax.Array) -> jax.Array:
    """ELL SpMV over B column vectors at once.  x: (n, B) -> (n, B).

    One gather of the column indices serves all B vectors; the reduction
    over K matches ``spmv_ell`` per column (same order), keeping batched
    and single-RHS PCG arithmetic identical."""
    return jnp.einsum("kr,krb->rb", vals, x[cols])


@partial(jax.jit, static_argnames=("n",))
def spmv_sell_batched(vals: jax.Array, cols: jax.Array, x: jax.Array,
                      n: int) -> jax.Array:
    """SELL-w SpMV over B column vectors.  x: (n, B) -> (n, B)."""
    g = x[cols]                                    # (n_slices, max_k, w, B)
    return k_sum(vals[..., None], g, axis=1).reshape(-1, x.shape[1])[:n]


# ---------------------------------------------------------------------------
# Mesh-sharded SpMV: operand rows (ELL) / slices (SELL) live sharded over one
# mesh axis, the vector is replicated, and the row results are all-gathered —
# one collective per SpMV, the distributed analogue of the paper's
# embarrassingly-parallel matrix-vector kernel.
# ---------------------------------------------------------------------------

def make_sharded_spmv(spmv_format: str, n: int, mesh: Mesh, axis: str,
                      vals: jax.Array, cols: jax.Array,
                      batched: bool, spmv_backend: str = "xla",
                      interpret: bool | None = None
                      ) -> Callable[[jax.Array], jax.Array]:
    """Distributed SpMV closure over mesh-sharded packed operands.

    ``vals``/``cols`` must be sharded over ``axis`` along their row
    dimension (ELL: the minor one of ``(K, n)``; SELL: the leading slice
    one), with that dimension a multiple of the axis size; the input vector is replicated and the output is replicated
    (each device computes its row block, one tiled all-gather assembles
    the full result).  Per-row arithmetic is identical to the
    single-device ``spmv_ell``/``spmv_sell`` paths, so the distributed
    PCG reproduces their float sequences bitwise.

    ``spmv_backend="pallas"`` (SELL only) computes each device's row block
    with the per-device block kernel (``kernels.sell_spmv_block``) instead
    of the jnp gather — the collective structure (one tiled all-gather) is
    unchanged, and the kernel's interpret-mode arithmetic matches the jnp
    path bitwise.
    """
    if spmv_backend not in ("xla", "pallas"):
        raise ValueError(f"unknown spmv backend {spmv_backend!r}; expected "
                         "'xla' or 'pallas'")
    if spmv_backend == "pallas" and spmv_format != "sell":
        raise ValueError("spmv_backend='pallas' requires spmv_format='sell' "
                         "(the kernel family is SELL-w)")
    if spmv_format == "ell":
        row_eq = "kr,krb->rb" if batched else "kr,kr->r"

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P(None, axis), P(None, axis), P()),
                 out_specs=P(), check_vma=False)
        def ell_block(v, c, x):
            y_loc = jnp.einsum(row_eq, v, x[c])
            return jax.lax.all_gather(y_loc, axis, tiled=True)

        return lambda x: ell_block(vals, cols, x)

    if spmv_format == "sell":
        use_kernel = spmv_backend == "pallas"
        if use_kernel:
            # deferred: repro.kernels.__init__ imports repro.core
            from repro.kernels.sell_spmv import sell_spmv_block

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P(axis, None, None), P(axis, None, None), P()),
                 out_specs=P(), check_vma=False)
        def sell_block(v, c, x):
            if use_kernel:
                y_loc = sell_spmv_block(v, c, x, interpret=interpret)
            else:
                v_b = v[..., None] if batched else v
                y_loc = k_sum(v_b, x[c], axis=1)  # (s, w) or (s, w, B)
                y_loc = y_loc.reshape((-1,) + y_loc.shape[2:])
            return jax.lax.all_gather(y_loc, axis, tiled=True)

        # jitted so the multiply-reduce fuses as in the kernel and in
        # ``spmv_sell`` even when called eagerly (bitwise parity)
        sell_fn = jax.jit(sell_block)
        return lambda x: sell_fn(vals, cols, x)[:n]

    raise ValueError(f"unknown spmv format {spmv_format!r}")


def pcg_iteration(spmv: Callable[[jax.Array], jax.Array],
                  precond: Callable[[jax.Array], jax.Array]):
    """One PCG step with the PRECONDITIONED pairings, as a pure function.

    The carried state is ``(x, r, p, rz)`` with ``rz = (r, z)`` from the
    previous step — exactly the body of ``_pcg_device``:

        alpha = (r, z) / (p, A p)        beta = (r2, z2) / (r, z)

    (NOT the unpreconditioned ``(r, r)`` pairings — using those lowers a
    plain-CG kernel whose roofline misses both triangular sweeps' traffic.)
    Used by ``core.partition.lower_solver_step`` for mesh dry-runs; tested
    against ``pcg`` iterates in tests/test_multidevice.py.
    """
    def step(x, r, p, rz):
        ap = spmv(p)
        alpha = rz / jnp.vdot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        z = precond(r)
        rz_new = jnp.vdot(r, z)
        beta = rz_new / rz
        p = z + beta * p
        return x, r, p, rz_new
    return step


@dataclasses.dataclass
class PCGResult:
    x: np.ndarray
    iterations: int
    relres: float
    converged: bool
    history: np.ndarray   # relative residual norm per iteration (padded NaN)
    # how the solve terminated — one of STATUS_NAMES[1:] (see the taxonomy
    # at the top of this module); ``converged`` stays as the legacy bool
    status: str = "CONVERGED"


@_scoped_pcg
def _pcg_device(spmv: Callable[[jax.Array], jax.Array],
                precond: Callable[[jax.Array], jax.Array],
                b: jax.Array,
                rtol: float = 1e-7,
                maxiter: int = 10_000,
                record_history: bool = False,
                divergence_factor: float | None = DIVERGENCE_FACTOR,
                stagnation_window: int | None = STAGNATION_WINDOW):
    """Device core of ``pcg``: pure jax in / jax out, jittable.

    ``rtol``/``maxiter``/``record_history`` and the monitoring knobs are
    Python values (static under jit).  Returns ``(x, iterations, relres,
    status, history)`` as jax arrays; ``SolverPlan`` wraps this in a cached
    ``jax.jit`` so warm solves skip retracing entirely.

    Health monitoring runs inside the loop: a non-SPD pairing
    (``p^T A p <= 0``) or a non-finite residual/pairing stops the loop with
    ``BREAKDOWN`` *before* the poisoned update replaces the last finite
    iterate; ``relres`` growing past ``divergence_factor * best`` stops
    with ``DIVERGED``; ``stagnation_window`` iterations without a new best
    stop with ``STAGNATED``.  All guards are selects, so the healthy-path
    float sequence is bitwise-identical to the unguarded loop.
    """
    if divergence_factor is None:
        divergence_factor = float("inf")
    if stagnation_window is None:
        stagnation_window = maxiter + 1
    b = jnp.asarray(b)
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)

    x0 = jnp.zeros_like(b)
    r0 = b
    z0 = precond(r0)
    p0 = z0
    rz0 = jnp.vdot(r0, z0)
    # carry ||r|| in the loop state: one full-vector reduction per step
    # (cond reads the carried value instead of recomputing the norm)
    rnorm0 = jnp.linalg.norm(r0)
    relres0 = rnorm0 / bnorm
    # a non-finite initial state (NaN/Inf in b, or a preconditioner that
    # produced one) is a breakdown before the first iteration
    init_ok = jnp.isfinite(relres0) & jnp.isfinite(rz0)
    status0 = jnp.where(init_ok, RUNNING, BREAKDOWN).astype(jnp.int32)
    hist0 = (jnp.full((maxiter + 1,), jnp.nan, dtype=b.dtype)
             if record_history else jnp.zeros((0,), dtype=b.dtype))
    if record_history:
        hist0 = hist0.at[0].set(relres0)

    def cond(state):
        _, _, _, _, _, rnorm, it, status, _, _, _ = state
        return ((rnorm / bnorm >= rtol) & (it < maxiter)
                & (status == RUNNING))

    def body(state):
        x, _, r, p, rz, rnorm, it, status, best, since_best, hist = state
        ap = spmv(p)
        pap = jnp.vdot(p, ap)
        alpha = rz / pap
        x2 = x + alpha * p
        r2 = r - alpha * ap
        z = precond(r2)
        rz2 = jnp.vdot(r2, z)
        beta = rz2 / rz
        p2 = z + beta * p
        rnorm2 = jnp.linalg.norm(r2)
        relres2 = rnorm2 / bnorm
        # pap > 0 is False for NaN pap too; a step that still produced a
        # non-finite residual/pairing (overflow) is equally a breakdown.
        # Broken steps are DISCARDED: a broken step makes cond False
        # immediately (status leaves RUNNING) and the loop outputs read
        # the carried scalars, never the poisoned r or p — so no vector
        # select runs inside the loop at all.  The previous iterate rides
        # along as x_prev (pure buffer rotation, no copy) and the single
        # rollback select happens once, after the loop.
        ok = (pap > 0) & jnp.isfinite(rnorm2) & jnp.isfinite(rz2)
        rz = jnp.where(ok, rz2, rz)
        rnorm = jnp.where(ok, rnorm2, rnorm)
        it = jnp.where(ok, it + 1, it)
        improved = relres2 < best
        diverged = ok & (relres2 > divergence_factor * best)
        since_best = jnp.where(ok, jnp.where(improved, 0, since_best + 1),
                               since_best)
        stagnated = ok & (since_best >= stagnation_window)
        best = jnp.where(ok, jnp.minimum(best, relres2), best)
        status = jnp.where(~ok, BREAKDOWN,
                           jnp.where(diverged, DIVERGED,
                                     jnp.where(stagnated, STAGNATED,
                                               status))).astype(jnp.int32)
        if record_history:
            hist = jnp.where(ok, hist.at[it].set(relres2), hist)
        return (x2, x, r2, p2, rz, rnorm, it, status, best, since_best,
                hist)

    state = (x0, x0, r0, p0, rz0, rnorm0, jnp.asarray(0), status0, relres0,
             jnp.asarray(0, dtype=jnp.int32), hist0)
    (x, x_prev, _, _, _, rnorm, it, status, _, _, hist) = jax.lax.while_loop(
        cond, body, state)
    # a BREAKDOWN exit left the poisoned update in x; report the last
    # finite iterate instead (healthy exits select x — identical bits)
    x = jnp.where(status == BREAKDOWN, x_prev, x)
    relres = rnorm / bnorm
    status = jnp.where(status == RUNNING,
                       jnp.where(relres < rtol, CONVERGED, MAXITER),
                       status).astype(jnp.int32)
    return x, it, relres, status, hist


def pcg(spmv: Callable[[jax.Array], jax.Array],
        precond: Callable[[jax.Array], jax.Array],
        b: jax.Array,
        rtol: float = 1e-7,
        maxiter: int = 10_000,
        record_history: bool = False,
        divergence_factor: float | None = DIVERGENCE_FACTOR,
        stagnation_window: int | None = STAGNATION_WINDOW) -> PCGResult:
    """Standard PCG; runs fully on device, one while_loop iteration per CG step.

    Terminates with a definite ``result.status`` on every input: healthy
    systems report ``CONVERGED``/``MAXITER`` exactly as before (bitwise —
    the monitoring is select-based), a zero RHS converges immediately with
    ``x = 0``, and NaN/Inf inputs, non-SPD pairings, divergence, and
    stagnation stop early instead of silently iterating on garbage (the
    reported ``x`` is the last finite iterate).
    """
    x, it, relres, status, hist = _pcg_device(
        spmv, precond, b, rtol=rtol, maxiter=maxiter,
        record_history=record_history, divergence_factor=divergence_factor,
        stagnation_window=stagnation_window)
    relres = float(relres)
    return PCGResult(x=np.asarray(x), iterations=int(it), relres=relres,
                     converged=relres < rtol, history=np.asarray(hist),
                     status=STATUS_NAMES[int(status)])


# ---------------------------------------------------------------------------
# Batched multi-RHS PCG (one while_loop for B right-hand sides).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchedPCGResult:
    x: np.ndarray           # (n, B) solutions
    iterations: np.ndarray  # (B,) per-RHS iteration counts
    relres: np.ndarray      # (B,) final relative residual norms
    converged: np.ndarray   # (B,) bool
    n_steps: int            # while_loop trips = max(iterations)
    # (maxiter+1, B) per-column relative residual norms (NaN once a column
    # has converged — matching the single-RHS ``pcg`` histories column for
    # column); empty when record_history=False
    history: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0, 0)))
    # (B,) per-column termination codes (indices into STATUS_NAMES)
    status: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), dtype=np.int32))

    @property
    def status_names(self) -> list[str]:
        """Per-column status names (``STATUS_NAMES[code]`` per column)."""
        return [STATUS_NAMES[int(s)] for s in self.status]


@_scoped_pcg
def _pcg_batched_device(spmv: Callable[[jax.Array], jax.Array],
                        precond: Callable[[jax.Array], jax.Array],
                        b: jax.Array,
                        rtol: float = 1e-7,
                        maxiter: int = 10_000,
                        record_history: bool = False,
                        divergence_factor: float | None = DIVERGENCE_FACTOR,
                        stagnation_window: int | None = STAGNATION_WINDOW):
    """Device core of ``pcg_batched``; returns jax arrays, jittable.

    Per-column health monitoring mirrors ``_pcg_device``: a column whose
    pairing goes non-positive is frozen BEFORE the division poisons it
    (``alpha = 0``, exactly how converged columns freeze), a column whose
    update still produced a non-finite residual rolls back to its last
    finite iterate, and divergence/stagnation trip per column.  A broken
    column deactivates with an explicit terminal status — never the old
    silent NaN-comparison fallout — while its healthy slab neighbours'
    float sequences stay bitwise-untouched (all guards are selects).
    """
    if divergence_factor is None:
        divergence_factor = float("inf")
    if stagnation_window is None:
        stagnation_window = maxiter + 1
    b = jnp.asarray(b)
    if b.ndim == 1:
        raise ValueError(
            f"pcg_batched expects b of shape (n, B), got a 1-D vector of "
            f"shape {b.shape}; a single RHS must be passed as a one-column "
            f"slab b[:, None] (B = 1), or use pcg")
    if b.ndim != 2:
        raise ValueError(f"pcg_batched expects b of shape (n, B), got "
                         f"{b.shape}")
    nb = b.shape[1]
    bnorm = jnp.linalg.norm(b, axis=0)
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)

    def relres_of(r):
        return jnp.linalg.norm(r, axis=0) / bnorm

    x0 = jnp.zeros_like(b)
    r0 = b
    z0 = precond(r0)
    p0 = z0
    rz0 = jnp.einsum("nb,nb->b", r0, z0)
    relres0 = relres_of(r0)
    # non-finite init (NaN/Inf b, poisoned factor): BREAKDOWN before the
    # first step.  NaN relres already failed `>= rtol`; the explicit
    # finiteness mask also catches Inf relres (which would pass) and pins
    # the deactivation to a status instead of a comparison accident.
    finite0 = jnp.isfinite(relres0) & jnp.isfinite(rz0)
    active0 = (relres0 >= rtol) & finite0
    status0 = jnp.where(finite0,
                        jnp.where(relres0 < rtol, CONVERGED, RUNNING),
                        BREAKDOWN).astype(jnp.int32)
    iters0 = jnp.zeros(nb, dtype=jnp.int32)
    since0 = jnp.zeros(nb, dtype=jnp.int32)
    hist0 = (jnp.full((maxiter + 1, nb), jnp.nan, dtype=b.dtype)
             if record_history else jnp.zeros((0, nb), dtype=b.dtype))
    if record_history:
        hist0 = hist0.at[0].set(relres0)

    def cond(state):
        _, _, _, _, active, _, step, _, _, _, _ = state
        return jnp.any(active) & (step < maxiter)

    def body(state):
        x, r, p, rz, active, iters, step, status, best, since, hist = state
        ap = spmv(p)
        pap = jnp.einsum("nb,nb->b", p, ap)
        # non-positive / non-finite curvature freezes the column BEFORE
        # the rz/pap division (alpha = 0, exactly how a converged column
        # freezes); for healthy columns `upd` equals `active` bitwise
        upd = active & (pap > 0)
        alpha = jnp.where(upd, rz / pap, 0.0)
        x2 = x + alpha[None, :] * p
        r2 = r - alpha[None, :] * ap
        z = precond(r2)
        rz2 = jnp.einsum("nb,nb->b", r2, z)
        beta = jnp.where(upd, rz2 / rz, 0.0)
        p2 = jnp.where(upd[None, :], z + beta[None, :] * p, p)
        relres2 = relres_of(r2)
        # a column whose update still produced a non-finite residual /
        # pairing (overflow) rolls back to its last finite iterate
        ok = upd & jnp.isfinite(relres2) & jnp.isfinite(rz2)
        broke = active & ~ok
        x = jnp.where(ok[None, :], x2, x)
        r = jnp.where(ok[None, :], r2, r)
        p = jnp.where(ok[None, :], p2, p)
        rz = jnp.where(ok, rz2, rz)
        iters = iters + ok.astype(jnp.int32)
        if record_history:
            # a column records its residual at row == its own iteration
            # count while healthy-active; frozen columns keep their NaN
            # padding, matching the single-RHS history shape one for one
            # (the lane index dtype must match `iters` — mixed i64/i32
            # scatter indices are a FutureWarning on the way to an error)
            lanes = jnp.arange(nb, dtype=iters.dtype)
            hist = hist.at[iters, lanes].set(
                jnp.where(ok, relres2, hist[iters, lanes]))
        improved = relres2 < best
        diverged = ok & (relres2 > divergence_factor * best)
        since = jnp.where(ok, jnp.where(improved, 0, since + 1), since)
        stagnated = ok & (since >= stagnation_window) & ~diverged
        best = jnp.where(ok, jnp.minimum(best, relres2), best)
        status = jnp.where(broke, BREAKDOWN,
                           jnp.where(diverged, DIVERGED,
                                     jnp.where(stagnated, STAGNATED,
                                               status))).astype(jnp.int32)
        active = ok & (relres2 >= rtol) & ~diverged & ~stagnated
        return (x, r, p, rz, active, iters, step + 1, status, best, since,
                hist)

    state = (x0, r0, p0, rz0, active0, iters0, jnp.asarray(0), status0,
             relres0, since0, hist0)
    (x, r, _, _, _, iters, step, status, _, _, hist) = jax.lax.while_loop(
        cond, body, state)
    relres = relres_of(r)
    # columns still RUNNING terminated healthily: converged or out of
    # budget (terminal codes set inside the loop are kept)
    status = jnp.where(status == RUNNING,
                       jnp.where(relres < rtol, CONVERGED, MAXITER),
                       status).astype(jnp.int32)
    return x, iters, relres, step, status, hist


def pcg_batched(spmv: Callable[[jax.Array], jax.Array],
                precond: Callable[[jax.Array], jax.Array],
                b: jax.Array,
                rtol: float = 1e-7,
                maxiter: int = 10_000,
                record_history: bool = False,
                divergence_factor: float | None = DIVERGENCE_FACTOR,
                stagnation_window: int | None = STAGNATION_WINDOW
                ) -> BatchedPCGResult:
    """PCG over B right-hand sides in ONE device while_loop.

    ``spmv`` and ``precond`` map (n, B) -> (n, B) column-wise (e.g.
    ``spmv_ell_batched`` and ``HBMCPreconditioner.apply_batched``).

    Per-RHS convergence masking: a column whose relative residual drops
    below ``rtol`` gets ``alpha = beta = 0`` from then on, freezing its
    ``x``/``r``/``p``/``rz`` exactly (0 * p adds exact zeros), while the
    remaining columns keep iterating.  Each column therefore performs the
    same arithmetic sequence as a single-RHS ``pcg`` on that column up to
    XLA's reduction-order rounding, and the per-RHS iteration counts match
    the single-RHS counts one for one.

    ``record_history=True`` additionally returns per-column residual
    histories ((maxiter+1, B), NaN-padded): column j's history is frozen
    the moment it converges, matching the single-RHS ``pcg`` history of
    that column in shape and NaN pattern exactly and in values up to
    reduction-order rounding (the batched dots reduce via
    ``einsum('nb,nb->b')`` rather than ``vdot``).

    The loop runs until every column has converged (or ``maxiter``): total
    wall-clock is max(iterations) rounds, with the S sequential trisolve
    rounds amortized over all live columns — the multi-RHS workload the
    round-major kernel was built for.

    Per-column termination is reported in ``result.status`` (codes into
    ``STATUS_NAMES``; names via ``result.status_names``): a column whose
    residual goes NaN — or that hits non-positive curvature, divergence,
    or stagnation — deactivates with an explicit ``BREAKDOWN`` /
    ``DIVERGED`` / ``STAGNATED`` code instead of silently falling out of
    the active mask mid-garbage, and its healthy neighbours are bitwise
    unaffected.
    """
    x, iters, relres, step, status, hist = _pcg_batched_device(
        spmv, precond, b, rtol=rtol, maxiter=maxiter,
        record_history=record_history,
        divergence_factor=divergence_factor,
        stagnation_window=stagnation_window)
    relres = np.asarray(relres)
    return BatchedPCGResult(x=np.asarray(x), iterations=np.asarray(iters),
                            relres=relres, converged=relres < rtol,
                            n_steps=int(step), history=np.asarray(hist),
                            status=np.asarray(status))


# ---------------------------------------------------------------------------
# Slab PCG: quantum-stepped batched PCG with slot-level entry/retirement.
#
# The serving layer (repro.serve) keeps B independent PCG solves resident in
# one (n, B) slab and advances them a bounded number of while_loop trips per
# dispatch.  Between dispatches the host retires converged columns and packs
# fresh right-hand sides into the freed slots; a ``fresh`` mask tells the
# next dispatch which columns to (re)initialize.  Continuing columns are
# carried through ``jnp.where`` untouched, so quantum boundaries do not
# perturb their float sequences: a column sees the exact same arithmetic it
# would in one uninterrupted ``_pcg_batched_device`` run at the same width.
# ---------------------------------------------------------------------------


class SlabState(NamedTuple):
    """Device-side carry of a resident PCG slab ((m, B) state vectors).

    ``fresh[j]`` marks column j for (re)initialization at the next dispatch:
    its ``r`` must already hold the embedded RHS (or zeros for an empty
    slot — zero residual initializes to ``relres = 0 < rtol``, i.e. inert).
    All other per-column entries of a fresh column are ignored and
    overwritten at dispatch entry.

    ``status[j]`` carries the per-column termination code (index into
    ``STATUS_NAMES``): ``RUNNING`` while iterating, resolved at the
    dispatch where the column deactivates.  An inactive column's status is
    always definite — the serving layer retires on it (and quarantines
    ``BREAKDOWN``/``DIVERGED``/``STAGNATED`` columns immediately instead
    of letting them hold a slot for their full ``maxiter`` budget).
    ``best``/``since_best`` are the divergence/stagnation monitor carry
    (best relres so far, iterations since it improved) — slab-resident so
    the monitoring is seamless across dispatch boundaries.
    """
    x: jax.Array        # (m, B) iterates
    r: jax.Array        # (m, B) residuals (RHS for fresh columns)
    p: jax.Array        # (m, B) search directions
    rz: jax.Array       # (B,)   carried (r, z) inner products
    bnorm: jax.Array    # (B,)   ||b|| per column (1.0 for zero columns)
    active: jax.Array   # (B,)   still iterating
    iters: jax.Array    # (B,)   per-column iteration counts (int32)
    relres: jax.Array   # (B,)   last relative residual norms
    fresh: jax.Array    # (B,)   initialize at next dispatch entry
    status: jax.Array   # (B,)   per-column termination codes (int32)
    best: jax.Array     # (B,)   best relres so far (monitor carry)
    since_best: jax.Array  # (B,) iterations since best improved (int32)


@_scoped_pcg
def _pcg_slab_device(spmv: Callable[[jax.Array], jax.Array],
                     precond: Callable[[jax.Array], jax.Array],
                     state: SlabState,
                     rtol: float = 1e-7,
                     maxiter: int = 10_000,
                     quantum: int = 16,
                     divergence_factor: float | None = DIVERGENCE_FACTOR,
                     stagnation_window: int | None = STAGNATION_WINDOW):
    """Advance a PCG slab by at most ``quantum`` iterations; jittable.

    Entry initialization applies only to columns with ``fresh`` set (their
    ``r`` holds the embedded RHS): exactly the ``_pcg_batched_device`` init
    per column — including its health screen (a non-finite fresh RHS is
    ``BREAKDOWN`` on entry, a zero RHS is ``CONVERGED``/inert).  The loop
    body performs the identical arithmetic sequence as
    ``_pcg_batched_device`` — converged/inert/broken columns are frozen by
    ``alpha = beta = 0``, breakdown/divergence/stagnation deactivate a
    column with its terminal status — with one addition: a per-column
    ``iters < maxiter`` cutoff (columns enter the slab at different times,
    so the global step counter cannot bound them).  Returns
    ``(SlabState, steps)`` with ``fresh`` cleared, every inactive column's
    ``status`` definite, and ``steps`` the number of while_loop trips
    taken this dispatch.
    """
    if divergence_factor is None:
        divergence_factor = float("inf")
    if stagnation_window is None:
        stagnation_window = maxiter + 1
    (x, r, p, rz, bnorm, active, iters, relres, fresh, status, best,
     since_best) = state

    # per-column init for fresh columns; continuing columns pass through
    # every `where` bitwise-untouched (the precond/einsum results for them
    # are computed and discarded — column-wise ops, no cross-column flow)
    z = precond(r)
    rz0 = jnp.einsum("nb,nb->b", r, z)
    nrm0 = jnp.linalg.norm(r, axis=0)
    bnorm0 = jnp.where(nrm0 == 0, 1.0, nrm0)
    relres0 = nrm0 / bnorm0
    finite0 = jnp.isfinite(relres0) & jnp.isfinite(rz0)
    x = jnp.where(fresh[None, :], jnp.zeros_like(x), x)
    p = jnp.where(fresh[None, :], z, p)
    rz = jnp.where(fresh, rz0, rz)
    bnorm = jnp.where(fresh, bnorm0, bnorm)
    iters = jnp.where(fresh, 0, iters)
    relres = jnp.where(fresh, relres0, relres)
    active = jnp.where(fresh, (relres0 >= rtol) & finite0, active)
    status = jnp.where(fresh,
                       jnp.where(finite0,
                                 jnp.where(relres0 < rtol, CONVERGED,
                                           RUNNING),
                                 BREAKDOWN),
                       status).astype(jnp.int32)
    best = jnp.where(fresh, relres0, best)
    since_best = jnp.where(fresh, 0, since_best).astype(jnp.int32)

    def relres_of(rr):
        return jnp.linalg.norm(rr, axis=0) / bnorm

    def cond(carry):
        _, _, _, _, active_, _, _, _, _, _, step = carry
        return jnp.any(active_) & (step < quantum)

    def body(carry):
        x, r, p, rz, active, iters, relres, status, best, since, step = \
            carry
        ap = spmv(p)
        pap = jnp.einsum("nb,nb->b", p, ap)
        # same per-column guards as _pcg_batched_device: freeze before a
        # bad division, roll back a non-finite update, monitor
        # divergence/stagnation — healthy columns select identical floats
        upd = active & (pap > 0)
        alpha = jnp.where(upd, rz / pap, 0.0)
        x2 = x + alpha[None, :] * p
        r2 = r - alpha[None, :] * ap
        z = precond(r2)
        rz2 = jnp.einsum("nb,nb->b", r2, z)
        beta = jnp.where(upd, rz2 / rz, 0.0)
        p2 = jnp.where(upd[None, :], z + beta[None, :] * p, p)
        relres2 = relres_of(r2)
        ok = upd & jnp.isfinite(relres2) & jnp.isfinite(rz2)
        broke = active & ~ok
        x = jnp.where(ok[None, :], x2, x)
        r = jnp.where(ok[None, :], r2, r)
        p = jnp.where(ok[None, :], p2, p)
        rz = jnp.where(ok, rz2, rz)
        iters = iters + ok.astype(jnp.int32)
        relres = jnp.where(ok, relres2, relres)
        improved = relres2 < best
        diverged = ok & (relres2 > divergence_factor * best)
        since = jnp.where(ok, jnp.where(improved, 0, since + 1), since)
        stagnated = ok & (since >= stagnation_window) & ~diverged
        best = jnp.where(ok, jnp.minimum(best, relres2), best)
        status = jnp.where(broke, BREAKDOWN,
                           jnp.where(diverged, DIVERGED,
                                     jnp.where(stagnated, STAGNATED,
                                               status))).astype(jnp.int32)
        active = (ok & (relres2 >= rtol) & (iters < maxiter)
                  & ~diverged & ~stagnated)
        return (x, r, p, rz, active, iters, relres, status, best, since,
                step + 1)

    carry = (x, r, p, rz, active, iters, relres, status, best, since_best,
             jnp.asarray(0))
    (x, r, p, rz, active, iters, relres, status, best, since_best,
     step) = jax.lax.while_loop(cond, body, carry)
    # every inactive column leaves the dispatch with a definite status:
    # terminal codes set in the loop are kept; an inactive RUNNING column
    # terminated healthily (converged, or out of per-column budget)
    status = jnp.where(active | (status != RUNNING), status,
                       jnp.where(relres < rtol, CONVERGED,
                                 MAXITER)).astype(jnp.int32)
    out = SlabState(x=x, r=r, p=p, rz=rz, bnorm=bnorm, active=active,
                    iters=iters, relres=relres,
                    fresh=jnp.zeros_like(fresh), status=status, best=best,
                    since_best=since_best)
    return out, step
