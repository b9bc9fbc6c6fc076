"""Reusable solver plan: factor once, solve many (the setup pipeline).

``SolverPlan`` owns everything ``solve_iccg`` used to rebuild from scratch
on every call:

    ordering            MC / BMC / HBMC permutation + padded system
    rounds              execution-ordered independent row sets
    IC(0) structure     pattern-only analysis (``ic0_structure``)
    IC(0) factor        round-parallel numeric phase (``ic0_refactor``)
    packed tables       vectorized ``pack_factor`` + fused round-major form
    SpMV operand        ELL / SELL packing of the (round-major) matrix
    jitted PCG          one cached ``jax.jit`` per (batched, rtol, maxiter,
                        record_history) signature

``plan.solve(b)`` / ``plan.solve_batched(B)`` perform ZERO host-side setup:
the only per-solve host work is embedding ``b`` into the solve layout and
extracting ``x`` back out.  ``plan.refactor(a_new)`` re-runs only the
numeric factorization + numeric repack for a matrix with the identical
sparsity pattern (the implicit time-stepping workload — see
``examples/timestepping.py``), skipping ordering, rounds, and symbolic
analysis entirely.

``solve_iccg`` / ``solve_iccg_batched`` (core/solvers.py) are thin wrappers:
build a plan, solve once.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import sell
from .coloring import (_validate_block_size, build_blocks, color_blocks,
                       multicolor_ordering, pad_system)
from .graph import adjacency_lists, level_sets, permute_system
from .hbmc import _validate_w, hbmc_from_bmc, pad_system_hbmc
from .ic0 import FactorBreakdownError, ic0_refactor, ic0_structure
from .iccg import (DIVERGENCE_FACTOR, STAGNATION_WINDOW,
                   BatchedPCGResult, PCGResult, SlabState,
                   _pcg_batched_device, _pcg_device, _pcg_slab_device,
                   make_sharded_spmv, spmv_ell, spmv_ell_batched, spmv_sell,
                   spmv_sell_batched, status_name)
from .timing import SOLVE_EMBED, SOLVE_EXTRACT, SOLVE_PCG, span
from .trisolve import (BACKENDS, LAYOUTS, DistributedRoundMajorPreconditioner,
                       HBMCPreconditioner, RoundMajorPreconditioner,
                       build_preconditioner_from_rounds,
                       auto_mesh,
                       build_round_major_preconditioner_from_rounds,
                       shard_fused_tables)


@dataclasses.dataclass
class ICCGReport:
    method: str
    result: PCGResult       # result.x is in the caller's (original) ordering
    n: int
    n_padded: int
    n_colors: int
    n_rounds: int           # sequential rounds per triangular solve
    setup_seconds: float
    solve_seconds: float
    lane_occupancy: float   # live lanes / lane slots of the solve layout
    x: np.ndarray           # solution in ORIGINAL ordering (== result.x)
    backend: str = "xla"
    layout: str = "round_major"
    spmv_backend: str = "xla"
    scheduler: str = "coloring"
    # host path of the solve (``setup_seconds`` is the embed interval
    # unless a wrapper adds its plan build to it)
    embed_seconds: float = 0.0
    extract_seconds: float = 0.0
    n_segments: int = 1     # lane-width segments of the round-major sweep
    # the sweep's work an apply, counted at plan build (see SolverPlan)
    sweep_steps: int = 0
    sweep_gather_slots: int = 0
    sweep_live_entries: int = 0


@dataclasses.dataclass
class BatchedICCGReport:
    method: str
    result: BatchedPCGResult  # result.x is (n, B) in the caller's ordering
    n: int
    n_padded: int
    n_colors: int
    n_rounds: int
    setup_seconds: float
    solve_seconds: float
    lane_occupancy: float
    x: np.ndarray           # (n, B) solutions in ORIGINAL ordering (== result.x)
    backend: str = "xla"
    layout: str = "round_major"
    spmv_backend: str = "xla"
    scheduler: str = "coloring"
    embed_seconds: float = 0.0
    extract_seconds: float = 0.0
    n_segments: int = 1
    sweep_steps: int = 0
    sweep_gather_slots: int = 0
    sweep_live_entries: int = 0


@dataclasses.dataclass
class SetupBreakdown:
    """Host-side setup wall-clock, by pipeline stage (seconds).

    The ordering stage splits further (``ordering`` is their sum plus
    the permute/pad assembly): ``block_build`` is the BMC block growth,
    ``color`` the quotient-graph coloring + permutation assembly,
    ``aggregate`` the HBMC level-1 interleaving, ``schedule`` the
    level-set sweep of ``scheduler="levelset"`` plans.  Stages a method
    or scheduler does not run stay 0.0.
    """
    ordering: float
    factor: float           # IC(0): structure analysis + numeric phase
    pack: float             # step packing + fuse + SpMV operand + transfer
    total: float
    block_build: float = 0.0
    color: float = 0.0
    aggregate: float = 0.0
    schedule: float = 0.0


@dataclasses.dataclass
class _System:
    """Ordered/padded system plus everything needed to run + undo it."""
    a_bar: sp.csr_matrix
    b_bar: np.ndarray | None
    perm: np.ndarray        # original index -> padded-ordered index
    n: int
    n_padded: int
    n_colors: int
    fwd_rounds: list
    bwd_rounds: list
    drop: np.ndarray | None
    # re-applies the SAME ordering to a new matrix (refactor path)
    apply_ordering: Callable[[sp.spmatrix], sp.csr_matrix] | None = None
    # per-stage wall clock of the ordering pipeline (SetupBreakdown keys)
    ordering_stages: dict[str, float] | None = None


# Round-schedule backends behind ``build_plan(scheduler=...)``.  Every
# scheduler fills the same fwd/bwd-rounds contract of ``_System`` (bwd is
# exactly the reversed fwd round list), so everything downstream — IC(0)
# structure, StepTables, the fused sweep, sharding — is scheduler-blind.
SCHEDULERS = ("coloring", "levelset")


def _levelset_rounds(a_bar: sp.spmatrix) -> tuple[list, list, float]:
    """Replace color rounds with dependency-level rounds on ``a_bar``.

    Level sets are the minimal-round legal schedule for the (already
    ordered/padded) pattern: on patterns where coloring degrades to many
    thin rounds, levels recover the widest legal parallelism.  Dummy
    rows are diagonal-only, land in level 0, and stay masked by the
    plan's drop mask.  Returns (fwd_rounds, bwd_rounds, seconds).
    """
    t0 = time.perf_counter()
    level, counts = level_sets(a_bar)
    fwd = sell.rounds_levelset(level, counts)
    return fwd, fwd[::-1], time.perf_counter() - t0


def _order_system(a: sp.csr_matrix, b: np.ndarray | None, method: str,
                  block_size: int, w: int,
                  scheduler: str = "coloring") -> _System:
    n = a.shape[0]
    stages: dict[str, float] = {}

    def _bmc_stages():
        # shared symmetrized adjacency: computed once, reused by both
        # stages (the block build and the quotient-graph contraction)
        t0 = time.perf_counter()
        adjacency = adjacency_lists(a)
        part = build_blocks(a, block_size, adjacency=adjacency)
        t1 = time.perf_counter()
        bmc = color_blocks(a, part, block_size, adjacency=adjacency)
        stages["block_build"] = t1 - t0
        stages["color"] = time.perf_counter() - t1
        return bmc

    if method == "mc":
        mc = multicolor_ordering(a)
        a_bar, b_bar = permute_system(a, b, mc.perm)
        sysd = _System(a_bar, b_bar, mc.perm, n, n, mc.n_colors,
                       sell.rounds_mc(mc, reverse=False),
                       sell.rounds_mc(mc, reverse=True), None,
                       lambda a2: permute_system(a2, None, mc.perm)[0])
    elif method == "bmc":
        bmc = _bmc_stages()
        a_bar, b_bar = pad_system(a, b, bmc)
        sysd = _System(a_bar, b_bar, bmc.perm, n, bmc.n_padded, bmc.n_colors,
                       sell.rounds_bmc(bmc, reverse=False),
                       sell.rounds_bmc(bmc, reverse=True), bmc.is_dummy,
                       lambda a2: pad_system(a2, None, bmc)[0])
    elif method == "hbmc":
        bmc = _bmc_stages()
        t0 = time.perf_counter()
        hb = hbmc_from_bmc(bmc, w)
        stages["aggregate"] = time.perf_counter() - t0
        a_bar, b_bar = pad_system_hbmc(a, b, hb)
        sysd = _System(a_bar, b_bar, hb.perm, n, hb.n_final, hb.n_colors,
                       sell.rounds_hbmc(hb, reverse=False),
                       sell.rounds_hbmc(hb, reverse=True), hb.is_dummy,
                       lambda a2: pad_system_hbmc(a2, None, hb)[0])
    elif method == "natural":
        sysd = _System(a, b, np.arange(n), n, n, n,
                       sell.rounds_natural(n, reverse=False),
                       sell.rounds_natural(n, reverse=True), None,
                       lambda a2: sp.csr_matrix(a2))
    else:
        raise ValueError(f"unknown method {method!r}")

    if scheduler == "levelset":
        # keep the method's ordering/padding (and so its cache-locality
        # and fill properties) but re-derive the rounds from the actual
        # dependency levels of the ordered pattern
        fwd, bwd, secs = _levelset_rounds(sysd.a_bar)
        sysd.fwd_rounds, sysd.bwd_rounds = fwd, bwd
        stages["schedule"] = secs
    elif scheduler != "coloring":
        raise ValueError(f"unknown scheduler {scheduler!r}; expected one "
                         f"of {SCHEDULERS}")
    sysd.ordering_stages = stages
    return sysd


def _pack_spmv(a_op: sp.spmatrix, spmv_format: str, w: int, dtype
               ) -> tuple[jax.Array, jax.Array, int]:
    """Pack a matrix for SpMV; returns (vals, cols, n) device operands."""
    if spmv_format == "sell":
        sm = sell.pack_sell(a_op, w)
        return (jnp.asarray(sm.vals, dtype=dtype), jnp.asarray(sm.cols),
                sm.n)
    cols_h, vals_h = sell.pack_ell(a_op)
    return (jnp.asarray(vals_h, dtype=dtype), jnp.asarray(cols_h),
            a_op.shape[0])


def _make_spmv(spmv_format: str, n: int, vals, cols, batched: bool,
               spmv_backend: str = "xla",
               interpret: bool | None = None) -> Callable:
    """SpMV closure over (possibly traced) packed operands.

    ``spmv_backend="pallas"`` (SELL only) routes through the
    ``kernels.sell_spmv`` family instead of the jnp gather/einsum path —
    bitwise-identical arithmetic in interpret mode, dense slice-tiled VMEM
    traffic when compiled on TPU.
    """
    if spmv_backend == "pallas":
        if spmv_format != "sell":
            raise ValueError("spmv_backend='pallas' requires "
                             "spmv_format='sell' (the kernel family is "
                             "SELL-w)")
        # deferred: repro.kernels.__init__ imports repro.core
        from repro.kernels.sell_spmv import sell_spmv, sell_spmv_batched
        if batched:
            return lambda x: sell_spmv_batched(vals, cols, x,
                                               interpret=interpret)[:n]
        return lambda x: sell_spmv(vals, cols, x, interpret=interpret)[:n]
    if spmv_format == "sell":
        if batched:
            return lambda x: spmv_sell_batched(vals, cols, x, n)
        return lambda x: spmv_sell(vals, cols, x, n)
    if batched:
        return lambda x: spmv_ell_batched(vals, cols, x)
    return lambda x: spmv_ell(vals, cols, x)


def _build_spmv_ops(a_op: sp.spmatrix, spmv_format: str, w: int, dtype,
                    spmv_backend: str = "xla",
                    interpret: bool | None = None
                    ) -> tuple[Callable, Callable]:
    """Pack a matrix for SpMV; returns (single-RHS, multi-RHS) closures
    sharing one set of device operands."""
    vals, cols, n = _pack_spmv(a_op, spmv_format, w, dtype)
    return (_make_spmv(spmv_format, n, vals, cols, batched=False,
                       spmv_backend=spmv_backend, interpret=interpret),
            _make_spmv(spmv_format, n, vals, cols, batched=True,
                       spmv_backend=spmv_backend, interpret=interpret))


def _build_preconditioner(l_bar, sysd: _System, dtype, backend: str,
                          interpret: bool | None, layout: str,
                          lane_multiple: int = 1):
    """Factor -> preconditioner (+ layout object for round_major)."""
    if layout == "round_major":
        return build_round_major_preconditioner_from_rounds(
            l_bar, sysd.fwd_rounds, sysd.bwd_rounds, drop_mask=sysd.drop,
            dtype=dtype, backend=backend, interpret=interpret,
            lane_multiple=lane_multiple)
    return build_preconditioner_from_rounds(
        l_bar, sysd.fwd_rounds, sysd.bwd_rounds, drop_mask=sysd.drop,
        dtype=dtype, backend=backend, interpret=interpret), None


# Manteuffel-style shift escalation (on_breakdown="escalate"): retry the
# numeric sweep with shift + extra, doubling `extra` from _ESCALATION_START,
# until the factor is clean (zero clamped pivots, all-finite data) or the
# attempt budget runs out.
_ESCALATION_START = 1e-3
_MAX_ESCALATIONS = 16
ON_BREAKDOWN = ("clamp", "raise", "escalate")


def _live_entries(l_bar: sp.csr_matrix, drop) -> int:
    """The factor's off-diagonal entries between kept rows, once in each
    sweep half: the gather slots that point at a real position."""
    low = sp.tril(l_bar, k=-1, format="coo")
    if drop is None:
        return 2 * low.nnz
    return 2 * int(np.count_nonzero(~(drop[low.row] | drop[low.col])))


def _occupancy_from_rounds(rounds, drop) -> float:
    if drop is not None:
        rounds = [r[~drop[r]] for r in rounds]
        rounds = [r for r in rounds if len(r)]
    live = np.array([len(r) for r in rounds], dtype=np.float64)
    rmax = live.max(initial=1.0)
    return float(np.mean(live / rmax)) if len(live) else 1.0


class SolverPlan:
    """Factor-once / solve-many ICCG plan (see module docstring).

    Build with ``build_plan(a, ...)`` (or the constructor directly).  The
    plan caches the ordering, rounds, IC(0) structure, fused round-major
    tables, packed SpMV operand and jitted PCG; ``solve``/``solve_batched``
    reuse all of it, ``refactor`` renews only the numeric parts.

    Counters of the sweep's work an apply, fixed at build: ``sweep_steps``
    (sequential steps, ``2 * n_rounds``), ``sweep_gather_slots`` (values
    gathered, rounds x K x lanes over every segment and half) and
    ``sweep_live_entries`` (the slots that point at a real position: the
    factor's off-diagonal entries, once per half).

    ``setup_count`` counts host-side setup passes (initial build and every
    ``refactor``); it must NOT change across ``solve`` calls — asserted by
    tests/test_setup_plan.py.
    """

    def __init__(self, a: sp.spmatrix, method: str = "hbmc",
                 block_size: int = 32, w: int = 8, shift: float = 0.0,
                 spmv_format: str = "ell", dtype=jnp.float64,
                 backend: str = "xla", interpret: bool | None = None,
                 layout: str = "round_major", mesh: Mesh | None = None,
                 mesh_axis: str = "data", lane_multiple: int = 1,
                 spmv_backend: str = "xla", on_breakdown: str = "clamp",
                 validate: str = "off", scheduler: str = "coloring"):
        # deferred: repro.analysis is jax-free but imports nothing from
        # core.plan, so this only guards against future cycles
        from repro.analysis.schedule import VALIDATE_MODES
        if validate not in VALIDATE_MODES:
            raise ValueError(f"unknown validate mode {validate!r}; "
                             f"expected one of {VALIDATE_MODES}")
        if scheduler not in SCHEDULERS:
            raise ValueError(f"unknown scheduler {scheduler!r}; expected "
                             f"one of {SCHEDULERS}")
        # fail fast with the argument's name, before any ordering work:
        # block_size=0 / w=0 used to flow through and corrupt the plan
        block_size = _validate_block_size(block_size, "build_plan")
        w = _validate_w(w, "build_plan")
        if on_breakdown not in ON_BREAKDOWN:
            raise ValueError(f"unknown on_breakdown {on_breakdown!r}; "
                             f"expected one of {ON_BREAKDOWN}")
        if layout not in LAYOUTS:
            raise ValueError(f"unknown layout {layout!r}; expected one of "
                             f"{LAYOUTS}")
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected one of "
                             f"{BACKENDS}")
        if spmv_backend not in BACKENDS:
            raise ValueError(f"unknown spmv backend {spmv_backend!r}; "
                             f"expected one of {BACKENDS}")
        if spmv_backend == "pallas" and spmv_format != "sell":
            raise ValueError("spmv_backend='pallas' requires "
                             "spmv_format='sell' (the kernel family is "
                             "SELL-w)")
        if mesh is not None:
            mesh = auto_mesh(mesh)
            if layout != "round_major":
                raise ValueError("mesh= requires layout='round_major' (the "
                                 "sharded apply is the fused round-major "
                                 "sweep)")
            if backend != "xla":
                raise ValueError("mesh= requires backend='xla' (the Pallas "
                                 "kernel is single-device; shard with the "
                                 "XLA sweep)")
            if mesh_axis not in mesh.axis_names:
                raise ValueError(f"mesh has no axis {mesh_axis!r}; axes are "
                                 f"{mesh.axis_names}")
            # lane axis must shard evenly: fold the axis size into the lane
            # padding (a single-device plan with the same lane_multiple is
            # bitwise identical — the parity oracle of the tests)
            lane_multiple = int(np.lcm(lane_multiple,
                                       mesh.shape[mesh_axis]))
        if "pallas" in (backend, spmv_backend):
            # deferred: repro.kernels.__init__ imports repro.core
            from repro.kernels.config import LANES, SUBLANES, resolve_interpret
            if not resolve_interpret(interpret):
                if jnp.dtype(dtype).itemsize > 4:
                    raise ValueError(
                        f"compiled Pallas kernels have no "
                        f"{jnp.dtype(dtype).name} (Mosaic lowers 32-bit "
                        f"types only); use dtype=jnp.float32, or the 'xla' "
                        f"backends")
                if backend == "pallas":
                    # the compiled sweep tiles each round's lanes in whole
                    # (8, 128) vreg tiles
                    lane_multiple = int(np.lcm(lane_multiple,
                                               SUBLANES * LANES))
        self.method = method
        self.scheduler = scheduler
        self.block_size = block_size
        self.w = w
        self.shift = shift
        self.on_breakdown = on_breakdown
        self.validate = validate
        # factor-health record, refreshed by every _factor pass
        self.effective_shift = shift
        self.clamped_pivots = 0
        self.shift_schedule: list[tuple[float, int]] = []
        self.spmv_format = spmv_format
        self.spmv_backend = spmv_backend
        self.dtype = dtype
        self.backend = backend
        self.interpret = interpret
        self.layout = layout
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.lane_multiple = max(int(lane_multiple), 1)
        self._np_dtype = np.dtype(jnp.dtype(dtype))
        self._pcg_cache: dict[tuple, Any] = {}
        self.setup_count = 0
        self.refactor_count = 0
        # bumped only while a PCG signature is being (re)traced
        self._trace_count = 0

        a = sp.csr_matrix(a)
        a.sort_indices()
        # original pattern kept for the refactor structure check
        self._a_indptr = a.indptr.copy()
        self._a_indices = a.indices.copy()

        t0 = time.perf_counter()
        self._sysd = _order_system(a, None, method, block_size, w,
                                   scheduler=scheduler)
        t1 = time.perf_counter()
        self._structure = ic0_structure(self._sysd.a_bar,
                                        self._sysd.fwd_rounds)
        l_bar = self._factor(self._sysd.a_bar)
        t2 = time.perf_counter()
        self._build_operators(l_bar)
        if validate != "off":
            # static race proof BEFORE the plan is handed out: "cheap" is
            # the O(nnz) round-monotonicity scan, "full" additionally
            # proves the materialized trisolve tables and the IC(0) step
            # schedule (raises ScheduleError with the offending witness)
            from repro.analysis.schedule import assert_plan_valid
            assert_plan_valid(self, validate,
                              context=f"build_plan(method={method!r})")
        t3 = time.perf_counter()
        self.timings = SetupBreakdown(ordering=t1 - t0, factor=t2 - t1,
                                      pack=t3 - t2, total=t3 - t0,
                                      **(self._sysd.ordering_stages or {}))
        self.setup_count += 1
        self.lane_occupancy = (
            self._rm.lane_occupancy if self._rm is not None else
            _occupancy_from_rounds(self._sysd.fwd_rounds, self._sysd.drop))

    # -- derived properties -------------------------------------------------

    @property
    def n(self) -> int:
        return self._sysd.n

    @property
    def n_padded(self) -> int:
        return self._sysd.n_padded

    @property
    def n_colors(self) -> int:
        return self._sysd.n_colors

    @property
    def n_rounds(self) -> int:
        return self._precond.n_rounds

    @property
    def n_segments(self) -> int:
        """Lane-width segments of the round-major sweep (1 for the index
        layout, whose rounds share one width)."""
        return self._rm.n_segments if self._rm is not None else 1

    @property
    def sweep_steps(self) -> int:
        """Sequential steps of one preconditioner apply (both halves)."""
        return 2 * self.n_rounds

    def _counters(self) -> dict:
        """The layout's counters, as the reports carry them."""
        return dict(lane_occupancy=self.lane_occupancy,
                    n_segments=self.n_segments, sweep_steps=self.sweep_steps,
                    sweep_gather_slots=self.sweep_gather_slots,
                    sweep_live_entries=self.sweep_live_entries)

    # -- setup internals ----------------------------------------------------

    @property
    def _operands_as_args(self) -> bool:
        """Whether the jitted PCG takes factor/SpMV operands as (pytree)
        ARGUMENTS — then a ``refactor`` swaps device arrays of identical
        shape without any retrace.  True for every path except
        layout="index" + backend="pallas" (whose kernel preconditioner is
        not a pytree; its jit closes over the operands and is rebuilt on
        refactor)."""
        return self.layout == "round_major" or self.backend == "xla"

    def _build_operators(self, l_bar) -> None:
        """Pack the factor + SpMV operand and move them to device.

        Under a mesh, the fused tables' lane axis and the SpMV operand's
        row/slice axis are placed SHARDED (``NamedSharding``); a
        ``refactor`` re-runs this with identical shapes and shardings, so
        the jitted PCG (whose operands are traced arguments) never
        retraces.
        """
        self._precond, self._rm = _build_preconditioner(
            l_bar, self._sysd, self.dtype, self.backend, self.interpret,
            self.layout, self.lane_multiple)
        self.sweep_gather_slots = self._precond.gather_slots
        self.sweep_live_entries = _live_entries(l_bar, self._sysd.drop)
        a_op = (sell.permute_round_major(self._sysd.a_bar, self._rm)
                if self._rm is not None else self._sysd.a_bar)
        self._spmv_vals, self._spmv_cols, self._spmv_n = _pack_spmv(
            a_op, self.spmv_format, self.w, self.dtype)
        if self.mesh is not None:
            mesh, ax = self.mesh, self.mesh_axis
            self._precond = DistributedRoundMajorPreconditioner(
                tables=shard_fused_tables(self._precond.tables, mesh, ax),
                mesh=mesh, axis=ax)
            n_dev = mesh.shape[ax]
            if self.spmv_format == "sell":
                # pad the slice axis so it shards evenly (padded slices are
                # all-zero: they contribute rows beyond n, cut by the [:n])
                pad = (-self._spmv_vals.shape[0]) % n_dev
                if pad:
                    widths = ((0, pad),) + ((0, 0),) * 2
                    self._spmv_vals = jnp.pad(self._spmv_vals, widths)
                    self._spmv_cols = jnp.pad(self._spmv_cols, widths)
                sh = NamedSharding(mesh, P(ax, None, None))
            else:
                sh = NamedSharding(mesh, P(None, ax))
            self._spmv_vals = jax.device_put(self._spmv_vals, sh)
            self._spmv_cols = jax.device_put(self._spmv_cols, sh)
        if not self._operands_as_args:
            self._pcg_cache.clear()   # closed-over operands -> retrace

    def _factor(self, a_bar: sp.csr_matrix) -> sp.csr_matrix:
        """Numeric IC(0) sweep under the plan's ``on_breakdown`` policy.

        A factor is *clean* when no diagonal pivot hit the breakdown guard
        and every entry is finite.  Policies on a dirty factor:

          * ``"clamp"`` (default) — keep the eps-clamped factor, exactly
            the pre-policy behavior (bitwise; the paper's semi-definite
            experiments rely on it), but record ``clamped_pivots``.
          * ``"raise"`` — raise :class:`FactorBreakdownError` immediately.
          * ``"escalate"`` — retry with ``shift + extra`` for doubling
            ``extra`` (Manteuffel-style diagonal shifting) until clean;
            raise FactorBreakdownError if the attempt budget runs out or
            the matrix itself is non-finite (no shift repairs NaN data).

        Every attempt is appended to ``self.shift_schedule`` as
        ``(shift, clamped_pivots)``; ``self.effective_shift`` is the shift
        of the factor actually in use and ``self.clamped_pivots`` its
        clamp count.
        """
        if not np.isfinite(a_bar.data).all():
            raise FactorBreakdownError(
                "matrix values are not finite; no diagonal shift can "
                "repair a NaN/Inf operand", shift_schedule=[])
        l_bar = ic0_refactor(self._structure, a_bar, shift=self.shift)
        clamped = int(getattr(l_bar, "clamped_pivots", 0))
        schedule = [(float(self.shift), clamped)]
        self.shift_schedule = schedule
        if clamped == 0 or self.on_breakdown == "clamp":
            self.effective_shift = self.shift
            self.clamped_pivots = clamped
            return l_bar
        if self.on_breakdown == "raise":
            raise FactorBreakdownError(
                f"IC(0) breakdown: {clamped} pivot(s) clamped at shift="
                f"{self.shift} (on_breakdown='raise'); retry with a larger "
                f"shift or on_breakdown='escalate'",
                clamped_pivots=clamped, shift_schedule=schedule)
        extra = _ESCALATION_START
        for _ in range(_MAX_ESCALATIONS):
            trial = float(self.shift) + extra
            l_bar = ic0_refactor(self._structure, a_bar, shift=trial)
            clamped = int(getattr(l_bar, "clamped_pivots", 0))
            schedule.append((trial, clamped))
            if clamped == 0:
                self.effective_shift = trial
                self.clamped_pivots = 0
                return l_bar
            extra *= 2.0
        raise FactorBreakdownError(
            f"IC(0) breakdown persists after {_MAX_ESCALATIONS} shift "
            f"escalations (last shift {schedule[-1][0]}, "
            f"{schedule[-1][1]} clamped pivot(s))",
            clamped_pivots=clamped, shift_schedule=schedule)

    def refactor(self, a_new: sp.spmatrix) -> SetupBreakdown:
        """Renew the factorization for a structure-identical matrix.

        Re-runs the value-dependent pipeline — permute values,
        round-parallel IC(0) *numeric* phase over the cached structure, and
        the (vectorized, O(nnz)) repack + device transfer — while ordering,
        rounds, layout and the IC(0) symbolic analysis stay cached and the
        jitted PCG is reused without a retrace (operands are traced
        arguments).  Raises ValueError if ``a_new``'s sparsity pattern
        differs.
        """
        a_new = sp.csr_matrix(a_new)
        a_new.sort_indices()
        if (a_new.shape[0] != self.n
                or not np.array_equal(a_new.indptr, self._a_indptr)
                or not np.array_equal(a_new.indices, self._a_indices)):
            raise ValueError("refactor requires a structure-identical "
                             "matrix (same sparsity pattern); build a new "
                             "plan instead")
        t0 = time.perf_counter()
        a_bar = self._sysd.apply_ordering(a_new)
        # factor BEFORE mutating plan state: a FactorBreakdownError from the
        # on_breakdown policy leaves the old (working) operators in place
        l_bar = self._factor(a_bar)
        self._sysd.a_bar = a_bar
        t1 = time.perf_counter()
        self._build_operators(l_bar)
        t2 = time.perf_counter()
        self.setup_count += 1
        self.refactor_count += 1
        return SetupBreakdown(ordering=0.0, factor=t1 - t0, pack=t2 - t1,
                              total=t2 - t0)

    # -- solving ------------------------------------------------------------

    def _pcg_fn(self, batched: bool, rtol: float, maxiter: int,
                record_history: bool,
                divergence_factor: float | None = DIVERGENCE_FACTOR,
                stagnation_window: int | None = STAGNATION_WINDOW):
        dvf = float("inf") if divergence_factor is None \
            else float(divergence_factor)
        stw = maxiter + 1 if stagnation_window is None \
            else int(stagnation_window)
        key = (batched, float(rtol), int(maxiter), bool(record_history),
               dvf, stw)
        fn = self._pcg_cache.get(key)
        if fn is not None:
            return fn
        # rtol/maxiter/record_history are baked in as Python constants; the
        # jitted wrapper is cached so warm solves never retrace, and (where
        # _operands_as_args) the factor/SpMV operands are traced ARGUMENTS
        # so refactor never retraces either.  self._trace_count increments
        # only while tracing — tests assert refactor stays at one trace.
        core = _pcg_batched_device if batched else _pcg_device
        fmt, n_op = self.spmv_format, self._spmv_n
        backend, interpret = self.backend, self.interpret
        spmv_backend = self.spmv_backend

        if self.mesh is not None:
            mesh, ax = self.mesh, self.mesh_axis

            def run(tables, sv, sc, b):
                self._trace_count += 1
                pre = DistributedRoundMajorPreconditioner(tables=tables,
                                                          mesh=mesh, axis=ax)
                apply_ = pre.apply_batched if batched else pre
                spmv = make_sharded_spmv(fmt, n_op, mesh, ax, sv, sc,
                                         batched, spmv_backend=spmv_backend,
                                         interpret=interpret)
                return core(spmv, apply_, b, rtol=rtol, maxiter=maxiter,
                            record_history=record_history,
                            divergence_factor=dvf, stagnation_window=stw)
            fn = jax.jit(run)
        elif self.layout == "round_major":
            def run(tables, sv, sc, b):
                self._trace_count += 1
                pre = RoundMajorPreconditioner(tables=tables,
                                               backend=backend,
                                               interpret=interpret)
                apply_ = pre.apply_batched if batched else pre
                spmv = _make_spmv(fmt, n_op, sv, sc, batched,
                                  spmv_backend=spmv_backend,
                                  interpret=interpret)
                return core(spmv, apply_, b, rtol=rtol, maxiter=maxiter,
                            record_history=record_history,
                            divergence_factor=dvf, stagnation_window=stw)
            fn = jax.jit(run)
        elif backend == "xla":
            n_final = self.n_padded

            def run(fwd, bwd, sv, sc, b):
                self._trace_count += 1
                pre = HBMCPreconditioner(fwd=fwd, bwd=bwd, n_final=n_final,
                                         backend="xla", kernel=None)
                apply_ = pre.apply_batched if batched else pre
                spmv = _make_spmv(fmt, n_op, sv, sc, batched,
                                  spmv_backend=spmv_backend,
                                  interpret=interpret)
                return core(spmv, apply_, b, rtol=rtol, maxiter=maxiter,
                            record_history=record_history,
                            divergence_factor=dvf, stagnation_window=stw)
            fn = jax.jit(run)
        else:
            # index + pallas: the kernel preconditioner is not a pytree, so
            # the operands are closure constants (cache cleared on refactor)
            pre = self._precond
            apply_ = pre.apply_batched if batched else pre
            spmv = _make_spmv(fmt, n_op, self._spmv_vals, self._spmv_cols,
                              batched, spmv_backend=spmv_backend,
                              interpret=interpret)

            def run(b):
                self._trace_count += 1
                return core(spmv, apply_, b, rtol=rtol, maxiter=maxiter,
                            record_history=record_history,
                            divergence_factor=dvf, stagnation_window=stw)
            fn = jax.jit(run)
        self._pcg_cache[key] = fn
        return fn

    def _run_pcg(self, batched: bool, rtol: float, maxiter: int,
                 record_history: bool, b_dev: jax.Array,
                 divergence_factor: float | None = DIVERGENCE_FACTOR,
                 stagnation_window: int | None = STAGNATION_WINDOW):
        fn = self._pcg_fn(batched, rtol, maxiter, record_history,
                          divergence_factor, stagnation_window)
        if self.layout == "round_major":
            return fn(self._precond.tables, self._spmv_vals,
                      self._spmv_cols, b_dev)
        if self.backend == "xla":
            return fn(self._precond.fwd, self._precond.bwd,
                      self._spmv_vals, self._spmv_cols, b_dev)
        return fn(b_dev)

    def _embed(self, b_bar: np.ndarray) -> jax.Array:
        b_host = self._rm.embed(b_bar) if self._rm is not None else b_bar
        b_dev = jnp.asarray(b_host, dtype=self.dtype)
        if self.mesh is not None:   # state vectors are replicated on the mesh
            b_dev = jax.device_put(b_dev, NamedSharding(self.mesh, P()))
        return b_dev

    def _extract(self, x_dev) -> np.ndarray:
        x_bar = (self._rm.extract(np.asarray(x_dev))
                 if self._rm is not None else np.asarray(x_dev))
        return np.asarray(x_bar[self._sysd.perm])

    def _check_slab(self, b: np.ndarray, who: str) -> np.ndarray:
        """Validate a multi-RHS slab: 2-D (n, B) with the plan's dtype.

        A 1-D b gets its own error (naming the B=1 spelling) and a float
        dtype mismatch is an error rather than a silent cast — the packed
        operands are ``self.dtype``, and quietly up/down-casting b would
        produce a result that matches neither precision's solve.
        """
        b = np.asarray(b)
        if b.ndim == 1:
            raise ValueError(
                f"{who} expects b of shape ({self.n}, B), got a 1-D vector "
                f"of shape {b.shape}; pass a single RHS as the one-column "
                f"slab b[:, None] (B = 1), or use plan.solve")
        if b.ndim != 2 or b.shape[0] != self.n:
            raise ValueError(f"{who} expects b of shape "
                             f"({self.n}, B), got {b.shape}")
        if np.issubdtype(b.dtype, np.floating) and b.dtype != self._np_dtype:
            raise TypeError(
                f"{who}: b has dtype {b.dtype} but the plan's packed "
                f"operands are {self._np_dtype}; cast b explicitly "
                f"(b.astype({self._np_dtype})) to opt in")
        return np.asarray(b, dtype=self._np_dtype)

    # -- slab serving primitives (see repro.serve) --------------------------

    @property
    def slab_m(self) -> int:
        """Length of a device-side state column in the solve layout."""
        return self._rm.m if self._rm is not None else self.n_padded

    def embed_rhs(self, b: np.ndarray) -> jax.Array:
        """Embed one RHS (original ordering, shape (n,)) into a device
        column of the solve layout (shape (slab_m,)) — the host half of
        packing a slab slot."""
        b = np.asarray(b, dtype=self._np_dtype)
        if b.shape != (self.n,):
            raise ValueError(f"plan.embed_rhs expects b of shape "
                             f"({self.n},), got {b.shape}")
        b_bar = np.zeros(self.n_padded, dtype=self._np_dtype)
        b_bar[self._sysd.perm] = b
        return self._embed(b_bar)

    def extract_solution(self, x_col) -> np.ndarray:
        """Undo ``embed_rhs``: device column (slab_m,) -> x in the
        caller's original ordering (n,)."""
        return self._extract(x_col)

    def new_slab_state(self, slab_width: int) -> SlabState:
        """An all-empty resident slab: every slot fresh with a zero RHS
        (zero residual initializes inert — see ``SlabState``)."""
        if slab_width < 1:
            raise ValueError(f"slab_width must be >= 1, got {slab_width}")
        m, dt = self.slab_m, self.dtype
        zeros = jnp.zeros((m, slab_width), dtype=dt)
        state = SlabState(
            x=zeros, r=zeros, p=zeros,
            rz=jnp.zeros((slab_width,), dtype=dt),
            bnorm=jnp.ones((slab_width,), dtype=dt),
            active=jnp.zeros((slab_width,), dtype=bool),
            iters=jnp.zeros((slab_width,), dtype=jnp.int32),
            relres=jnp.zeros((slab_width,), dtype=dt),
            fresh=jnp.ones((slab_width,), dtype=bool),
            status=jnp.zeros((slab_width,), dtype=jnp.int32),
            best=jnp.zeros((slab_width,), dtype=dt),
            since_best=jnp.zeros((slab_width,), dtype=jnp.int32))
        if self.mesh is not None:   # slab state is replicated on the mesh
            sh = NamedSharding(self.mesh, P())
            state = SlabState(*(jax.device_put(v, sh) for v in state))
        return state

    def _slab_fn(self, rtol: float, maxiter: int, quantum: int,
                 divergence_factor: float | None = DIVERGENCE_FACTOR,
                 stagnation_window: int | None = STAGNATION_WINDOW):
        """Jitted quantum-step over a resident slab; cached per signature
        exactly like ``_pcg_fn`` (operands as traced args where possible,
        so ``refactor`` never retraces)."""
        dvf = float("inf") if divergence_factor is None \
            else float(divergence_factor)
        stw = maxiter + 1 if stagnation_window is None \
            else int(stagnation_window)
        key = ("slab", float(rtol), int(maxiter), int(quantum), dvf, stw)
        fn = self._pcg_cache.get(key)
        if fn is not None:
            return fn
        fmt, n_op = self.spmv_format, self._spmv_n
        backend, interpret = self.backend, self.interpret
        spmv_backend = self.spmv_backend

        if self.mesh is not None:
            mesh, ax = self.mesh, self.mesh_axis

            def run(tables, sv, sc, state):
                self._trace_count += 1
                pre = DistributedRoundMajorPreconditioner(tables=tables,
                                                          mesh=mesh, axis=ax)
                spmv = make_sharded_spmv(fmt, n_op, mesh, ax, sv, sc,
                                         True, spmv_backend=spmv_backend,
                                         interpret=interpret)
                return _pcg_slab_device(spmv, pre.apply_batched, state,
                                        rtol=rtol, maxiter=maxiter,
                                        quantum=quantum,
                                        divergence_factor=dvf,
                                        stagnation_window=stw)
            fn = jax.jit(run)
        elif self.layout == "round_major":
            def run(tables, sv, sc, state):
                self._trace_count += 1
                pre = RoundMajorPreconditioner(tables=tables,
                                               backend=backend,
                                               interpret=interpret)
                spmv = _make_spmv(fmt, n_op, sv, sc, True,
                                  spmv_backend=spmv_backend,
                                  interpret=interpret)
                return _pcg_slab_device(spmv, pre.apply_batched, state,
                                        rtol=rtol, maxiter=maxiter,
                                        quantum=quantum,
                                        divergence_factor=dvf,
                                        stagnation_window=stw)
            fn = jax.jit(run)
        elif backend == "xla":
            n_final = self.n_padded

            def run(fwd, bwd, sv, sc, state):
                self._trace_count += 1
                pre = HBMCPreconditioner(fwd=fwd, bwd=bwd, n_final=n_final,
                                         backend="xla", kernel=None)
                spmv = _make_spmv(fmt, n_op, sv, sc, True,
                                  spmv_backend=spmv_backend,
                                  interpret=interpret)
                return _pcg_slab_device(spmv, pre.apply_batched, state,
                                        rtol=rtol, maxiter=maxiter,
                                        quantum=quantum,
                                        divergence_factor=dvf,
                                        stagnation_window=stw)
            fn = jax.jit(run)
        else:
            # index + pallas: operands are closure constants (cache cleared
            # on refactor, same as _pcg_fn)
            pre = self._precond
            spmv = _make_spmv(fmt, n_op, self._spmv_vals, self._spmv_cols,
                              True, spmv_backend=spmv_backend,
                              interpret=interpret)

            def run(state):
                self._trace_count += 1
                return _pcg_slab_device(spmv, pre.apply_batched, state,
                                        rtol=rtol, maxiter=maxiter,
                                        quantum=quantum,
                                        divergence_factor=dvf,
                                        stagnation_window=stw)
            fn = jax.jit(run)
        self._pcg_cache[key] = fn
        return fn

    def run_slab(self, state: SlabState, rtol: float = 1e-7,
                 maxiter: int = 10_000,
                 quantum: int = 16,
                 divergence_factor: float | None = DIVERGENCE_FACTOR,
                 stagnation_window: int | None = STAGNATION_WINDOW
                 ) -> tuple[SlabState, jax.Array]:
        """Advance a resident slab by at most ``quantum`` PCG iterations.

        Columns flagged ``fresh`` are (re)initialized from their ``r``
        at entry; continuing columns resume bitwise where they left off
        (dispatch boundaries do not perturb their float sequences).
        Returns ``(new_state, steps_taken)``; every inactive column of the
        new state has a definite ``status``.
        """
        fn = self._slab_fn(rtol, maxiter, quantum,
                           divergence_factor, stagnation_window)
        if self.layout == "round_major":
            return fn(self._precond.tables, self._spmv_vals,
                      self._spmv_cols, state)
        if self.backend == "xla":
            return fn(self._precond.fwd, self._precond.bwd,
                      self._spmv_vals, self._spmv_cols, state)
        return fn(state)

    def solve_slab(self, b: np.ndarray, slab_width: int = 1,
                   rtol: float = 1e-7, maxiter: int = 10_000,
                   slot: int = 0) -> ICCGReport:
        """Solve one RHS through the slab path at a given resident width.

        Packs ``b`` into ``slot`` of an otherwise-empty
        width-``slab_width`` slab and runs it to convergence in a single
        dispatch.  This is the standalone oracle for serving: a column
        served through ``repro.serve.SolverService`` at slab width B in
        slot s is bitwise equal to
        ``plan.solve_slab(b, slab_width=B, slot=s)`` — slab columns are
        independent of their neighbours' contents and of dispatch
        boundaries, but (width, slot) pin the lowered reduction trees (at
        some widths XLA emits lane-position-dependent reductions; B = 2
        does on CPU).  At ``slab_width=1`` it is bitwise equal to
        ``plan.solve_batched(b[:, None])``.  Iteration counts equal the
        single-RHS ``plan.solve`` counts at EVERY width and slot; iterates
        agree with ``plan.solve`` to reduction-order rounding only (XLA
        lowers the batched ``einsum`` dots differently from ``vdot``).
        """
        t = {}
        with span(SOLVE_EMBED, t):
            b = np.asarray(b, dtype=self._np_dtype)
            if b.shape != (self.n,):
                raise ValueError(f"plan.solve_slab expects b of shape "
                                 f"({self.n},), got {b.shape}")
            if not 0 <= slot < slab_width:
                raise ValueError(f"slot {slot} out of range for "
                                 f"slab_width {slab_width}")
            state = self.new_slab_state(slab_width)
            state = state._replace(
                r=state.r.at[:, slot].set(self.embed_rhs(b)))
        with span(SOLVE_PCG, t):
            state, _ = self.run_slab(state, rtol=rtol, maxiter=maxiter,
                                     quantum=maxiter)
            x = jax.block_until_ready(state.x)
        with span(SOLVE_EXTRACT, t):
            x_out = self.extract_solution(x[:, slot])
            relres = float(state.relres[slot])
            iters = int(state.iters[slot])
            status = status_name(state.status[slot])
        res = PCGResult(x=x_out, iterations=iters, relres=relres,
                        converged=relres < rtol, history=np.zeros((0,)),
                        status=status)
        return ICCGReport(
            method=self.method, result=res, n=self.n,
            n_padded=self.n_padded, n_colors=self.n_colors,
            n_rounds=self.n_rounds, setup_seconds=t[SOLVE_EMBED],
            solve_seconds=t[SOLVE_PCG], x=x_out, backend=self.backend,
            layout=self.layout, spmv_backend=self.spmv_backend,
            scheduler=self.scheduler, embed_seconds=t[SOLVE_EMBED],
            extract_seconds=t[SOLVE_EXTRACT], **self._counters())

    def solve(self, b: np.ndarray, rtol: float = 1e-7,
              maxiter: int = 10_000,
              record_history: bool = False) -> ICCGReport:
        """Solve A x = b reusing every cached setup product.

        Per-call host work is exactly: embed ``b`` into the solve layout,
        extract ``x`` back into the caller's ordering.
        """
        t = {}
        with span(SOLVE_EMBED, t):
            b = np.asarray(b, dtype=self._np_dtype)
            if b.shape != (self.n,):
                raise ValueError(f"plan.solve expects b of shape "
                                 f"({self.n},), got {b.shape}")
            b_bar = np.zeros(self.n_padded, dtype=self._np_dtype)
            b_bar[self._sysd.perm] = b
            b_dev = self._embed(b_bar)
        with span(SOLVE_PCG, t):
            x, it, relres, status, hist = self._run_pcg(
                False, rtol, maxiter, record_history, b_dev)
            x = jax.block_until_ready(x)
        with span(SOLVE_EXTRACT, t):
            x_out = self._extract(x)
            relres, it = float(relres), int(it)
            status, hist = status_name(status), np.asarray(hist)
        res = PCGResult(x=x_out, iterations=it, relres=relres,
                        converged=relres < rtol, history=hist,
                        status=status)
        return ICCGReport(
            method=self.method, result=res, n=self.n,
            n_padded=self.n_padded, n_colors=self.n_colors,
            n_rounds=self.n_rounds, setup_seconds=t[SOLVE_EMBED],
            solve_seconds=t[SOLVE_PCG], x=x_out, backend=self.backend,
            layout=self.layout, spmv_backend=self.spmv_backend,
            scheduler=self.scheduler, embed_seconds=t[SOLVE_EMBED],
            extract_seconds=t[SOLVE_EXTRACT], **self._counters())

    def solve_batched(self, b: np.ndarray, rtol: float = 1e-7,
                      maxiter: int = 10_000,
                      record_history: bool = False) -> BatchedICCGReport:
        """Solve A x_j = b_j for all columns of ``b`` ((n, B)) in one PCG
        loop, reusing every cached setup product."""
        t = {}
        with span(SOLVE_EMBED, t):
            b = self._check_slab(b, "plan.solve_batched")
            b_bar = np.zeros((self.n_padded, b.shape[1]),
                             dtype=self._np_dtype)
            b_bar[self._sysd.perm] = b
            b_dev = self._embed(b_bar)
        with span(SOLVE_PCG, t):
            x, iters, relres, step, status, hist = self._run_pcg(
                True, rtol, maxiter, record_history, b_dev)
            x = jax.block_until_ready(x)
        with span(SOLVE_EXTRACT, t):
            x_out = self._extract(x)
            relres, iters, step = (np.asarray(relres), np.asarray(iters),
                                   int(step))
            status, hist = np.asarray(status), np.asarray(hist)
        res = BatchedPCGResult(x=x_out, iterations=iters, relres=relres,
                               converged=relres < rtol, n_steps=step,
                               history=hist, status=status)
        return BatchedICCGReport(
            method=self.method, result=res, n=self.n,
            n_padded=self.n_padded, n_colors=self.n_colors,
            n_rounds=self.n_rounds, setup_seconds=t[SOLVE_EMBED],
            solve_seconds=t[SOLVE_PCG], x=x_out, backend=self.backend,
            layout=self.layout, spmv_backend=self.spmv_backend,
            scheduler=self.scheduler, embed_seconds=t[SOLVE_EMBED],
            extract_seconds=t[SOLVE_EXTRACT], **self._counters())


def build_plan(a: sp.spmatrix, method: str = "hbmc", block_size: int = 32,
               w: int = 8, shift: float = 0.0, spmv_format: str = "ell",
               dtype=jnp.float64, backend: str = "xla",
               interpret: bool | None = None,
               layout: str = "round_major", mesh: Mesh | None = None,
               mesh_axis: str = "data",
               lane_multiple: int = 1,
               spmv_backend: str = "xla",
               on_breakdown: str = "clamp",
               validate: str = "off",
               scheduler: str = "coloring") -> SolverPlan:
    """One-time setup: ordering -> round-parallel IC(0) -> packed operators.

    Returns a ``SolverPlan`` whose ``solve`` / ``solve_batched`` /
    ``refactor`` amortize this cost over arbitrarily many solves.

    With ``mesh=`` (a ``jax.sharding.Mesh``) the plan is distributed: the
    fused round-major tables' lane axis and the ELL/SELL SpMV operand are
    sharded over ``mesh_axis`` and the preconditioner apply runs the fused
    sweep with one collective per round.  ``lane_multiple`` pads the lane
    axis (folded with the mesh axis size automatically); a single-device
    plan built with the same ``lane_multiple`` is the bitwise parity
    oracle for a distributed plan.

    ``backend`` picks the trisolve implementation; ``spmv_backend`` (with
    ``spmv_format="sell"``) independently picks the SpMV one — with both
    set to ``"pallas"`` the entire PCG iteration runs through Pallas
    kernels on one VMEM-resident round-major state.

    ``validate`` runs the static schedule race detector
    (``repro.analysis``) at setup: ``"cheap"`` is an O(nnz)
    round-monotonicity scan of the ordering's rounds, ``"full"``
    additionally proves the materialized trisolve tables and the IC(0)
    step schedule dependency-ordered, and ``"deep"`` adds the static
    kernel checks plus the dtype-flow lint of every lowering path against
    the plan's precision contract (``repro.analysis.dtype_flow``).  A
    violation raises ``repro.analysis.ScheduleError`` carrying the
    offending row pair / edge / round / eqn; ``"off"`` (default) skips
    the proof.

    ``scheduler`` picks how the ordered pattern is cut into parallel
    rounds: ``"coloring"`` (default) uses the method's color rounds,
    ``"levelset"`` re-derives the rounds from the dependency levels of
    the ordered pattern — the minimal-round legal schedule, for
    irregular patterns where coloring degrades to thin rounds.  Both
    feed the identical ``StepTables`` contract, so every backend /
    layout / mesh combination composes with either scheduler.
    """
    return SolverPlan(a, method=method, block_size=block_size, w=w,
                      shift=shift, spmv_format=spmv_format, dtype=dtype,
                      backend=backend, interpret=interpret, layout=layout,
                      mesh=mesh, mesh_axis=mesh_axis,
                      lane_multiple=lane_multiple,
                      spmv_backend=spmv_backend, on_breakdown=on_breakdown,
                      validate=validate, scheduler=scheduler)


# ---------------------------------------------------------------------------
# Operator-building shim kept for benchmarks (pre-plan API surface).
# ---------------------------------------------------------------------------

def _build_operators(sysd: _System, shift: float, spmv_format: str, w: int,
                     dtype, backend: str, interpret: bool | None,
                     layout: str, batched: bool, spmv_backend: str = "xla"):
    """IC(0) + preconditioner + SpMV in the requested layout.

    Returns ``(precond, spmv_fn, rm_layout)`` exactly as the pre-plan
    solver did; ``benchmarks/bench_trisolve.py`` uses it to time raw
    operator applies.  The factorization runs through the round-parallel
    path (``ic0_rounds`` semantics).
    """
    if layout not in LAYOUTS:
        raise ValueError(f"unknown layout {layout!r}; expected one of "
                         f"{LAYOUTS}")
    st = ic0_structure(sysd.a_bar, sysd.fwd_rounds)
    l_bar = ic0_refactor(st, sysd.a_bar, shift=shift)
    precond, rm = _build_preconditioner(l_bar, sysd, dtype, backend,
                                        interpret, layout)
    a_op = sell.permute_round_major(sysd.a_bar, rm) if rm is not None \
        else sysd.a_bar
    single, batched_fn = _build_spmv_ops(a_op, spmv_format, w, dtype,
                                         spmv_backend=spmv_backend,
                                         interpret=interpret)
    return precond, (batched_fn if batched else single), rm
