"""Vectorized forward/backward substitution over HBMC step tables (§4.3).

The solve is ``S = n_c * b_s`` sequential rounds; each round is a dense,
fully-parallel gather / fused-multiply-subtract / scale over all live lanes
(every level-1 block of the color x w lanes).  On TPU the per-round work is
pure VPU element-wise + gather; rounds are a ``lax.fori_loop`` so the HLO is
O(1) in problem size.

Two device backends, selected by ``build_preconditioner(..., backend=...)``:
  * ``"xla"``    — ``forward_solve`` / ``backward_solve``, pure jnp
    (``fori_loop`` + scatter), the production fallback and the oracle the
    Pallas kernel is validated against.
  * ``"pallas"`` — ``repro.kernels.hbmc_trisolve`` operating on the dense
    round-major repacking (``sell.to_round_major``), with explicit VMEM
    blocking; contiguous stores instead of scatters.  ``interpret``
    defaults from the runtime (compiled on TPU, interpreted elsewhere).

And two PCG-loop layouts:
  * ``HBMCPreconditioner`` (``layout="index"``) applies in permuted-matrix
    index space — the solve layout is re-gathered/scattered per apply.
  * ``RoundMajorPreconditioner`` (``layout="round_major"``, the default
    solver path) applies natively on round-major vectors, both sweeps over
    one buffer (a loop per segment and half, each segment packed at its
    own lane width); zero per-apply permutations.

All variants expose a multi-RHS path (``apply_batched``) consumed by the
batched PCG front-end (``iccg.pcg_batched``).

``DistributedRoundMajorPreconditioner`` shards the fused round-major
apply over a device mesh axis (lane axis sharded, state replicated, one
all-gather per round — paper §4.4.3 one level up); ``SolverPlan`` wires
it in via ``build_plan(..., mesh=)``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .hbmc import HBMCOrdering
from .sell import (MAX_SEGMENTS, FusedRoundMajorTables, RoundMajorLayout,
                   StepTables, fuse_round_major, pack_factor_hbmc,
                   stack_sweeps)

BACKENDS = ("xla", "pallas")
LAYOUTS = ("round_major", "index")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceTables:
    """StepTables moved to device as a pytree."""
    rows: jax.Array   # (S, R) int32
    cols: jax.Array   # (S, R, K) int32
    vals: jax.Array   # (S, R, K)
    dinv: jax.Array   # (S, R)
    n_slots: int

    def tree_flatten(self):
        return (self.rows, self.cols, self.vals, self.dinv), (self.n_slots,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n_slots=aux[0])

    @classmethod
    def from_host(cls, t: StepTables, dtype=jnp.float64) -> "DeviceTables":
        return cls(rows=jnp.asarray(t.rows), cols=jnp.asarray(t.cols),
                   vals=jnp.asarray(t.vals, dtype=dtype),
                   dinv=jnp.asarray(t.dinv, dtype=dtype), n_slots=t.n_slots)


def _substitute(tables: DeviceTables, q: jax.Array,
                x0: jax.Array | None = None) -> jax.Array:
    """Run all rounds of one triangular solve.  q has length n_slots-1.

    With ``x0`` the vector starts from an existing iterate and the rounds
    overwrite it in place — this is a Gauss-Seidel sweep when the tables
    hold the FULL off-diagonal part of A (see gauss_seidel_sweep)."""
    n_slots = tables.n_slots
    if x0 is None:
        y0 = jnp.zeros((n_slots,), dtype=q.dtype)
    else:
        y0 = jnp.concatenate([x0, jnp.zeros((1,), dtype=q.dtype)])
    qp = jnp.concatenate([q, jnp.zeros((1,), dtype=q.dtype)])
    S = tables.rows.shape[0]

    def body(s, y):
        rows = tables.rows[s]                       # (R,)
        gathered = y[tables.cols[s]]                # (R, K)
        acc = jnp.einsum("rk,rk->r", tables.vals[s], gathered)
        t = (qp[rows] - acc) * tables.dinv[s]
        return y.at[rows].set(t)

    y = jax.lax.fori_loop(0, S, body, y0)
    return y[:-1]


def _substitute_batched(tables: DeviceTables, q: jax.Array) -> jax.Array:
    """Multi-RHS variant of ``_substitute``.  q: (n_slots-1, B).

    Per-column arithmetic follows the single-RHS path (same gather, same
    K-reduction) up to XLA's reassociation of the einsum, so each column
    agrees with the corresponding single-RHS solve to rounding — tight
    enough that batched PCG reproduces single-RHS iteration counts.
    """
    n_slots = tables.n_slots
    b = q.shape[1]
    y0 = jnp.zeros((n_slots, b), dtype=q.dtype)
    qp = jnp.concatenate([q, jnp.zeros((1, b), dtype=q.dtype)], axis=0)
    S = tables.rows.shape[0]

    def body(s, y):
        rows = tables.rows[s]                       # (R,)
        gathered = y[tables.cols[s]]                # (R, K, B)
        acc = jnp.einsum("rk,rkb->rb", tables.vals[s], gathered)
        t = (qp[rows] - acc) * tables.dinv[s][:, None]
        return y.at[rows].set(t)

    y = jax.lax.fori_loop(0, S, body, y0)
    return y[:-1]


@jax.jit
def forward_solve(tables: DeviceTables, q: jax.Array) -> jax.Array:
    """y = L^{-1} q over the packed forward tables (eq. 4.12-4.18)."""
    return _substitute(tables, q)


@jax.jit
def backward_solve(tables: DeviceTables, y: jax.Array) -> jax.Array:
    """z = L^{-T} y over the packed backward tables."""
    return _substitute(tables, y)


@jax.jit
def forward_solve_batched(tables: DeviceTables, q: jax.Array) -> jax.Array:
    """Y = L^{-1} Q over the packed forward tables.  Q: (n, B)."""
    return _substitute_batched(tables, q)


@jax.jit
def backward_solve_batched(tables: DeviceTables, y: jax.Array) -> jax.Array:
    """Z = L^{-T} Y over the packed backward tables.  Y: (n, B)."""
    return _substitute_batched(tables, y)


# ---------------------------------------------------------------------------
# Round-major-native path: the PCG state itself lives in round-major
# coordinates, so the preconditioner apply performs ZERO permutations and
# both sweeps run over one buffer, one program loop per segment and half.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceSweep:
    """sell.SweepTables moved to device: one half of one segment."""
    cols: jax.Array   # (n, K, R) int32 — flat round-major gather positions
    vals: jax.Array   # (n, K, R)
    dinv: jax.Array   # (n, R)

    def tree_flatten(self):
        return (self.cols, self.vals, self.dinv), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceFusedTables:
    """sell.FusedRoundMajorTables moved to device as a pytree.

    ``fwd[c]`` / ``bwd[c]`` are segment ``c``'s forward rounds and its
    backward rounds (backward execution order); the segments' offsets in
    the flat state follow from their shapes.
    """
    fwd: tuple[DeviceSweep, ...]
    bwd: tuple[DeviceSweep, ...]

    def tree_flatten(self):
        return (self.fwd, self.bwd), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def segments(self) -> tuple[tuple[int, int], ...]:
        """(rounds, lanes) per segment."""
        return tuple(t.dinv.shape for t in self.fwd)

    @property
    def n_segments(self) -> int:
        return len(self.fwd)

    @property
    def n_steps(self) -> int:
        """Rounds per sweep (an apply runs 2 * n_steps steps)."""
        return sum(n for n, _ in self.segments)

    @property
    def m(self) -> int:
        """Length of the round-major state."""
        return sum(n * r for n, r in self.segments)

    @property
    def gather_slots(self) -> int:
        """Values an apply gathers: rounds x K x lanes, over every segment
        and half."""
        return sum(t.cols.size for t in self.fwd + self.bwd)

    def stacked(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        """The single segment as the Pallas fused kernel's ``(2S, R, K)``
        operands (``sell.stack_sweeps``)."""
        if self.n_segments != 1:
            raise ValueError(f"the Pallas fused kernel runs one segment, "
                             f"these tables have {self.n_segments}")
        return stack_sweeps(self.fwd[0], self.bwd[0], self.m, xp=jnp)

    @classmethod
    def from_host(cls, f: FusedRoundMajorTables,
                  dtype=jnp.float64) -> "DeviceFusedTables":
        def dev(t):
            return DeviceSweep(cols=jnp.asarray(t.cols),
                               vals=jnp.asarray(t.vals, dtype=dtype),
                               dinv=jnp.asarray(t.dinv, dtype=dtype))
        return cls(fwd=tuple(map(dev, f.fwd)), bwd=tuple(map(dev, f.bwd)))


def _sweeps(tables: DeviceFusedTables, q: jax.Array,
            segments: tuple[tuple[int, int], ...],
            axis: str | None = None) -> jax.Array:
    """z = (L L^T)^{-1} q in round-major coordinates.  q: (m,) or (m, B).

    One ``fori_loop`` per segment and half over one flat buffer: the
    forward loops in segment order, then the backward loops in reverse
    order, each step gathering, contracting and storing one round as a
    dense ``lax.dynamic_update_slice`` — the backward half overwrites the
    forward result in place, in reverse slice order (see
    ``sell.FusedRoundMajorTables`` for the safety argument).  Zero scatter
    ops in the jaxpr.  ``segments`` gives each segment's (rounds, lanes)
    at full width; under ``shard_map`` over ``axis`` the tables hold this
    device's lane block and one tiled all-gather per step assembles the
    round before its store.
    """
    tail = q.shape[1:]                            # () or (B,)
    # einsum (not elementwise-multiply + sum): XLA contracts it directly
    # instead of materializing the product — measurably faster on CPU.
    # The kernel-exact op order lives in kernels/ref.py instead.
    eq = "kr,krb->rb" if tail else "kr,kr->r"
    shard = None if axis is None else jax.lax.axis_index(axis)
    offsets = np.cumsum([0] + [n * r for n, r in segments[:-1]])

    def loop(t: DeviceSweep, off: int, r_full: int, y, backward: bool):
        n, r_loc = t.dinv.shape

        def body(j, y):
            vals = t.vals[j]
            gathered = jnp.take(y, t.cols[j], axis=0, fill_value=0)
            if r_loc == 1 < r_full:
                # XLA lowers a one-lane contraction as a plain dot, which
                # sums in another order than the full round's batched one:
                # contract this device's lane beside a zero lane instead
                pad = ((0, 0), (0, 1)) + ((0, 0),) * len(tail)
                acc = jnp.einsum(eq, jnp.pad(vals, pad[:2]),
                                 jnp.pad(gathered, pad))[:1]
            else:
                acc = jnp.einsum(eq, vals, gathered)
            # pin the index dtype: the loop counter is weakly typed and
            # axis_index is i32 — mixing them flips dtypes between the
            # dynamic_slice index operands
            dest = (int(off) + ((n - 1 - j) if backward else j) * r_full
                    ).astype(jnp.int32)
            zeros = (jnp.zeros_like(dest),) * len(tail)
            mine = dest if axis is None else dest + shard * r_loc
            # forward rounds read their q slice; backward rounds the y
            # slice they are about to overwrite
            q_cur = jax.lax.dynamic_slice(y if backward else q,
                                          (mine,) + zeros, (r_loc,) + tail)
            d = t.dinv[j][:, None] if tail else t.dinv[j]
            new = (q_cur - acc) * d
            if axis is not None:
                new = jax.lax.all_gather(new, axis, tiled=True)
            return jax.lax.dynamic_update_slice(y, new, (dest,) + zeros)

        return jax.lax.fori_loop(0, n, body, y)

    y = jnp.zeros(q.shape, dtype=q.dtype)
    for t, off, (_, r) in zip(tables.fwd, offsets, segments):
        y = loop(t, off, r, y, backward=False)
    for t, off, (_, r) in reversed(list(zip(tables.bwd, offsets, segments))):
        y = loop(t, off, r, y, backward=True)
    return y


@jax.jit
def fused_solve(tables: DeviceFusedTables, q: jax.Array) -> jax.Array:
    """z = (L L^T)^{-1} q, round-major in and out.  q: (m,) or (m, B)."""
    return _sweeps(tables, q, tables.segments)


fused_solve_batched = fused_solve     # the multi-RHS apply, q: (m, B)


# ---------------------------------------------------------------------------
# Mesh-sharded substitution: every segment's lane axis is sharded over one
# mesh axis, the solution vector is replicated, and each step ends in ONE
# tiled all-gather of the lane updates — the distributed analogue of the
# paper's "one synchronization per color" (§4.4.3), one level up: level-1
# blocks -> devices, w lanes -> the vector unit within a device.
# ---------------------------------------------------------------------------

def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis in ``Auto`` mode.

    ``jax.make_mesh`` returns ``Explicit`` axes, under which a gather from
    a sharded operand has no defined output sharding and tracing raises.
    The solver's sharded paths place their own collectives (``shard_map``)
    and leave the rest to the compiler's propagation, which is what
    ``Auto`` axes give."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _lane_specs(tables: DeviceFusedTables, axis: str):
    """PartitionSpecs sharding every table's lane axis over ``axis``."""
    return jax.tree.map(
        lambda x: P(None, None, axis) if x.ndim == 3 else P(None, axis),
        tables)


def _dist_substitute_fused(mesh: Mesh, axis: str, tables: DeviceFusedTables,
                           q: jax.Array) -> jax.Array:
    """The round-major apply with every segment's lane axis sharded over
    ``axis`` (each segment's lanes a multiple of the axis size).

    ``q``: (m,) or (m, B), replicated.  Per step, every device computes its
    own lane block's updates (gathering from its replica of y) and one
    ``all_gather(tiled=True)`` assembles the round's dense slice before the
    store — the per-lane arithmetic is exactly the single-device sweep's,
    so results are bitwise identical to it over the same tables.
    """
    segments = tables.segments

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(_lane_specs(tables, axis), P()), out_specs=P(),
             check_vma=False)
    def solve(tables_l, q_l):
        return _sweeps(tables_l, q_l, segments, axis=axis)

    return solve(tables, q)


@dataclasses.dataclass(frozen=True)
class DistributedRoundMajorPreconditioner:
    """``RoundMajorPreconditioner`` sharded over a device mesh axis.

    ``tables`` hold the round-major segments with every LANE axis sharded
    over ``mesh``/``axis`` (``NamedSharding(mesh, P(None, None, axis))``
    for cols/vals, ``P(None, axis)`` for dinv) — the heavy data is fully
    distributed; the (m,) state vectors stay replicated.  The apply runs
    the segments' loops with one collective per round.
    """
    tables: DeviceFusedTables
    mesh: Mesh
    axis: str = "data"

    @property
    def n_rounds(self) -> int:
        return self.tables.n_steps

    @property
    def m(self) -> int:
        return self.tables.m

    def __call__(self, r: jax.Array) -> jax.Array:
        return _dist_substitute_fused(self.mesh, self.axis, self.tables, r)

    def apply_batched(self, r: jax.Array) -> jax.Array:
        return _dist_substitute_fused(self.mesh, self.axis, self.tables, r)


def shard_fused_tables(tables: DeviceFusedTables, mesh: Mesh,
                       axis: str = "data") -> DeviceFusedTables:
    """Place round-major tables with every lane axis sharded over ``axis``.

    Each segment's lane axis must already be a multiple of the axis size —
    build the plan/tables with ``lane_multiple = mesh.shape[axis]``
    (``pack_steps(..., lane_multiple=...)``) rather than re-padding here,
    so every round-major position stays valid.
    """
    n_dev = mesh.shape[axis]
    for _, lanes in tables.segments:
        if lanes % n_dev != 0:
            raise ValueError(
                f"lane axis ({lanes}) is not a multiple of mesh axis "
                f"{axis!r} ({n_dev}); pack with lane_multiple={n_dev}")
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        tables, _lane_specs(tables, axis))


@dataclasses.dataclass(frozen=True)
class RoundMajorPreconditioner:
    """IC(0) apply operating natively on round-major (m,) state vectors.

    Unlike ``HBMCPreconditioner`` (which gathers/scatters between index
    space and the solve layout on every apply), this preconditioner's input
    and output ARE round-major: the only permutations of a solve happen in
    ``RoundMajorLayout.embed``/``extract``, once each, outside the PCG loop.

    ``backend="xla"`` runs ``fused_solve`` (a loop per segment and half,
    dynamic slices); ``backend="pallas"`` runs
    ``kernels.hbmc_trisolve_fused`` (one pallas_call, 2S-step sequential
    grid, y VMEM-resident across sweeps) on single-segment tables.
    """
    tables: DeviceFusedTables
    backend: str = "xla"
    interpret: bool | None = None

    @property
    def n_rounds(self) -> int:
        return self.tables.n_steps

    @property
    def m(self) -> int:
        return self.tables.m

    @property
    def gather_slots(self) -> int:
        return self.tables.gather_slots

    def _pallas_operands(self, r: jax.Array, batched: bool):
        cols, vals, dinv = self.tables.stacked()
        s_, lanes = self.tables.segments[0]
        shape = (s_, lanes) + ((r.shape[-1],) if batched else ())
        return cols, vals, dinv, r.reshape(shape)

    def __call__(self, r: jax.Array) -> jax.Array:
        if self.backend == "pallas":
            from repro.kernels.hbmc_trisolve import hbmc_trisolve_fused
            return hbmc_trisolve_fused(*self._pallas_operands(r, False),
                                       interpret=self.interpret)
        return fused_solve(self.tables, r)

    def apply_batched(self, r: jax.Array) -> jax.Array:
        if self.backend == "pallas":
            from repro.kernels.hbmc_trisolve import hbmc_trisolve_fused_batched
            return hbmc_trisolve_fused_batched(
                *self._pallas_operands(r, True), interpret=self.interpret)
        return fused_solve_batched(self.tables, r)


def build_round_major_preconditioner_from_rounds(
        l_final: sp.csr_matrix, fwd_rounds, bwd_rounds, drop_mask=None,
        dtype=jnp.float64, backend: str = "xla",
        interpret: bool | None = None, lane_multiple: int = 1
        ) -> tuple[RoundMajorPreconditioner, RoundMajorLayout]:
    """Pack a factor into the segmented round-major form; returns the
    native preconditioner plus the layout (the b-in / x-out permutation
    pair).  The Pallas fused kernel takes uniform tables, so its plans
    keep one segment.

    ``lane_multiple`` pads every segment's lane axis so it shards evenly
    over a mesh axis of that size (see
    ``DistributedRoundMajorPreconditioner``)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    from .sell import pack_factor
    fwd_h, bwd_h = pack_factor(l_final, fwd_rounds, bwd_rounds, drop_mask,
                               lane_multiple)
    fused_h = fuse_round_major(
        fwd_h, bwd_h, max_segments=1 if backend == "pallas" else MAX_SEGMENTS)
    pre = RoundMajorPreconditioner(
        tables=DeviceFusedTables.from_host(fused_h, dtype=dtype),
        backend=backend, interpret=interpret)
    return pre, fused_h.layout


def build_round_major_preconditioner(
        l_final: sp.csr_matrix, ordering: HBMCOrdering, dtype=jnp.float64,
        backend: str = "xla", interpret: bool | None = None
        ) -> tuple[RoundMajorPreconditioner, RoundMajorLayout]:
    from .sell import rounds_hbmc
    return build_round_major_preconditioner_from_rounds(
        l_final, rounds_hbmc(ordering, reverse=False),
        rounds_hbmc(ordering, reverse=True), drop_mask=ordering.is_dummy,
        dtype=dtype, backend=backend, interpret=interpret)


@dataclasses.dataclass(frozen=True)
class HBMCPreconditioner:
    """IC(0) preconditioner  M^{-1} r = (L L^T)^{-1} r  in HBMC order.

    ``backend`` selects the triangular-solve implementation:
      * ``"xla"``    — fori_loop substitution over ``fwd``/``bwd``
        (``kernel`` is None);
      * ``"pallas"`` — the round-major Pallas kernel held in ``kernel``
        (a ``repro.kernels.ops.KernelPreconditioner``); ``fwd``/``bwd``
        are None so the (S, R, K) tables live on device only once.  The
        legacy index-space dry-run path (core.partition.shard_tables /
        lower_solver_step) consumes DeviceTables, i.e. the "xla" layout;
        the production distributed apply is
        ``DistributedRoundMajorPreconditioner``.
    """
    fwd: DeviceTables | None
    bwd: DeviceTables | None
    n_final: int
    backend: str = "xla"
    kernel: Any = None

    @property
    def n_rounds(self) -> int:
        t = self.fwd if self.fwd is not None else self.kernel.fwd
        return int(t.rows.shape[0])

    @property
    def gather_slots(self) -> int:
        """Values an apply gathers: both halves' (S, R, K) tables."""
        tabs = (self.fwd, self.bwd) if self.fwd is not None else (
            self.kernel.fwd, self.kernel.bwd)
        return sum(t.cols.size for t in tabs)

    def __call__(self, r: jax.Array) -> jax.Array:
        if self.backend == "pallas":
            return self.kernel(r)
        y = forward_solve(self.fwd, r)
        return backward_solve(self.bwd, y)

    def apply_batched(self, r: jax.Array) -> jax.Array:
        """Multi-RHS apply: r (n, B) -> (n, B), columns independent."""
        if self.backend == "pallas":
            return self.kernel.apply_batched(r)
        y = forward_solve_batched(self.fwd, r)
        return backward_solve_batched(self.bwd, y)


def _assemble_preconditioner(fwd_h: StepTables, bwd_h: StepTables,
                             n_final: int, dtype, backend: str,
                             interpret: bool | None) -> HBMCPreconditioner:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    if backend == "pallas":
        # deferred import: repro.kernels.ops itself imports repro.core.sell
        from repro.kernels.ops import build_kernel_preconditioner
        kernel = build_kernel_preconditioner(fwd_h, bwd_h, dtype=dtype,
                                             use_kernel=True,
                                             interpret=interpret)
        return HBMCPreconditioner(fwd=None, bwd=None, n_final=n_final,
                                  backend=backend, kernel=kernel)
    return HBMCPreconditioner(
        fwd=DeviceTables.from_host(fwd_h, dtype=dtype),
        bwd=DeviceTables.from_host(bwd_h, dtype=dtype),
        n_final=n_final, backend=backend, kernel=None)


def build_preconditioner(l_final: sp.csr_matrix, ordering: HBMCOrdering,
                         dtype=jnp.float64, backend: str = "xla",
                         interpret: bool | None = None) -> HBMCPreconditioner:
    fwd_h, bwd_h = pack_factor_hbmc(l_final, ordering)
    return _assemble_preconditioner(fwd_h, bwd_h, ordering.n_final, dtype,
                                    backend, interpret)


def build_preconditioner_from_rounds(
        l_final: sp.csr_matrix, fwd_rounds, bwd_rounds,
        drop_mask=None, dtype=jnp.float64, backend: str = "xla",
        interpret: bool | None = None) -> HBMCPreconditioner:
    """Generic variant: MC / BMC / natural solvers share the machinery."""
    from .sell import pack_factor
    fwd_h, bwd_h = pack_factor(l_final, fwd_rounds, bwd_rounds, drop_mask)
    return _assemble_preconditioner(fwd_h, bwd_h, l_final.shape[0], dtype,
                                    backend, interpret)


# ---------------------------------------------------------------------------
# Sequential oracle (host) — used by tests to pin down exact semantics.
# ---------------------------------------------------------------------------

def sequential_forward(l: sp.csr_matrix, q: np.ndarray) -> np.ndarray:
    return sp.linalg.spsolve_triangular(sp.csr_matrix(l), q, lower=True)


def sequential_backward(l: sp.csr_matrix, y: np.ndarray) -> np.ndarray:
    return sp.linalg.spsolve_triangular(sp.csr_matrix(l).T.tocsr(), y,
                                        lower=False)
