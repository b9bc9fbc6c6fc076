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
    solver path) applies natively on round-major vectors with both sweeps
    fused into one 2S-step pass; zero per-apply permutations.

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
from .sell import (FusedRoundMajorTables, RoundMajorLayout, StepTables,
                   fuse_round_major, pack_factor_hbmc)

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
# both sweeps run as one fused pass (2S steps over one buffer).
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceFusedTables:
    """sell.FusedRoundMajorTables moved to device as a pytree.

    Row ``g`` of each array drives fused step ``g``: forward rounds for
    ``g < S``, backward rounds (backward execution order) for ``g >= S``.
    """
    cols: jax.Array   # (2S, R, K) int32 — fwd-round-major gather positions
    vals: jax.Array   # (2S, R, K)
    dinv: jax.Array   # (2S, R)

    def tree_flatten(self):
        return (self.cols, self.vals, self.dinv), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_steps(self) -> int:
        """Rounds per sweep (the fused loop runs 2 * n_steps steps)."""
        return self.dinv.shape[0] // 2

    @property
    def lanes(self) -> int:
        return self.dinv.shape[1]

    @classmethod
    def from_host(cls, f: FusedRoundMajorTables,
                  dtype=jnp.float64) -> "DeviceFusedTables":
        return cls(cols=jnp.asarray(f.cols),
                   vals=jnp.asarray(f.vals, dtype=dtype),
                   dinv=jnp.asarray(f.dinv, dtype=dtype))


def _substitute_fused(tables: DeviceFusedTables, q: jax.Array) -> jax.Array:
    """Fused fwd+bwd substitution in round-major coordinates.  q: (S, R).

    The round-major ``_substitute``: each step's store is a dense
    ``lax.dynamic_update_slice`` instead of the ``y.at[rows].set`` scatter
    of the index-space path — the backward half overwrites the forward
    result in place, in reverse slice order (see kernels/hbmc_trisolve.py
    for the safety argument).  Zero scatter ops in the jaxpr.
    """
    s_, r_ = q.shape
    s2 = 2 * s_
    y0 = jnp.zeros((s_ * r_,), dtype=q.dtype)

    def body(g, y):
        gathered = jnp.take(y, tables.cols[g], axis=0, fill_value=0)  # (R, K)
        # einsum (not elementwise-multiply + sum): XLA contracts it directly
        # instead of materializing the product — measurably faster on CPU.
        # The kernel-exact op order lives in kernels/ref.py instead.
        acc = jnp.einsum("rk,rk->r", tables.vals[g], gathered)
        dest = jnp.where(g < s_, g, s2 - 1 - g) * r_
        q_cur = jnp.where(g < s_, q[jnp.minimum(g, s_ - 1)],
                          jax.lax.dynamic_slice(y, (dest,), (r_,)))
        t = (q_cur - acc) * tables.dinv[g]
        return jax.lax.dynamic_update_slice(y, t, (dest,))

    return jax.lax.fori_loop(0, s2, body, y0)


def _substitute_fused_batched(tables: DeviceFusedTables,
                              q: jax.Array) -> jax.Array:
    """Multi-RHS fused substitution.  q: (S, R, B) -> (S*R, B)."""
    s_, r_, b_ = q.shape
    s2 = 2 * s_
    y0 = jnp.zeros((s_ * r_, b_), dtype=q.dtype)

    def body(g, y):
        gathered = jnp.take(y, tables.cols[g], axis=0, fill_value=0)
        acc = jnp.einsum("rk,rkb->rb", tables.vals[g], gathered)
        dest = jnp.where(g < s_, g, s2 - 1 - g) * r_
        q_cur = jnp.where(g < s_, q[jnp.minimum(g, s_ - 1)],
                          jax.lax.dynamic_slice(y, (dest, jnp.zeros_like(dest)), (r_, b_)))
        t = (q_cur - acc) * tables.dinv[g][:, None]
        return jax.lax.dynamic_update_slice(y, t, (dest, jnp.zeros_like(dest)))

    return jax.lax.fori_loop(0, s2, body, y0)


@jax.jit
def fused_solve(tables: DeviceFusedTables, q: jax.Array) -> jax.Array:
    """z = (L L^T)^{-1} q, round-major in and out.  q: (S, R) -> (S*R,)."""
    return _substitute_fused(tables, q)


@jax.jit
def fused_solve_batched(tables: DeviceFusedTables, q: jax.Array) -> jax.Array:
    """Multi-RHS fused apply.  q: (S, R, B) -> (S*R, B)."""
    return _substitute_fused_batched(tables, q)


# ---------------------------------------------------------------------------
# Mesh-sharded fused substitution: the lane axis R is sharded over one mesh
# axis, the solution vector is replicated, and each fused step ends in ONE
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


def _dist_substitute_fused(mesh: Mesh, axis: str, m: int,
                           cols: jax.Array, vals: jax.Array,
                           dinv: jax.Array, q: jax.Array,
                           batched: bool) -> jax.Array:
    """Fused fwd+bwd sweep with the lane axis sharded over ``axis``.

    ``cols``/``vals``: (2S, R, K) with R a multiple of the axis size;
    ``dinv``: (2S, R); ``q``: (S, R) (or (S, R, B)).  Per fused step, every
    device computes its own lane block's updates (gathering from its
    replica of y) and one ``all_gather(tiled=True)`` assembles the round's
    dense slice before the store — the per-lane arithmetic is exactly
    ``_substitute_fused``'s, so results are bitwise identical to the
    single-device sweep over the same tables.
    """
    r_full = dinv.shape[1]
    t_spec = (P(None, axis, None), P(None, axis, None), P(None, axis))
    q_spec = P(None, axis, None) if batched else P(None, axis)

    @partial(jax.shard_map, mesh=mesh, in_specs=t_spec + (q_spec,),
             out_specs=P(), check_vma=False)
    def solve(cols_l, vals_l, dinv_l, q_l):
        s_ = q_l.shape[0]
        r_loc = dinv_l.shape[1]
        s2 = 2 * s_
        tail = q_l.shape[2:]                      # () or (B,)
        y0 = jnp.zeros((m,) + tail, dtype=q_l.dtype)
        i = jax.lax.axis_index(axis)
        eq = "rk,rkb->rb" if batched else "rk,rk->r"

        def body(g, y):
            gathered = jnp.take(y, cols_l[g], axis=0, fill_value=0)
            acc = jnp.einsum(eq, vals_l[g], gathered)
            # pin the index dtype: the loop counter is weakly typed and
            # axis_index is i32 — mixing them flips dtypes between the
            # dynamic_slice index operands
            dest = (jnp.where(g < s_, g, s2 - 1 - g) * r_full
                    ).astype(jnp.int32)
            zeros = (jnp.zeros_like(dest),) * len(tail)
            # forward half reads its lane block of q; backward half reads
            # the y slice it is about to overwrite (see _substitute_fused)
            q_cur = jnp.where(
                g < s_, q_l[jnp.minimum(g, s_ - 1)],
                jax.lax.dynamic_slice(
                    y, (dest + i * r_loc,) + zeros, (r_loc,) + tail))
            d = dinv_l[g][:, None] if batched else dinv_l[g]
            t = (q_cur - acc) * d
            t_full = jax.lax.all_gather(t, axis, tiled=True)
            return jax.lax.dynamic_update_slice(y, t_full, (dest,) + zeros)

        return jax.lax.fori_loop(0, s2, body, y0)

    return solve(cols, vals, dinv, q)


@dataclasses.dataclass(frozen=True)
class DistributedRoundMajorPreconditioner:
    """``RoundMajorPreconditioner`` sharded over a device mesh axis.

    ``tables`` hold the fused round-major form with the LANE axis sharded
    over ``mesh``/``axis`` (``NamedSharding(mesh, P(None, axis, None))``
    for cols/vals, ``P(None, axis)`` for dinv) — the heavy data is fully
    distributed; the (m,) state vectors stay replicated.  The apply is the
    fused single-pass 2S-step sweep with one collective per round.
    """
    tables: DeviceFusedTables
    mesh: Mesh
    axis: str = "data"

    @property
    def n_rounds(self) -> int:
        return self.tables.n_steps

    @property
    def m(self) -> int:
        return self.tables.n_steps * self.tables.lanes

    def _reshape(self, r: jax.Array, batched: bool) -> jax.Array:
        s_, lanes = self.tables.n_steps, self.tables.lanes
        shape = (s_, lanes) + ((r.shape[-1],) if batched else ())
        return r.reshape(shape)

    def __call__(self, r: jax.Array) -> jax.Array:
        t = self.tables
        return _dist_substitute_fused(self.mesh, self.axis, self.m, t.cols,
                                      t.vals, t.dinv,
                                      self._reshape(r, batched=False),
                                      batched=False)

    def apply_batched(self, r: jax.Array) -> jax.Array:
        t = self.tables
        return _dist_substitute_fused(self.mesh, self.axis, self.m, t.cols,
                                      t.vals, t.dinv,
                                      self._reshape(r, batched=True),
                                      batched=True)


def shard_fused_tables(tables: DeviceFusedTables, mesh: Mesh,
                       axis: str = "data") -> DeviceFusedTables:
    """Place fused tables with the lane axis sharded over ``axis``.

    The lane axis must already be a multiple of the axis size — build the
    plan/tables with ``lane_multiple = mesh.shape[axis]``
    (``pack_steps(..., lane_multiple=...)``) rather than re-padding here,
    so every round-major position stays valid.
    """
    n_dev = mesh.shape[axis]
    if tables.lanes % n_dev != 0:
        raise ValueError(
            f"lane axis ({tables.lanes}) is not a multiple of mesh axis "
            f"{axis!r} ({n_dev}); pack with lane_multiple={n_dev}")
    sh3 = NamedSharding(mesh, P(None, axis, None))
    sh2 = NamedSharding(mesh, P(None, axis))
    return DeviceFusedTables(cols=jax.device_put(tables.cols, sh3),
                             vals=jax.device_put(tables.vals, sh3),
                             dinv=jax.device_put(tables.dinv, sh2))


@dataclasses.dataclass(frozen=True)
class RoundMajorPreconditioner:
    """IC(0) apply operating natively on round-major (m,) state vectors.

    Unlike ``HBMCPreconditioner`` (which gathers/scatters between index
    space and the solve layout on every apply), this preconditioner's input
    and output ARE round-major: the only permutations of a solve happen in
    ``RoundMajorLayout.embed``/``extract``, once each, outside the PCG loop.

    ``backend="xla"`` runs ``fused_solve`` (fori_loop, dynamic slices);
    ``backend="pallas"`` runs ``kernels.hbmc_trisolve_fused`` (one
    pallas_call, 2S-step sequential grid, y VMEM-resident across sweeps).
    """
    tables: DeviceFusedTables
    backend: str = "xla"
    interpret: bool | None = None

    @property
    def n_rounds(self) -> int:
        return self.tables.n_steps

    @property
    def m(self) -> int:
        return self.tables.n_steps * self.tables.lanes

    def _reshape(self, r: jax.Array, batched: bool) -> jax.Array:
        s_, lanes = self.tables.n_steps, self.tables.lanes
        shape = (s_, lanes) + ((r.shape[-1],) if batched else ())
        return r.reshape(shape)

    def __call__(self, r: jax.Array) -> jax.Array:
        q = self._reshape(r, batched=False)
        if self.backend == "pallas":
            from repro.kernels.hbmc_trisolve import hbmc_trisolve_fused
            return hbmc_trisolve_fused(self.tables.cols, self.tables.vals,
                                       self.tables.dinv, q,
                                       interpret=self.interpret)
        return fused_solve(self.tables, q)

    def apply_batched(self, r: jax.Array) -> jax.Array:
        q = self._reshape(r, batched=True)
        if self.backend == "pallas":
            from repro.kernels.hbmc_trisolve import hbmc_trisolve_fused_batched
            return hbmc_trisolve_fused_batched(
                self.tables.cols, self.tables.vals, self.tables.dinv, q,
                interpret=self.interpret)
        return fused_solve_batched(self.tables, q)


def build_round_major_preconditioner_from_rounds(
        l_final: sp.csr_matrix, fwd_rounds, bwd_rounds, drop_mask=None,
        dtype=jnp.float64, backend: str = "xla",
        interpret: bool | None = None, lane_multiple: int = 1
        ) -> tuple[RoundMajorPreconditioner, RoundMajorLayout]:
    """Pack a factor into the fused round-major form; returns the native
    preconditioner plus the layout (the b-in / x-out permutation pair).

    ``lane_multiple`` pads the lane axis so it shards evenly over a mesh
    axis of that size (see ``DistributedRoundMajorPreconditioner``)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    from .sell import pack_factor
    fwd_h, bwd_h = pack_factor(l_final, fwd_rounds, bwd_rounds, drop_mask,
                               lane_multiple)
    fused_h = fuse_round_major(fwd_h, bwd_h)
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
