"""Pallas TPU kernel for the HBMC triangular substitution.

TPU adaptation of the paper's AVX-512 inner loop (Fig. 4.6).  The rounds of
the HBMC substitution are laid out *round-major*: the R lanes of round ``s``
occupy the contiguous slice ``y[s*R : (s+1)*R]``.  Laying the vector out in
execution order turns the paper's per-block strided stores into dense
contiguous VMEM stores.  Round-major layout is itself an equivalent
reordering (same argument as HBMC <- BMC: lanes of one round are mutually
independent), so convergence is untouched.

Kernel layout.  The solution vector lives in VMEM as ``(m / wc, wc)`` rows
of ``wc`` lanes (``wc`` = 128, the TPU lane width, when R is a multiple of
it; otherwise one row per round, which only interpret-mode shapes use).
The packed tables are re-laid out as ``(steps, K, R / wc, wc)``: K sits on
a leading axis, so every block is lane-dense, where the ``(steps, R, K)``
packing would pad K (about 4) to 128 lanes.

Grid ``(steps, R / (tile * wc))``: one sequential step per round and lane
tile.  TPU grid steps execute in order, which realizes the round -> round
dependency without extra synchronization, mirroring "one thread barrier per
color" in the paper; the lane tiles of one round are independent.

The gather.  Mosaic lowers a vector gather only within one vreg, so the
``_mm512_i32logather_pd`` gather from the whole VMEM-resident vector is
built from what it does lower: the column indices of the step also arrive
in SMEM, a scalar loop copies the ``wc``-lane row holding each wanted entry
into a ``(wc, wc)`` staging block (a dynamic sublane load and store), and
one transpose plus a one-hot select over sublanes (keyed by the index's
lane) turns the staged rows into the lane-dense ``(1, wc)`` vector of
gathered values.  Missing neighbours (index ``m``, zero value) gather an
exact 0, as ``jnp.take(..., fill_value=0)`` does.

Working set (VMEM): the resident output ``y`` (double-buffered by the
pipeline), the staging block and the per-step table blocks; the limit is
``config.VMEM_LIMIT_BYTES`` (the budget of ``analysis.kernel_checks``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .config import LANES, SUBLANES, VMEM_LIMIT_BYTES, resolve_interpret


# block-index zero: an int32 literal, where a Python 0 would make the index
# map return int64 under jax_enable_x64, which Mosaic rejects
_Z = np.int32(0)


def lane_width(r: int) -> int:
    """Lanes per row of the kernel layout for round width ``r``."""
    return LANES if r % LANES == 0 else r


def _tile_rows(rows: int) -> int:
    """Rows of ``wc`` lanes per grid step: one (8, 128) tile when the
    round allows, else the whole round."""
    return SUBLANES if rows % SUBLANES == 0 else rows


def gather_rows(idx_ref, base, cols, y_ref, stage_ref, m: int):
    """Gather ``y[cols]`` for one row of ``wc`` indices.

    ``idx_ref`` (SMEM) holds the same indices as ``cols`` ((1, wc) int32,
    VMEM) from flat offset ``base``; ``y_ref`` is the ``(m / wc, wc)``
    vector.  Indices ``>= m`` gather 0.  Returns ``(1, wc)``.
    """
    wc = cols.shape[-1]
    wc32 = jnp.int32(wc)    # int32 operands stay int32 under jax_enable_x64

    def stage(l, carry):
        c = jnp.minimum(idx_ref[0, 0, base + l], jnp.int32(m - 1))
        stage_ref[pl.ds(l, 1), :] = y_ref[pl.ds(jax.lax.div(c, wc32), 1), :]
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(wc), stage, None)
    # staged^T[x, l] = lane x of the row holding index l; keep x = lane(l)
    sub = jax.lax.broadcasted_iota(jnp.int32, (wc, wc), 0)
    picked = jnp.sum(jnp.where(sub == jax.lax.rem(cols, wc32),
                               stage_ref[...].T, 0),
                     axis=0, keepdims=True)
    return jnp.where(cols < m, picked, 0)


def _sweep_kernel(idx_ref, cols_ref, vals_ref, dinv_ref, q_ref, y_ref,
                  stage_ref, *, s_fwd: int, rows: int, m: int):
    """One (round, lane tile) step of the sweep.

    Steps ``g < s_fwd`` are forward rounds writing slice ``g``; later steps
    are backward rounds writing slice ``2 * s_fwd - 1 - g`` in place, their
    RHS being the forward result they overwrite.
    """
    g = pl.program_id(0)
    j = pl.program_id(1)
    _, k_, tile, wc = cols_ref.shape
    fwd = g < s_fwd
    dest = jnp.where(fwd, g, 2 * s_fwd - 1 - g) * rows + j * tile
    g_planes = jnp.stack([
        jnp.concatenate([gather_rows(idx_ref, (k * tile + i) * wc,
                                     cols_ref[0, k, i:i + 1, :], y_ref,
                                     stage_ref, m)
                         for i in range(tile)], axis=0)
        for k in range(k_)])                                  # (K, tile, wc)
    acc = jnp.sum(vals_ref[0] * g_planes, axis=0)             # (tile, wc)
    q_cur = jnp.where(fwd, q_ref[0], y_ref[pl.ds(dest, tile), :])
    y_ref[pl.ds(dest, tile), :] = (q_cur - acc) * dinv_ref[0]


def _sweep(cols, vals, dinv, q, *, s_fwd: int, interpret: bool):
    """Run ``cols.shape[0]`` steps over ``q`` ((S, R)); returns (S*R,)."""
    steps, r_, k_ = cols.shape
    if not interpret and r_ % (SUBLANES * LANES):
        raise ValueError(
            f"the compiled sweep tiles each round's lanes in whole "
            f"({SUBLANES}, {LANES}) tiles, but R = {r_}; pack with "
            f"lane_multiple={SUBLANES * LANES}")
    s_ = q.shape[0]
    m = s_ * r_
    wc = lane_width(r_)
    rows = r_ // wc
    tile = _tile_rows(rows)
    # kernel layout: K off the lane axis, lanes in rows of wc
    cols_k = jnp.swapaxes(cols, 1, 2).reshape(steps, k_, rows, wc)
    vals_k = jnp.swapaxes(vals, 1, 2).reshape(steps, k_, rows, wc)
    # the same indices flat per (step, lane tile), for the scalar loop
    idx = (cols_k.reshape(steps, k_, rows // tile, tile * wc)
           .transpose(0, 2, 1, 3).reshape(steps * (rows // tile), 1, -1))
    n_t = rows // tile
    q_row = lambda g, j: (jnp.minimum(g, s_ - 1), j, _Z)
    y = pl.pallas_call(
        functools.partial(_sweep_kernel, s_fwd=s_fwd, rows=rows, m=m),
        grid=(steps, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, k_ * tile * wc),
                         lambda g, j: (g * n_t + j, _Z, _Z),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, k_, tile, wc), lambda g, j: (g, _Z, j, _Z)),
            pl.BlockSpec((1, k_, tile, wc), lambda g, j: (g, _Z, j, _Z)),
            pl.BlockSpec((1, tile, wc), lambda g, j: (g, j, _Z)),
            pl.BlockSpec((1, tile, wc), q_row),
        ],
        # y stays resident: every step gathers from all of it
        out_specs=pl.BlockSpec((m // wc, wc), lambda g, j: (_Z, _Z)),
        out_shape=jax.ShapeDtypeStruct((m // wc, wc), vals.dtype),
        scratch_shapes=[pltpu.VMEM((wc, wc), vals.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(idx, cols_k, vals_k, dinv.reshape(steps, rows, wc),
      q.reshape(s_, rows, wc))
    return y.reshape(m)


def _per_column(fn, q: jax.Array) -> jax.Array:
    """Multi-RHS apply as one single-RHS apply per column of ``q``."""
    return jnp.stack([fn(q[..., b]) for b in range(q.shape[-1])], axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hbmc_trisolve(cols: jax.Array, vals: jax.Array, dinv: jax.Array,
                  q: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Solve the round-major packed triangular system.

    Args:
      cols: (S, R, K) int32 — column indices in round-major coordinates;
        padding must point at a slot whose matching ``vals`` entry is 0.
      vals: (S, R, K) — off-diagonal values (0 on padding).
      dinv: (S, R) — inverse diagonal (0 on padding lanes).
      q:    (S, R) — right-hand side in round-major layout.

    Returns:
      y: (S*R,) solution in round-major layout.
    """
    return _sweep(cols, vals, dinv, q, s_fwd=cols.shape[0],
                  interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def hbmc_trisolve_batched(cols: jax.Array, vals: jax.Array, dinv: jax.Array,
                          q: jax.Array, *, interpret: bool | None = None
                          ) -> jax.Array:
    """``hbmc_trisolve`` for B right-hand sides.  q: (S, R, B) ->
    (S*R, B), one sweep per column."""
    return _per_column(functools.partial(hbmc_trisolve, cols, vals, dinv,
                                         interpret=interpret), q)


# ---------------------------------------------------------------------------
# Fused forward+backward sweep: ONE pallas_call, 2S sequential rounds.
# ---------------------------------------------------------------------------
#
# The backward rounds are the forward rounds reversed (lane order included),
# so in forward round-major coordinates the backward sweep's stores are ALSO
# dense contiguous slices: step g >= S writes slice (2S-1-g)*R.  One VMEM
# buffer therefore carries the whole preconditioner apply: the forward half
# fills it with y = L^{-1} q, the backward half overwrites it in place with
# z = L^{-T} y in reverse slice order (each backward gather touches only
# already-overwritten z slices; the current slice's y is read just before its
# store).  Compared with two pallas_calls this halves kernel launches and
# keeps y VMEM-resident across the fwd->bwd handoff instead of round-tripping
# through HBM.


@functools.partial(jax.jit, static_argnames=("interpret",))
def hbmc_trisolve_fused(cols: jax.Array, vals: jax.Array, dinv: jax.Array,
                        q: jax.Array, *, interpret: bool | None = None
                        ) -> jax.Array:
    """z = (L L^T)^{-1} q in round-major coordinates, one kernel launch.

    Args:
      cols: (2S, R, K) int32 — forward round-major gather positions; rows
        0..S-1 are the forward rounds, S..2S-1 the backward rounds in
        backward execution order (``sell.stack_sweeps`` of the one
        segment of ``sell.fuse_round_major(..., max_segments=1)``).
      vals: (2S, R, K) — off-diagonal values (0 on padding).
      dinv: (2S, R) — inverse diagonal (0 on padding lanes).
      q:    (S, R) — right-hand side in round-major layout.

    Returns:
      z: (S*R,) solution in round-major layout (holes stay 0).
    """
    s2, r_, _ = cols.shape
    if q.shape != (s2 // 2, r_):
        raise ValueError(f"q shape {q.shape} != rounds shape {(s2 // 2, r_)}")
    return _sweep(cols, vals, dinv, q, s_fwd=s2 // 2,
                  interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def hbmc_trisolve_fused_batched(cols: jax.Array, vals: jax.Array,
                                dinv: jax.Array, q: jax.Array, *,
                                interpret: bool | None = None) -> jax.Array:
    """Multi-RHS fused solve.  q: (S, R, B) -> z: (S*R, B), one fused
    sweep per column."""
    s2, r_, _ = cols.shape
    b_ = q.shape[-1]
    if q.shape != (s2 // 2, r_, b_):
        raise ValueError(f"q shape {q.shape} != {(s2 // 2, r_, b_)}")
    return _per_column(functools.partial(hbmc_trisolve_fused, cols, vals,
                                         dinv, interpret=interpret), q)
