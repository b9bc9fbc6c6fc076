"""Pallas TPU kernel family for SELL-w sparse matrix-vector products (§5.2).

SELL-C-sigma with C = w: each slice holds w rows column-major
(``(n_slices, K, w)``).  The kernels re-lay the operands out for the TPU
as ``(K, rows / 128, 128)`` — one lane-dense plane per slot k, matrix rows
on the 128-wide lane axis — and tile the rows over the grid; x stays
VMEM-resident for gathers (same residency argument as the trisolve
kernel), and the gather is the staged-row gather of
``hbmc_trisolve.gather_rows``.  Slices are zero-padded to the slice-max
row length, matching the paper's SELL cost model (the Audikw_1
40%-padding discussion in §5.2.2 is reproduced by
``benchmarks/bench_trisolve.py`` via the padded_nnz counter).

Three entry points sharing one kernel body:

  * ``sell_spmv``          — single RHS, x (n_pad,) -> y (n_slices*w,)
  * ``sell_spmv_batched``  — B RHS, x (n_pad, B) -> y (n_slices*w, B), one
    single-RHS product per column
  * ``sell_spmv_block``    — shard_map-compatible per-device block variant:
    consumes the LOCAL slice shard of the operands plus the replicated
    vector and returns the local row block (no slicing to n — the caller
    all-gathers; see ``core.iccg.make_sharded_spmv``)

All outputs are in slice-row-major order, padded to ``n_slices * w`` rows;
callers slice to the matrix dimension (``core.plan._make_spmv`` does).
Against zero-padded ``vals`` the gather (indices past x read 0, as
``jnp.take(..., fill_value=0)`` does) makes padding lanes contribute exact
zeros, and the K-reduction runs in k order, so results match the jnp
oracles in ``ref.py`` and the XLA ``spmv_sell`` path bit for bit in
interpret mode (asserted in tests/test_spmv.py).  ``interpret`` defaults
from the backend (``config.resolve_interpret``): compiled on TPU,
interpreted elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .config import (DEFAULT_SLICE_TILE, LANES, SUBLANES, VMEM_LIMIT_BYTES,
                     resolve_interpret)
from .hbmc_trisolve import _Z, gather_rows


def _sell_spmv_kernel(idx_ref, cols_ref, vals_ref, x_ref, y_ref, stage_ref,
                      *, n_x: int):
    k_, tile, wc = cols_ref.shape

    def row(i, carry):
        g_planes = jnp.stack([
            gather_rows(idx_ref, (k * tile + i) * wc,
                        cols_ref[k, pl.ds(i, 1), :], x_ref, stage_ref, n_x)
            for k in range(k_)])                              # (K, 1, wc)
        y_ref[pl.ds(i, 1), :] = jnp.sum(
            vals_ref[:, pl.ds(i, 1), :] * g_planes, axis=0)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(tile), row, None)


def _row_tile(slice_tile: int, w: int) -> int:
    """Rows of LANES matrix rows per grid step: ``slice_tile`` slices,
    rounded up to whole (8, 128) tiles."""
    rows = -(-slice_tile * w // LANES)
    return -(-rows // SUBLANES) * SUBLANES


@functools.partial(jax.jit, static_argnames=("slice_tile", "interpret"))
def sell_spmv(vals: jax.Array, cols: jax.Array, x: jax.Array,
              *, slice_tile: int = DEFAULT_SLICE_TILE,
              interpret: bool | None = None) -> jax.Array:
    """y = A x with A in SELL-w layout.

    Args:
      vals: (n_slices, K, w) slice-packed values (0 padding).
      cols: (n_slices, K, w) int32 column indices (padding -> any index whose
        vals entry is 0; indices past x read 0).
      x:    (n_pad,) input vector.
      slice_tile: slices per grid step (rounded up to whole vreg tiles).

    Returns:
      y: (n_slices * w,) in slice-row-major order.
    """
    interpret = resolve_interpret(interpret)
    n_slices, k_, w_ = vals.shape
    n_rows = n_slices * w_
    tile = _row_tile(slice_tile, w_)
    n_t = -(-n_rows // (tile * LANES))
    pad = n_t * tile * LANES - n_rows

    def planes(a):        # (n_slices, K, w) -> (K, rows / LANES, LANES)
        a = jnp.swapaxes(a, 0, 1).reshape(k_, n_rows)
        return jnp.pad(a, ((0, 0), (0, pad))).reshape(k_, -1, LANES)

    vals_k, cols_k = planes(vals), planes(cols)
    idx = (cols_k.reshape(k_, n_t, tile * LANES).transpose(1, 0, 2)
           .reshape(n_t, 1, -1))
    n_x = x.shape[0]
    x2 = jnp.pad(x, (0, -n_x % LANES)).reshape(-1, LANES)
    y = pl.pallas_call(
        functools.partial(_sell_spmv_kernel, n_x=n_x),
        grid=(n_t,),
        in_specs=[
            pl.BlockSpec((1, 1, k_ * tile * LANES), lambda t: (t, _Z, _Z),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((k_, tile, LANES), lambda t: (_Z, t, _Z)),
            pl.BlockSpec((k_, tile, LANES), lambda t: (_Z, t, _Z)),
            pl.BlockSpec(x2.shape, lambda t: (_Z, _Z)),  # x fully resident
        ],
        out_specs=pl.BlockSpec((tile, LANES), lambda t: (t, _Z)),
        out_shape=jax.ShapeDtypeStruct((n_t * tile, LANES), vals.dtype),
        scratch_shapes=[pltpu.VMEM((LANES, LANES), vals.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(idx, cols_k, vals_k, x2)
    return y.reshape(-1)[:n_rows]


@functools.partial(jax.jit, static_argnames=("slice_tile", "interpret"))
def sell_spmv_batched(vals: jax.Array, cols: jax.Array, x: jax.Array,
                      *, slice_tile: int = DEFAULT_SLICE_TILE,
                      interpret: bool | None = None) -> jax.Array:
    """Y = A X for B column vectors.  x: (n_pad, B).

    One ``sell_spmv`` per column, so each column's K-reduction is exactly
    the single-RHS one and batched and single-RHS PCG arithmetic stay
    identical.

    Returns:
      y: (n_slices * w, B) in slice-row-major order.
    """
    return jnp.stack([sell_spmv(vals, cols, x[:, b], slice_tile=slice_tile,
                                interpret=interpret)
                      for b in range(x.shape[-1])], axis=-1)


def sell_spmv_block(vals: jax.Array, cols: jax.Array, x: jax.Array,
                    *, slice_tile: int = DEFAULT_SLICE_TILE,
                    interpret: bool | None = None) -> jax.Array:
    """Per-device block SpMV for use inside ``shard_map``.

    ``vals``/``cols`` are the device-LOCAL slice shard ((s_loc, K, w));
    ``x`` is the replicated input vector ((n_pad,) or (n_pad, B)) indexed
    by GLOBAL positions, so the local gather needs no index translation.
    Returns the local row block ((s_loc * w,) or (s_loc * w, B)) — the
    caller assembles the full result with one tiled all-gather
    (``core.iccg.make_sharded_spmv``), mirroring the xla sharded path.
    """
    if x.ndim == 2:
        return sell_spmv_batched(vals, cols, x, slice_tile=slice_tile,
                                 interpret=interpret)
    return sell_spmv(vals, cols, x, slice_tile=slice_tile,
                     interpret=interpret)
