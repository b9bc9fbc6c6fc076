"""Peak table and the least bytes one PCG iteration has to move.

Why the bound is bandwidth.  One preconditioned CG iteration does one
SpMV (2 flops per stored nonzero), two triangular sweeps (2 flops per
factor nonzero each) and about ten vector operations of 1 to 2 flops per
row.  Against the bytes counted below that is about 0.2 flop per byte in
f32, three orders of magnitude under a TPU v5e's ridge point (197e12
flop/s / 819e9 B/s = 240 flop/B).  So the least time of an iteration is
its least bytes over the peak bandwidth, and ``iter_roofline`` is

    least_bytes_per_iteration(n, nnz, dtype) * iterations
    ------------------------------------------------------   (in %)
          peak HBM bandwidth * device busy seconds

The byte count uses only the configuration's ``n``, ``nnz`` (the full
symmetric matrix, diagonal included) and value type, never the plan's
packed or padded tables, so no layout, padding or lane multiple can move
it.  It counts what any implementation that streams a general sparse
matrix from device memory must read or write once per iteration:

* SpMV: the matrix as one triangle with its diagonal,
  ``(nnz + n) / 2`` values, plus one 32-bit column index for each of the
  ``(nnz - n) / 2`` off-diagonal entries (the diagonal needs no index, and
  row pointers are left out: a count that errs low keeps the share under
  100%);
* the two sweeps: the IC(0) factor has the lower triangle's pattern, so
  each of the forward and the backward sweep reads the same again;
* vectors: ten streams of ``n`` values.  The iteration updates x, r and p
  in place (a read and a write each) and makes q = A p and z = M^-1 r,
  each written once and read once.  Dot products fuse into the passes
  that produce their operands.

An implementation that keeps the matrix or the factor resident in
on-chip memory across iterations would move fewer bytes than this, and
would read over 100%; no such implementation exists in the program.
"""
from __future__ import annotations

import numpy as np

INDEX_BYTES = 4
VECTOR_STREAMS = 10

# Published peaks per chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture).
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/yardstick.py "
                       f"with their source") from None


def triangle_bytes(n: int, nnz: int, value_bytes: int) -> int:
    """One triangle of a symmetric n x n matrix with ``nnz`` nonzeros,
    diagonal included: its values plus an index per off-diagonal entry."""
    if (nnz - n) % 2:
        raise ValueError(f"nnz={nnz} with n={n} is not a symmetric "
                         f"pattern with a full diagonal")
    off = (nnz - n) // 2
    return (off + n) * value_bytes + off * INDEX_BYTES


def least_bytes_per_iteration(n: int, nnz: int, dtype) -> int:
    """Least bytes one PCG iteration with an IC(0) preconditioner moves
    (module docstring): SpMV triangle + two factor sweeps + vectors."""
    vb = np.dtype(dtype).itemsize
    return 3 * triangle_bytes(n, nnz, vb) + VECTOR_STREAMS * n * vb
