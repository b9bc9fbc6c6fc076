"""The least bytes of one PCG iteration, kernel by kernel.

``bench.yardstick.least_bytes_per_iteration`` is the sum of these three
addends (its docstring gives the argument for each); split, they give a
roofline share per kernel: the bytes of one kernel's call over the peak
bandwidth times that call's seconds in the trace (``bench.scopes``).
"""
from __future__ import annotations

import numpy as np

from bench.yardstick import VECTOR_STREAMS, triangle_bytes


def spmv_least_bytes(n: int, nnz: int, dtype) -> int:
    """One SpMV: the matrix as one triangle with its diagonal."""
    return triangle_bytes(n, nnz, np.dtype(dtype).itemsize)


def sweep_least_bytes(n: int, nnz: int, dtype) -> int:
    """One preconditioner apply: the IC(0) factor, read by the forward
    and again by the backward sweep."""
    return 2 * triangle_bytes(n, nnz, np.dtype(dtype).itemsize)


def vector_least_bytes(n: int, dtype) -> int:
    """One iteration's vector work: ``VECTOR_STREAMS`` streams of n."""
    return VECTOR_STREAMS * n * np.dtype(dtype).itemsize
