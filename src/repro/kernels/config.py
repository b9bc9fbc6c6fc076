"""Shared kernel configuration helpers.

All kernel entry points take ``interpret=None`` and resolve it here:
compiled on TPU, interpreted everywhere else (CPU/GPU development and CI).
Both kernel families compile for TPU v5e in f32 (Mosaic has no f64) and
have run compiled on one (``chip_smoke.py``); f64 runs only interpreted.
"""
from __future__ import annotations

import jax

# The TPU vector register is SUBLANES x LANES 32-bit words; kernel layouts
# put the long axis (lanes of a round, rows of a matrix) on LANES.
LANES = 128
SUBLANES = 8

# Scoped VMEM each kernel may use (v5e has 128 MiB per core; the compiler's
# default scope is 16 MiB).  Must equal analysis.kernel_checks'
# VMEM_BUDGET_BYTES, which proves plans fit it without importing jax.
VMEM_LIMIT_BYTES = 64 * 2**20

# Slices per grid step of the SELL SpMV kernels (VMEM tile height): one
# tile is slice_tile * K * w values + as many int32 columns — ~0.5 MiB at
# the production K <= 32, w = 8, f32, far below VMEM alongside the
# resident x vector.
DEFAULT_SLICE_TILE = 256


def default_interpret() -> bool:
    """True iff Pallas kernels should run in interpret mode (no TPU)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve an ``interpret`` argument: ``None`` -> backend default."""
    return default_interpret() if interpret is None else bool(interpret)
