"""Pallas TPU kernels for the paper's compute hot-spots.

hbmc_trisolve — the HBMC forward/backward substitution (the paper's core
kernel, Fig 4.6 TPU adaptation): round-major layout, sequential grid over
rounds, VMEM-resident solution vector, a staged-row gather Mosaic lowers,
contiguous stores.

sell_spmv — SELL-w sparse matrix-vector product family (paper §5.2):
single-RHS, batched multi-RHS (one product per column), and the
shard_map-compatible per-device block variant consumed by the
mesh-sharded SpMV.

Both compile for TPU v5e (f32) and run compiled there
(tests/test_tpu_compile.py, chip_smoke.py).

Both families ship ref.py pure-jnp oracles (bitwise in interpret mode) and
the same interpret-by-backend defaulting (config.resolve_interpret), and
are validated across (shape, w, dtype, batch) sweeps
(tests/test_trisolve.py, tests/test_spmv.py).
"""
from .config import DEFAULT_SLICE_TILE, default_interpret, resolve_interpret
from .hbmc_trisolve import (hbmc_trisolve, hbmc_trisolve_batched,
                            hbmc_trisolve_fused, hbmc_trisolve_fused_batched)
from .sell_spmv import sell_spmv, sell_spmv_batched, sell_spmv_block
from .ops import DeviceRoundMajorTables, build_kernel_preconditioner
from .ref import (hbmc_trisolve_batched_ref, hbmc_trisolve_fused_batched_ref,
                  hbmc_trisolve_fused_ref, hbmc_trisolve_ref,
                  sell_spmv_batched_ref, sell_spmv_ref)
