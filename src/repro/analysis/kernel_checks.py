"""Static Pallas kernel checks for the hbmc_trisolve / sell_spmv families.

The kernels (``repro.kernels``) assume a handful of static properties of
their packed operands that, when violated, fail only at dispatch time (or
worse, silently on TPU where an out-of-tile index wraps).  These checks
prove them on the host before any ``pallas_call``:

  * **shape/grid consistency** — the fused trisolve runs ``2S`` steps
    over ``(2S, R, K)`` operands (two mirrored sweeps), the SELL kernel
    takes ``(n_slices, K, w)`` operands;
  * **index-map bounds** — every gather index a kernel can read with a
    nonzero value must land inside the VMEM-resident vector (the
    ``fill_value=0`` guard is only correct when paired with zero values);
  * **VMEM footprint** — the kernel's working set (the resident vector
    and the per-step blocks, both double-buffered by the pipeline, plus
    the gather's staging block) against the scoped VMEM the kernels
    request, with the estimate returned so callers can rescale.

Checks return :class:`repro.analysis.schedule.Violation` lists (empty =
clean) so the CLI prints one witness format for schedule and kernel
findings alike.  The budget is the ``vmem_limit_bytes`` every kernel asks
the compiler for (``kernels.config.VMEM_LIMIT_BYTES``; a v5e core has
128 MiB of VMEM).
"""
from __future__ import annotations

import numpy as np

from .schedule import MAX_VIOLATIONS, ScheduleError, Violation

#: Scoped VMEM per kernel (bytes) — equal to
#: ``kernels.config.VMEM_LIMIT_BYTES``, the limit the kernels request.
VMEM_BUDGET_BYTES = 64 * 2**20

#: Mirror kernels.config (LANES, SUBLANES, DEFAULT_SLICE_TILE) without
#: importing jax.
LANES, SUBLANES = 128, 8
DEFAULT_SLICE_TILE = 256


def trisolve_fused_vmem_bytes(s2: int, r: int, k: int,
                              itemsize: int) -> int:
    """Working set of the fused-trisolve kernel, in bytes.

    Resident: the output y (S*R).  Per grid step: one lane tile (T lanes)
    of cols (int32) and vals, K planes each, plus its dinv and q rows.
    Both are double-buffered by the pipeline.  Scratch: the (wc, wc)
    staging block of the gather.  T and wc follow the kernel: rows of
    wc = 128 lanes when R allows, tiles of 8 such rows when the round has
    a multiple of 8, else the whole round.
    """
    wc = LANES if r % LANES == 0 else r
    rows = r // wc
    t = (SUBLANES if rows % SUBLANES == 0 else rows) * wc
    resident = (s2 // 2) * r * itemsize
    per_step = t * k * (4 + itemsize) + 2 * t * itemsize
    return 2 * (resident + per_step) + wc * wc * itemsize


def sell_spmv_vmem_bytes(t: int, k: int, w: int, n_pad: int,
                         itemsize: int) -> int:
    """Working set of the SELL SpMV kernel, in bytes: the resident x
    (n_pad, padded to whole 128-lane rows), per grid step the cols and
    vals planes of ``t`` slices (rounded up to whole (8, 128) tiles) and
    the output tile — all double-buffered — plus the (128, 128) staging
    block of the gather."""
    rows = -(-t * w // LANES)
    step_rows = -(-rows // SUBLANES) * SUBLANES * LANES
    resident = -(-n_pad // LANES) * LANES * itemsize
    per_step = step_rows * (k * (4 + itemsize) + itemsize)
    return 2 * (resident + per_step) + LANES * LANES * itemsize


def check_trisolve_fused(cols, vals, dinv,
                         vmem_budget: int = VMEM_BUDGET_BYTES,
                         where: str = "kernel/hbmc_trisolve_fused"
                         ) -> list[Violation]:
    """Static checks for ``kernels.hbmc_trisolve.hbmc_trisolve_fused``
    (and its batched variant) against packed fused tables."""
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    dinv = np.asarray(dinv)
    out: list[Violation] = []
    if cols.ndim != 3 or cols.shape != vals.shape:
        out.append(Violation(
            kind="shape-mismatch", where=where,
            detail=f"cols {cols.shape} vs vals {vals.shape}; expected "
                   f"matching (2S, R, K)"))
        return out
    s2, r_, k_ = cols.shape
    if dinv.shape != (s2, r_):
        out.append(Violation(
            kind="shape-mismatch", where=where,
            detail=f"dinv {dinv.shape} != {(s2, r_)}"))
        return out
    if s2 % 2:
        # grid (2S,) with the fwd/bwd halves mirrored: odd step counts
        # cannot split into two sweeps
        out.append(Violation(
            kind="grid-divisibility", where=where,
            detail=f"fused step axis {s2} is odd; expected 2*S"))
        return out
    m = (s2 // 2) * r_
    if not np.issubdtype(cols.dtype, np.integer):
        out.append(Violation(
            kind="index-dtype", where=where,
            detail=f"cols dtype {cols.dtype} is not integral"))
        return out
    oob = (cols < 0) | (cols > m)
    if oob.any():
        g, t, k = (int(x) for x in np.argwhere(oob)[0])
        out.append(Violation(
            kind="index-bounds", where=where, round=g,
            detail=f"cols[{g},{t},{k}] = {int(cols[g, t, k])} outside the "
                   f"kernel's gather domain [0, {m}] (fill_value pad is "
                   f"exactly {m})"))
    live_oob = (cols == m) & (vals != 0)
    if live_oob.any():
        g, t, k = (int(x) for x in np.argwhere(live_oob)[0])
        out.append(Violation(
            kind="index-bounds", where=where, round=g,
            detail=f"vals[{g},{t},{k}] != 0 on the fill_value pad "
                   f"position — the guarded read would drop a real "
                   f"contribution"))
    need = trisolve_fused_vmem_bytes(s2, r_, k_, vals.dtype.itemsize)
    if need > vmem_budget:
        out.append(Violation(
            kind="vmem-budget", where=where,
            detail=f"working set ~{need / 2**20:.1f} MiB exceeds "
                   f"the {vmem_budget / 2**20:.1f} MiB budget (S={s2 // 2}, "
                   f"R={r_}, K={k_}); shard rounds across devices"))
    return out[:MAX_VIOLATIONS]


def check_sell_spmv(vals, cols, n_pad: int,
                    slice_tile: int = DEFAULT_SLICE_TILE,
                    vmem_budget: int = VMEM_BUDGET_BYTES,
                    where: str = "kernel/sell_spmv") -> list[Violation]:
    """Static checks for the ``kernels.sell_spmv`` family against a packed
    SELL operand; ``n_pad`` is the length of the VMEM-resident x vector."""
    vals = np.asarray(vals)
    cols = np.asarray(cols)
    out: list[Violation] = []
    if vals.ndim != 3 or cols.shape != vals.shape:
        out.append(Violation(
            kind="shape-mismatch", where=where,
            detail=f"cols {cols.shape} vs vals {vals.shape}; expected "
                   f"matching (n_slices, K, w)"))
        return out
    n_slices, k_, w_ = vals.shape
    if slice_tile < 1:
        out.append(Violation(
            kind="grid-divisibility", where=where,
            detail=f"slice_tile {slice_tile} < 1"))
        return out
    if not np.issubdtype(cols.dtype, np.integer):
        out.append(Violation(
            kind="index-dtype", where=where,
            detail=f"cols dtype {cols.dtype} is not integral"))
        return out
    # the kernel pads the rows to whole grid tiles, so the grid always
    # divides; what CAN go wrong is a live gather index outside the
    # resident x (read as 0 — a dropped term)
    t = min(slice_tile, n_slices)
    live = vals != 0
    bad = live & ((cols < 0) | (cols >= n_pad))
    if bad.any():
        s, k, w = (int(x) for x in np.argwhere(bad)[0])
        out.append(Violation(
            kind="index-bounds", where=where, round=s // max(t, 1),
            detail=f"cols[{s},{k},{w}] = {int(cols[s, k, w])} with a "
                   f"nonzero value, outside x's domain [0, {n_pad}) — the "
                   f"fill_value guard would silently drop this term"))
    need = sell_spmv_vmem_bytes(t, k_, w_, n_pad, vals.dtype.itemsize)
    if need > vmem_budget:
        out.append(Violation(
            kind="vmem-budget", where=where,
            detail=f"working set ~{need / 2**20:.1f} MiB exceeds "
                   f"the {vmem_budget / 2**20:.1f} MiB budget "
                   f"(tile={t}, K={k_}, w={w_}, n_pad={n_pad}); "
                   f"lower slice_tile or shard the slice axis"))
    return out[:MAX_VIOLATIONS]


def check_plan_kernels(plan, vmem_budget: int = VMEM_BUDGET_BYTES
                       ) -> list[Violation]:
    """Run the static kernel checks a plan's backend selection implies.

    ``backend="pallas"`` (round-major) routes the preconditioner through
    ``hbmc_trisolve_fused``; ``spmv_backend="pallas"`` routes the SpMV
    through ``sell_spmv``.  XLA-only plans return ``[]`` — their lowering
    has no static kernel contract to break.
    """
    out: list[Violation] = []
    if plan.backend == "pallas" and plan.layout == "round_major":
        out += check_trisolve_fused(*plan._precond.tables.stacked(),
                                    vmem_budget=vmem_budget)
    if plan.spmv_backend == "pallas":
        out += check_sell_spmv(plan._spmv_vals, plan._spmv_cols,
                               n_pad=int(plan.slab_m),
                               vmem_budget=vmem_budget)
    return out


def assert_plan_kernels(plan, vmem_budget: int = VMEM_BUDGET_BYTES,
                        context: str = "") -> None:
    violations = check_plan_kernels(plan, vmem_budget=vmem_budget)
    if violations:
        raise ScheduleError(violations, context=context)
