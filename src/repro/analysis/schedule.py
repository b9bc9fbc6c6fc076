"""Static schedule race detector: prove a plan race-free before dispatch.

Every parallel claim of the paper reduces to a static property of the
schedule.  The dependency DAG of a triangular factor L has an edge
``j -> i`` for every strictly-lower nonzero ``L[i, j]``: row ``i``'s
substitution reads ``y[j]``, so ``j`` must be *finished* first.  A round
schedule (MC / BMC / HBMC rounds, or any future scheduler backend) is legal
iff every edge crosses strictly forward in round order — which implies both
halves of the paper's claim at once:

  * every round is an **antichain** of the DAG (no intra-round edge:
    rows of one round are mutually independent, eq. 4.1), and
  * every step **reads only earlier-round writes** (the per-round barrier
    is the only synchronization the sweep needs).

The checkers here verify that property at three levels of materialization:

  ``check_rounds``         the ordering's round sets against the CSR
                           pattern (the O(nnz) "cheap" proof)
  ``check_step_tables``    the packed per-round gather tables
                           (``sell.StepTables`` — what the XLA sweep runs)
  ``check_fused_tables``   the segmented fwd+bwd round-major tables
                           (``sell.FusedRoundMajorTables`` — what the
                           round-major sweeps, sharded or not, and the
                           Pallas kernel run)
  ``check_ic0_structure``  the IC(0) factorization step schedule
                           (``ic0.IC0Structure`` — the setup pipeline)

All checkers return a list of machine-readable :class:`Violation` witnesses
(empty = proven clean) instead of a bare bool, so a failure names the exact
offending row pair / DAG edge / round.  ``validate_plan`` composes them for
a built ``SolverPlan`` (the ``validate=`` knob of ``build_plan``), and
``python -m repro.analysis`` runs them from the command line.

Everything here is host-side numpy on host-side (or host-copied) tables:
no jax import, so ``core.plan`` can defer-import this module without a
cycle.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp

#: Checkers stop collecting after this many witnesses per artifact: the
#: point of a witness is to pinpoint, not to enumerate every consequence of
#: one corrupted round.
MAX_VIOLATIONS = 16


@dataclasses.dataclass(frozen=True)
class Violation:
    """One schedule/contract defect, pinned to its witness.

    ``kind``   what property failed (e.g. ``"intra-round-edge"``)
    ``where``  which artifact it was found in (``"rounds"``,
               ``"step_tables"``, ``"fused_tables"``, ``"ic0_steps"``,
               ``"kernel"``, ...)
    ``round``  the offending round / step / grid index, when applicable
    ``rows``   the offending row pair ``(i, j)`` in the checked ordering
    ``edge``   the offending DAG edge ``(src, dst)`` (src must finish
               before dst may start) or table-position pair
    ``detail`` human-readable one-liner
    """
    kind: str
    where: str
    round: int | None = None
    rows: tuple | None = None
    edge: tuple | None = None
    detail: str = ""

    def __str__(self) -> str:
        bits = [f"{self.where}: {self.kind}"]
        if self.round is not None:
            bits.append(f"round={self.round}")
        if self.rows is not None:
            bits.append(f"rows={tuple(int(x) for x in self.rows)}")
        if self.edge is not None:
            bits.append(f"edge={tuple(int(x) for x in self.edge)}")
        if self.detail:
            bits.append(f"({self.detail})")
        return " ".join(bits)


class ScheduleError(ValueError):
    """A schedule failed static validation.  Carries the machine-readable
    ``violations`` list; the message shows the first few witnesses."""

    def __init__(self, violations: list[Violation], context: str = ""):
        self.violations = list(violations)
        head = "; ".join(str(v) for v in self.violations[:4])
        more = len(self.violations) - 4
        if more > 0:
            head += f"; ... {more} more"
        prefix = f"{context}: " if context else ""
        super().__init__(f"{prefix}schedule validation failed "
                         f"[{len(self.violations)} violation(s)]: {head}")


def _strict_lower_edges(a: sp.spmatrix) -> tuple[np.ndarray, np.ndarray]:
    """Dependency edges (src=j, dst=i) of the forward sweep: one per
    strictly-lower nonzero a[i, j]."""
    low = sp.tril(sp.csr_matrix(a), k=-1, format="coo")
    return low.col.astype(np.int64), low.row.astype(np.int64)


def check_rounds(a_bar: sp.spmatrix, rounds: list[np.ndarray],
                 drop_mask: np.ndarray | None = None,
                 where: str = "rounds") -> list[Violation]:
    """Prove ``rounds`` is a legal forward schedule for ``a_bar``.

    ``rounds`` are execution-ordered row sets of the (already ordered /
    padded) matrix; ``drop_mask`` marks rows excluded from the schedule
    (dummy padding).  O(nnz + n): one pass to build the row -> round map,
    one vectorized scan over the strictly-lower pattern.  This is exactly
    the ``validate="cheap"`` proof — forward-crossing edges imply both the
    antichain property and read-only-earlier-writes.
    """
    n = a_bar.shape[0]
    out: list[Violation] = []
    round_id = np.full(n, -1, dtype=np.int64)
    for s, r in enumerate(rounds):
        r = np.asarray(r)
        if len(r) and (r.min() < 0 or r.max() >= n):
            bad = int(r[(r < 0) | (r >= n)][0])
            out.append(Violation(
                kind="row-out-of-range", where=where, round=s,
                rows=(bad, bad),
                detail=f"round {s} schedules row {bad} outside [0, {n})"))
            if len(out) >= MAX_VIOLATIONS:
                return out
            r = r[(r >= 0) & (r < n)]
        uniq, counts = np.unique(r, return_counts=True)
        dup = np.concatenate([uniq[counts > 1], r[round_id[r] >= 0]])
        if len(dup):
            i = int(dup[0])
            prev = int(round_id[i]) if round_id[i] >= 0 else s
            out.append(Violation(
                kind="duplicate-row", where=where, round=s, rows=(i, i),
                detail=f"row {i} scheduled in rounds {prev} and {s}"))
            if len(out) >= MAX_VIOLATIONS:
                return out
        round_id[r] = s
    unsched = np.flatnonzero(round_id < 0)
    if drop_mask is not None:
        unsched = unsched[~drop_mask[unsched]]
    for i in unsched[:MAX_VIOLATIONS - len(out)]:
        out.append(Violation(
            kind="unscheduled-row", where=where, rows=(int(i), int(i)),
            detail=f"row {int(i)} appears in no round"))
    if len(out) >= MAX_VIOLATIONS:
        return out

    src, dst = _strict_lower_edges(a_bar)
    rs, rd = round_id[src], round_id[dst]
    live = (rs >= 0) & (rd >= 0)   # unscheduled endpoints already reported,
    # unless they were dropped rows — a dropped row carrying a dependency
    # edge is a silent read of a never-computed value:
    if drop_mask is not None:
        dropped_edge = np.flatnonzero(
            (~live) & (drop_mask[src] | drop_mask[dst]))
        for e in dropped_edge[:MAX_VIOLATIONS - len(out)]:
            out.append(Violation(
                kind="unscheduled-dependency", where=where,
                rows=(int(dst[e]), int(src[e])),
                edge=(int(src[e]), int(dst[e])),
                detail="dependency edge touches a row dropped from the "
                       "schedule"))
        if len(out) >= MAX_VIOLATIONS:
            return out
    bad_same = np.flatnonzero(live & (rs == rd))
    for e in bad_same[:MAX_VIOLATIONS - len(out)]:
        out.append(Violation(
            kind="intra-round-edge", where=where, round=int(rs[e]),
            rows=(int(dst[e]), int(src[e])),
            edge=(int(src[e]), int(dst[e])),
            detail=f"rows {int(src[e])} and {int(dst[e])} share round "
                   f"{int(rs[e])} but are connected — not an antichain"))
    if len(out) >= MAX_VIOLATIONS:
        return out
    bad_order = np.flatnonzero(live & (rs > rd))
    for e in bad_order[:MAX_VIOLATIONS - len(out)]:
        out.append(Violation(
            kind="cross-round-order", where=where, round=int(rd[e]),
            rows=(int(dst[e]), int(src[e])),
            edge=(int(src[e]), int(dst[e])),
            detail=f"row {int(dst[e])} (round {int(rd[e])}) reads row "
                   f"{int(src[e])} written later (round {int(rs[e])})"))
    return out


def check_reversed_rounds(fwd_rounds: list[np.ndarray],
                          bwd_rounds: list[np.ndarray],
                          where: str = "rounds") -> list[Violation]:
    """The backward schedule must be the reversed forward schedule (lane
    order included) — the property ``fuse_round_major`` builds on.  A legal
    forward schedule then implies a legal backward one (same DAG, reversed)."""
    if len(fwd_rounds) != len(bwd_rounds):
        return [Violation(
            kind="round-count-mismatch", where=where,
            detail=f"{len(fwd_rounds)} forward vs {len(bwd_rounds)} "
                   f"backward rounds")]
    out = []
    for s, (f, b) in enumerate(zip(fwd_rounds, reversed(bwd_rounds))):
        if not np.array_equal(np.asarray(f), np.asarray(b)):
            out.append(Violation(
                kind="backward-not-reversed", where=where, round=s,
                detail="backward rounds are not the reversed forward "
                       "rounds (lane order included)"))
            if len(out) >= MAX_VIOLATIONS:
                break
    return out


def _table_arrays(t) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """(rows, cols, vals, n_slots) as host numpy from host or device tables."""
    return (np.asarray(t.rows), np.asarray(t.cols), np.asarray(t.vals),
            int(t.n_slots))


def check_step_tables(tables, tri: sp.spmatrix | None = None,
                      where: str = "step_tables") -> list[Violation]:
    """Verify materialized per-round gather tables (``sell.StepTables`` or
    ``trisolve.DeviceTables``) read only earlier-round writes.

    Checks, per step ``s``: every non-pad column index is a row assigned to
    a strictly earlier step (the packed form of the DAG proof), pad columns
    carry zero values, and indices stay in ``[0, n_slots)``.  With ``tri``
    (the strictly-triangular matrix the tables were packed from) it also
    proves **coverage**: every nonzero of ``tri`` whose row is scheduled
    appears in the tables — a silently dropped dependency is as much a race
    as a misordered one.
    """
    rows, cols, vals, n_slots = _table_arrays(tables)
    s_, r_ = rows.shape
    pad = n_slots - 1
    out: list[Violation] = []

    oob = (cols < 0) | (cols >= n_slots)
    if oob.any():
        s, t, k = (int(x) for x in np.argwhere(oob)[0])
        out.append(Violation(
            kind="index-out-of-range", where=where, round=s,
            detail=f"cols[{s},{t},{k}] = {int(cols[s, t, k])} outside "
                   f"[0, {n_slots})"))
    pad_val = (cols == pad) & (vals != 0)
    if pad_val.any():
        s, t, k = (int(x) for x in np.argwhere(pad_val)[0])
        out.append(Violation(
            kind="nonzero-pad-value", where=where, round=s,
            detail=f"vals[{s},{t},{k}] = {vals[s, t, k]!r} on the scratch "
                   f"pad slot"))

    step_of = np.full(n_slots, -1, dtype=np.int64)
    live = rows != pad
    uniq, counts = np.unique(rows[live], return_counts=True)
    for i in uniq[counts > 1][:MAX_VIOLATIONS - len(out)]:
        out.append(Violation(
            kind="duplicate-row", where=where, rows=(int(i), int(i)),
            detail=f"row {int(i)} assigned to multiple lanes"))
    step_idx = np.broadcast_to(np.arange(s_)[:, None], rows.shape)
    step_of[rows[live]] = step_idx[live]

    # every live (vals != 0, non-pad) gather must hit a row written earlier
    gather = (cols != pad) & (vals != 0)
    src_step = np.where(gather, step_of[np.minimum(cols, pad)], -2)
    reader_step = np.broadcast_to(np.arange(s_)[:, None, None], cols.shape)
    never = gather & (src_step == -1)
    late = gather & (src_step >= reader_step)
    for mask, kind, fmt in (
            (never, "unscheduled-dependency",
             "reads row {src} which is never written"),
            (late, "premature-read",
             "reads row {src} (step {ss}) at step {s}")):
        for s, t, k in np.argwhere(mask)[:MAX_VIOLATIONS - len(out)]:
            s, t, k = int(s), int(t), int(k)
            src = int(cols[s, t, k])
            dst = int(rows[s, t])
            out.append(Violation(
                kind=kind, where=where, round=s, rows=(dst, src),
                edge=(src, dst),
                detail=fmt.format(src=src, s=s,
                                  ss=int(step_of[src]))))
        if len(out) >= MAX_VIOLATIONS:
            return out

    if tri is not None:
        tri = sp.csr_matrix(tri)
        tri.sort_indices()
        packed = set(zip(rows[:, :, None].repeat(
            cols.shape[-1], axis=-1)[gather].tolist(),
            cols[gather].tolist()))
        coo = tri.tocoo()
        for i, j, v in zip(coo.row, coo.col, coo.data):
            if v == 0 or step_of[i] < 0:
                continue
            if (int(i), int(j)) not in packed:
                out.append(Violation(
                    kind="dropped-dependency", where=where,
                    rows=(int(i), int(j)), edge=(int(j), int(i)),
                    detail=f"pattern entry ({int(i)}, {int(j)}) missing "
                           f"from the packed tables"))
                if len(out) >= MAX_VIOLATIONS:
                    break
    return out


def check_fused_tables(fused, where: str = "fused_tables"
                       ) -> list[Violation]:
    """Verify round-major tables (``sell.FusedRoundMajorTables`` or
    ``trisolve.DeviceFusedTables``) are triangular in execution order.

    In flat round-major coordinates segment ``c``'s forward round ``j``
    writes the contiguous slice from ``o_c + j R_c``, and its backward
    step ``j`` the slice of forward round ``n_c - 1 - j``; the forward
    sweep runs the segments in order (steps ``0..S-1``), the backward
    sweep in reverse order (steps ``S..2S-1``).  The race freedom proof is
    positional: every live gather of a forward round must read strictly
    BELOW its destination (already-written ``y``), every live gather of a
    backward round strictly ABOVE it (already-overwritten ``z`` — its
    dependencies), and pad gathers (``cols == m``) must carry zero values
    so the ``fill_value=0`` read is inert.
    """
    segs = [tuple(np.asarray(x) for x in (f.cols, f.vals, b.cols, b.vals))
            for f, b in zip(fused.fwd, fused.bwd)]
    sizes = [tuple(f.dinv.shape) for f in fused.fwd]
    lay = getattr(fused, "layout", None)
    out: list[Violation] = []
    shapes = [(c.shape[0], c.shape[2]) for s in segs for c in (s[0], s[2])]
    if (len(fused.fwd) != len(fused.bwd)
            or shapes != [s for s in sizes for _ in range(2)]
            or (lay is not None and tuple(lay.segments) != tuple(sizes))):
        out.append(Violation(
            kind="shape-mismatch", where=where,
            detail=f"forward segments {sizes} disagree with the backward "
                   f"tables or the layout"))
        return out
    m = sum(n * r for n, r in sizes)
    offsets = np.cumsum([0] + [n * r for n, r in sizes[:-1]])
    dests = [off + np.arange(n * r).reshape(n, r)
             for off, (n, r) in zip(offsets, sizes)]
    # (first global step, cols, vals, destination per lane, half)
    halves, g0 = [], 0
    for (fc, fv, _, _), dest in zip(segs, dests):
        halves.append((g0, fc, fv, dest, "forward"))
        g0 += len(fc)
    for (_, _, bc, bv), dest in reversed(list(zip(segs, dests))):
        halves.append((g0, bc, bv, dest[::-1], "backward"))
        g0 += len(bc)

    for g0, cols, vals, dest, half in halves:
        # cols / vals are (rounds, K, lanes); witnesses name (step, lane, k)
        oob = (cols < 0) | (cols > m)
        if oob.any():
            g, k, t = (int(x) for x in np.argwhere(oob)[0])
            out.append(Violation(
                kind="index-out-of-range", where=where, round=g0 + g,
                detail=f"cols[{g0 + g},{t},{k}] = {int(cols[g, k, t])} "
                       f"outside [0, {m}]"))
        pad_val = (cols == m) & (vals != 0)
        if pad_val.any():
            g, k, t = (int(x) for x in np.argwhere(pad_val)[0])
            out.append(Violation(
                kind="nonzero-pad-value", where=where, round=g0 + g,
                detail=f"vals[{g0 + g},{t},{k}] = {vals[g, k, t]!r} on the "
                       f"out-of-range pad position"))
        live = (vals != 0) & (cols < m)
        if half == "forward":
            bad, word = live & (cols >= dest[:, None, :]), "below"
        else:
            bad, word = live & (cols <= dest[:, None, :]), "above"
        for g, k, t in np.argwhere(bad)[:MAX_VIOLATIONS - len(out)]:
            g, k, t = int(g), int(k), int(t)
            src, dst = int(cols[g, k, t]), int(dest[g, t])
            out.append(Violation(
                kind="premature-read", where=where, round=g0 + g,
                rows=(dst, src), edge=(src, dst),
                detail=f"{half} half gathers position {src} at step "
                       f"{g0 + g}, not strictly {word} its destination "
                       f"{dst}"))
        if len(out) >= MAX_VIOLATIONS:
            return out
    return out


def check_ic0_structure(st, where: str = "ic0_steps") -> list[Violation]:
    """Verify the IC(0) factorization step schedule is dependency-ordered.

    Step ``s`` of ``ic0.IC0Structure`` computes the entry positions
    ``steps[s][0]``; its inner-product operand positions (``pab``) and the
    diagonal of every dividing row (``dep_off``) must all be *computed at a
    strictly earlier step* — otherwise the vectorized batch reads an
    unfactored value.  Also proves every pattern position is computed
    exactly once.
    """
    out: list[Violation] = []
    nnz = int(st.indices.size)
    step_of_pos = np.full(nnz, -1, dtype=np.int64)
    for s, (pos, n_off, dep_off, rows_di, pab, npair, tgt) in \
            enumerate(st.steps):
        pos = np.asarray(pos)
        seen = step_of_pos[pos] >= 0
        for p in pos[seen][:MAX_VIOLATIONS - len(out)]:
            out.append(Violation(
                kind="duplicate-position", where=where, round=s,
                edge=(int(p), int(p)),
                detail=f"entry position {int(p)} computed at steps "
                       f"{int(step_of_pos[p])} and {s}"))
        step_of_pos[pos] = s
    if len(out) >= MAX_VIOLATIONS:
        return out
    missing = np.flatnonzero(step_of_pos < 0)
    for p in missing[:MAX_VIOLATIONS - len(out)]:
        out.append(Violation(
            kind="uncomputed-position", where=where, edge=(int(p), int(p)),
            detail=f"pattern position {int(p)} is never computed"))
    if len(out) >= MAX_VIOLATIONS:
        return out

    diag_pos = st.indptr[1:] - 1    # diagonal entry position of every row
    row_of_pos = np.repeat(np.arange(st.n), np.diff(st.indptr))
    for s, (pos, n_off, dep_off, rows_di, pab, npair, tgt) in \
            enumerate(st.steps):
        pos = np.asarray(pos)
        # off-diagonal entries divide by the diagonal of row dep_off
        if n_off:
            dstep = step_of_pos[diag_pos[np.asarray(dep_off)]]
            bad = np.flatnonzero(dstep >= s)
            for b in bad[:MAX_VIOLATIONS - len(out)]:
                j = int(np.asarray(dep_off)[b])
                i = int(row_of_pos[pos[b]])
                out.append(Violation(
                    kind="premature-read", where=where, round=s,
                    rows=(i, j), edge=(int(diag_pos[j]), int(pos[b])),
                    detail=f"step {s} divides by diag of row {j} computed "
                           f"at step {int(dstep[b])}"))
            if len(out) >= MAX_VIOLATIONS:
                return out
        if npair:
            pab = np.asarray(pab)
            ostep = step_of_pos[pab]
            bad = np.flatnonzero(ostep >= s)
            for b in bad[:MAX_VIOLATIONS - len(out)]:
                op = int(pab[b])
                tpos = int(pos[np.asarray(tgt)[b % npair]])
                out.append(Violation(
                    kind="premature-read", where=where, round=s,
                    rows=(int(row_of_pos[tpos]), int(row_of_pos[op])),
                    edge=(op, tpos),
                    detail=f"step {s} multiplies operand position {op} "
                           f"computed at step {int(ostep[b])}"))
            if len(out) >= MAX_VIOLATIONS:
                return out
    return out


# ---------------------------------------------------------------------------
# Plan-level composition (the validate= knob).
# ---------------------------------------------------------------------------

VALIDATE_MODES = ("off", "cheap", "full", "deep")


def validate_plan(plan, mode: str = "full") -> list[Violation]:
    """Run the race detector against a built ``SolverPlan``.

    ``mode="cheap"`` — the O(nnz) round-monotonicity scan of the ordering's
    rounds against the ordered matrix pattern, plus the
    backward-is-reversed-forward check.  ``mode="full"`` — additionally
    prove the *materialized* schedules: the packed trisolve tables
    (fused round-major or per-sweep index tables, whichever the plan runs)
    and the IC(0) factorization step schedule.  ``mode="deep"`` — on top
    of "full", run the static kernel checks and trace every lowering path
    through the dtype-flow linter (``analysis.dtype_flow``) — the only
    mode that imports jax, so it stays a deferred import and the cheaper
    modes keep working in jax-free contexts.  Returns the violation list
    (empty = proven); raise via :func:`assert_plan_valid`.
    """
    if mode not in VALIDATE_MODES:
        raise ValueError(f"unknown validate mode {mode!r}; expected one of "
                         f"{VALIDATE_MODES}")
    if mode == "off":
        return []
    sysd = plan._sysd
    out = check_rounds(sysd.a_bar, sysd.fwd_rounds, drop_mask=sysd.drop)
    out += check_reversed_rounds(sysd.fwd_rounds, sysd.bwd_rounds)
    if mode == "cheap" or out:
        return out
    if plan.layout == "round_major":
        out += check_fused_tables(plan._precond.tables)
    else:
        out += check_step_tables(plan._precond.fwd, where="step_tables/fwd")
        out += check_step_tables(plan._precond.bwd, where="step_tables/bwd")
    out += check_ic0_structure(plan._structure)
    if mode == "deep" and not out:
        from .dtype_flow import check_plan_dtype_flow
        from .kernel_checks import check_plan_kernels
        out += check_plan_kernels(plan)
        out += check_plan_dtype_flow(plan)
    return out


def assert_plan_valid(plan, mode: str = "full", context: str = "") -> None:
    """``validate_plan`` that raises :class:`ScheduleError` on violations."""
    violations = validate_plan(plan, mode)
    if violations:
        raise ScheduleError(violations, context=context)
