"""Collective-structure proofs over lowered (optimized) HLO.

The paper's distributed claim (§4.4.3) is that one color-round costs one
synchronization — in the shard_map lowering, ONE tiled ``all-gather`` per
fused sweep step, and nothing else.  ``contracts.DISTRIBUTED_APPLY``
proves that at the jaxpr level (one ``all_gather`` eqn in the traced loop
body); this module proves it survives XLA: the *optimized* HLO of a mesh
plan must contain

  * exactly one all-gather inside each of the apply's while bodies, one
    forward and one backward loop per segment, each loop's
    ``known_trip_count`` equal to its segment's rounds (2S steps in all,
    S = color rounds), and every gather tiled (result bytes ==
    participants x operand bytes).  A segment of one round runs each step
    as a loop of one trip or, where XLA inlines such a loop (it does on
    the CPU), in the computation that runs the apply;
  * exactly one collective (an all-gather) in the sharded SpMV;
  * zero ``all-reduce`` / ``reduce-scatter`` / ``all-to-all`` /
    ``collective-permute`` anywhere in the whole PCG solve — the state
    vectors are replicated, so the dot-product pairings need no
    collective at all, and any reduction XLA sneaks in is a regression
    witness;
  * zero collectives of any kind for a single-device plan.

Built on the shared HLO parse in ``analysis.hlo``; witnesses reuse
:class:`~repro.analysis.schedule.Violation`.  CI runs this under
``--xla_force_host_platform_device_count=4``.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

from . import hlo
from .schedule import ScheduleError, Violation

#: collectives the solver's lowering may never emit (the dot products run
#: replicated; resharding mid-solve would be a layout leak)
FORBIDDEN_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-to-all",
                         "collective-permute")


@dataclasses.dataclass(frozen=True)
class CollectiveBody:
    """One while body carrying collectives in an optimized module."""
    comp: str               # computation name
    trip: int               # executed iterations of the enclosing while
    gathers: tuple          # all-gather op names (direct ops of the body)
    others: tuple           # non-all-gather collective op names


def optimized_hlo(fn, *args) -> str:
    """Optimized (post-SPMD) HLO text of ``jit(fn)`` on ``args``."""
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


def collective_bodies(text: str) -> tuple[list, dict]:
    """(bodies, module_counts): every while body that directly contains a
    collective, plus the module-wide static collective census by kind."""
    comps = hlo.parse_module(text)
    trips: dict = {}
    for comp in comps.values():
        for op in comp.ops:
            if hlo.base_kind(op.kind) == "while":
                t = hlo.trip_count(op, comps)
                for cname in hlo.called_comps(op.rest):
                    trips[cname] = max(trips.get(cname, 0), t)
    bodies = []
    counts: dict = {}
    for comp in comps.values():
        gathers, others = [], []
        for op in comp.ops:
            base = hlo.base_kind(op.kind)
            if base not in hlo.COLLECTIVES or op.kind.endswith("-done"):
                continue
            counts[base] = counts.get(base, 0) + 1
            (gathers if base == "all-gather" else others).append(op.name)
        if (gathers or others) and comp.name in trips:
            bodies.append(CollectiveBody(
                comp=comp.name, trip=trips[comp.name],
                gathers=tuple(gathers), others=tuple(others)))
    return bodies, counts


def _check_tiled(text: str, where: str) -> list[Violation]:
    """Every all-gather must be tiled: result size == participants x
    operand size (an untiled gather would replicate a full-length vector
    per round — the exact failure mode shard_fused_tables exists to
    avoid)."""
    out = []
    for comp in hlo.parse_module(text).values():
        for op in comp.ops:
            if hlo.base_kind(op.kind) != "all-gather" \
                    or op.kind.endswith("-done"):
                continue
            group = hlo.replica_group_size(op)
            ob = hlo.operand_bytes(op, comp)
            if not ob:
                continue            # operand outside the comp: unprovable
            rb = op.bytes if not op.kind.endswith("-start") else op.bytes - ob
            if group is not None and rb != group * ob:
                out.append(Violation(
                    kind="untiled-all-gather", where=where,
                    detail=f"{op.name} in {comp.name}: result {rb} B != "
                           f"{group} participants x operand {ob} B"))
    return out


def check_collective_structure(text: str, *,
                               n_rounds: int | Sequence[int] | None = None,
                               expect_gathers: int | None = None,
                               inline_gathers: int = 0,
                               where: str = "collectives"
                               ) -> list[Violation]:
    """Structural proof over one optimized module.

    Always enforced: no forbidden collective kinds, at most one all-gather
    per while body, every gather tiled.  ``n_rounds`` (the rounds of each
    segment; an int is one segment) additionally pins the sweep shape:
    one collective-bearing while body per segment of more than one round
    and half, whose trip counts are the segments' rounds, each twice, and
    two all-gathers outside them per segment of one round (in a loop of
    one trip, or inline where XLA inlines that loop).  ``inline_gathers``
    lets one while body, the PCG loop's, hold that many more than one:
    the inlined one-round steps of the apply it runs beside its SpMV's
    gather.  ``expect_gathers`` pins the module-wide static all-gather op
    count (e.g. 1 for the sharded SpMV).
    """
    bodies, counts = collective_bodies(text)
    out: list[Violation] = []
    for kind in FORBIDDEN_COLLECTIVES:
        if counts.get(kind):
            out.append(Violation(
                kind="forbidden-collective", where=where,
                detail=f"{counts[kind]} {kind} op(s) in the optimized "
                       f"module; only tiled all-gathers are allowed"))
    inlined = False
    for b in bodies:
        if b.others:
            out.append(Violation(
                kind="forbidden-collective", where=where,
                detail=f"while body {b.comp} contains "
                       f"{', '.join(b.others)}"))
        if inline_gathers and not inlined \
                and len(b.gathers) == 1 + inline_gathers:
            inlined = True
        elif len(b.gathers) > 1:
            out.append(Violation(
                kind="extra-collective", where=where, round=b.trip,
                detail=f"while body {b.comp} runs {len(b.gathers)} "
                       f"all-gathers per step ({', '.join(b.gathers)}); "
                       f"the sweep contract is one"))
    if n_rounds is not None:
        rounds = [n_rounds] if isinstance(n_rounds, int) else list(n_rounds)
        want = sorted(2 * [r for r in rounds if r > 1])
        sweep = [b for b in bodies if b.gathers and b.trip > 1]
        trips = sorted(b.trip for b in sweep)
        loose = counts.get("all-gather", 0) - sum(len(b.gathers)
                                                   for b in sweep)
        single = 2 * sum(r == 1 for r in rounds)
        if loose != single:
            out.append(Violation(
                kind="extra-collective" if loose > single
                else "missing-collective", where=where,
                detail=f"{loose} all-gather(s) outside loops of more than "
                       f"one trip; the apply's one-round segments run "
                       f"{single} steps"))
        if want and not sweep:
            out.append(Violation(
                kind="missing-collective", where=where,
                detail="no while body contains an all-gather — the sweep "
                       "lost its per-round tile exchange"))
        elif len(sweep) != len(want):
            out.append(Violation(
                kind="extra-collective" if len(sweep) > len(want)
                else "missing-collective", where=where,
                detail=f"{len(sweep)} collective-bearing while bodies "
                       f"({', '.join(b.comp for b in sweep)}); the apply "
                       f"has {len(want)} sweep loops (a forward and a "
                       f"backward loop per segment of more than one "
                       f"round)"))
        elif trips != want:
            bad = next(t for t, w in zip(trips, want) if t != w)
            out.append(Violation(
                kind="trip-count-mismatch", where=where, round=bad,
                detail=f"sweep bodies run {trips} steps, expected {want}: "
                       f"2S = {2 * sum(rounds)} (S = {sum(rounds)} rounds "
                       f"in {len(rounds)} segment(s))"))
    if expect_gathers is not None:
        got = counts.get("all-gather", 0)
        if got != expect_gathers:
            out.append(Violation(
                kind="extra-collective" if got > expect_gathers
                else "missing-collective", where=where,
                detail=f"{got} all-gather op(s) in the module, expected "
                       f"exactly {expect_gathers}"))
    out += _check_tiled(text, where)
    return out


def _zero_collectives(text: str, where: str) -> list[Violation]:
    stats = hlo.parse_collectives(text)
    if stats.total_count == 0:
        return []
    kinds = {k: c for k, c in stats.count_by_kind.items() if c}
    return [Violation(
        kind="extra-collective", where=where,
        detail=f"single-device lowering emits collectives: {kinds}")]


def check_plan_collectives(plan) -> list[Violation]:
    """Compile the plan's apply, SpMV and full PCG solve and prove their
    collective structure.  Single-device plans must lower collective-free;
    mesh plans must match the one-tiled-all-gather-per-round contract."""
    import jax.numpy as jnp

    from repro.core.iccg import make_sharded_spmv
    from repro.core.plan import _make_spmv

    q = jnp.zeros((plan.slab_m,), dtype=plan.dtype)
    pre = plan._precond
    out: list[Violation] = []

    if plan.mesh is None:
        spmv = _make_spmv(plan.spmv_format, plan._spmv_n, plan._spmv_vals,
                          plan._spmv_cols, False,
                          spmv_backend=plan.spmv_backend,
                          interpret=plan.interpret)
        out += _zero_collectives(optimized_hlo(lambda x: pre(x), q),
                                 "collectives/apply")
        out += _zero_collectives(optimized_hlo(spmv, q),
                                 "collectives/spmv")
        return out

    spmv = make_sharded_spmv(plan.spmv_format, plan._spmv_n, plan.mesh,
                             plan.mesh_axis, plan._spmv_vals,
                             plan._spmv_cols, False,
                             spmv_backend=plan.spmv_backend,
                             interpret=plan.interpret)
    out += check_collective_structure(
        optimized_hlo(lambda x: pre(x), q),
        n_rounds=[n for n, _ in pre.tables.segments],
        where="collectives/apply")
    out += check_collective_structure(
        optimized_hlo(spmv, q), expect_gathers=1, where="collectives/spmv")
    # whole solve: the sweep loops (init and iteration applies) and the
    # SpMV may each gather; nothing may reduce — replicated state needs no
    # all-reduce for the dot pairings
    fn = plan._pcg_fn(False, 1e-8, 8, False)
    solve_text = fn.lower(plan._precond.tables, plan._spmv_vals,
                          plan._spmv_cols, q).compile().as_text()
    ones = sum(n == 1 for n, _ in pre.tables.segments)
    out += check_collective_structure(solve_text, inline_gathers=2 * ones,
                                      where="collectives/solve")
    return out


def assert_plan_collectives(plan, context: str = "") -> None:
    """``check_plan_collectives`` that raises :class:`ScheduleError`."""
    violations = check_plan_collectives(plan)
    if violations:
        raise ScheduleError(violations, context=context)
