"""Setup-pipeline benchmark: array-program ordering + SolverPlan reuse.

Five questions, one JSON answer (schema ``bench_setup/v2``):

  1. **Setup breakdown + legacy speedup** — cold ``build_plan`` wall-clock
     split into ordering (further split block_build / color / aggregate) /
     factor / pack, against the seed's "legacy" pipeline (per-node Python
     block building, sequential up-looking ``ic0``, per-row step/ELL
     packing — preserved verbatim below), per ordering method.
     ``block_build_speedup`` tracks the vectorized block builder against
     the seed walk on the same matrix (acceptance: >= 3x at n=4096).
  2. **Scheduler backends** — cold setup + warm solve for
     ``scheduler="coloring"`` vs ``scheduler="levelset"`` on the same
     system (round counts, schedule_s, iteration parity).
  3. **Large-n cold setup** (``--large-n``) — one n >= 250k system
     through the full vectorized pipeline, with a single rep of the seed
     block walk for scale (the legacy path's only reachable stage at
     this size).
  4. **Plan-reuse amortization** — cold ``solve_iccg`` vs warm
     ``plan.solve`` for the same system: the warm path must spend ~zero
     host-side setup (``warm_setup_s``) and amortize the cold setup away
     after ``breakeven_solves`` solves.
  5. **Refactor vs full setup** — ``plan.refactor(a')`` (numeric-only:
     values change, pattern fixed — the implicit time-stepping workload)
     vs building a fresh plan.

    PYTHONPATH=src python -m benchmarks.bench_setup [--smoke] [--large-n]
        [--out BENCH_setup.json]

CI runs ``--smoke --large-n`` and uploads the artifact; the committed
snapshot is the tracked trajectory sample.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402

from repro.core import build_plan, coloring, ic0, sell, solve_iccg  # noqa: E402
from repro.core import plan as plan_mod  # noqa: E402
from repro.core.matrices import laplace_2d, laplace_3d  # noqa: E402
from repro.core.solvers import _order_system  # noqa: E402

BS, W = 32, 8


# ---------------------------------------------------------------------------
# The seed setup pipeline, preserved verbatim as the trajectory baseline:
# per-node block building with Python sets, per-row step/ELL packing, and
# the sequential up-looking IC(0) (which still lives in core.ic0 as the
# semantics oracle).  This is what every solve_iccg call paid before the
# round-parallel pipeline.
# ---------------------------------------------------------------------------

def _seed_build_blocks(a, block_size):
    import heapq
    n = a.shape[0]
    from repro.core.graph import adjacency_lists
    indptr, indices = adjacency_lists(a)
    assigned = np.zeros(n, dtype=bool)
    blocks = []
    next_seed = 0
    while True:
        while next_seed < n and assigned[next_seed]:
            next_seed += 1
        if next_seed >= n:
            break
        blk = [next_seed]
        assigned[next_seed] = True
        heap, in_heap = [], set()
        for u in indices[indptr[next_seed]:indptr[next_seed + 1]]:
            if not assigned[u] and u not in in_heap:
                heapq.heappush(heap, int(u)); in_heap.add(int(u))
        while len(blk) < block_size and heap:
            v = heapq.heappop(heap)
            if assigned[v]:
                continue
            blk.append(v)
            assigned[v] = True
            for u in indices[indptr[v]:indptr[v + 1]]:
                u = int(u)
                if not assigned[u] and u not in in_heap:
                    heapq.heappush(heap, u); in_heap.add(u)
        blk.sort()
        blocks.append(blk)
    return blocks


def _seed_build_blocks_partition(a, block_size, adjacency=None):
    """Seed walk behind the new ``build_blocks`` contract (the end-to-end
    legacy baseline swaps this in for the vectorized builder)."""
    blocks = _seed_build_blocks(a, block_size)
    return coloring.BlockPartition(
        members=np.concatenate([np.asarray(b, dtype=np.int64)
                                for b in blocks]),
        lens=np.array([len(b) for b in blocks], dtype=np.int64))


def _seed_pack_steps(tri, diag, rounds, drop_mask=None):
    tri = sp.csr_matrix(tri)
    tri.sort_indices()
    n = tri.shape[0]
    n_slots = n + 1
    if drop_mask is not None:
        rounds = [r[~drop_mask[r]] for r in rounds]
        rounds = [r for r in rounds if len(r)]
    S = len(rounds)
    R = max(len(r) for r in rounds)
    K = max(int(np.diff(tri.indptr).max(initial=0)), 1)
    rows = np.full((S, R), n_slots - 1, dtype=np.int32)
    cols = np.full((S, R, K), n_slots - 1, dtype=np.int32)
    vals = np.zeros((S, R, K))
    dinv = np.zeros((S, R))
    live = np.zeros(S, dtype=np.int32)
    for s, rset in enumerate(rounds):
        live[s] = len(rset)
        rows[s, :len(rset)] = rset
        dinv[s, :len(rset)] = 1.0 / diag[rset]
        for t, r in enumerate(rset):
            lo, hi = tri.indptr[r], tri.indptr[r + 1]
            cols[s, t, :hi - lo] = tri.indices[lo:hi]
            vals[s, t, :hi - lo] = tri.data[lo:hi]
    return sell.StepTables(rows=rows, cols=cols, vals=vals, dinv=dinv,
                           n_slots=n_slots, live=live)


def _seed_pack_ell(a):
    a = sp.csr_matrix(a)
    a.sort_indices()
    n = a.shape[0]
    k = max(int(np.diff(a.indptr).max(initial=0)), 1)
    cols = np.zeros((n, k), dtype=np.int32)
    vals = np.zeros((n, k))
    for r in range(n):
        lo, hi = a.indptr[r], a.indptr[r + 1]
        cols[r, :hi - lo] = a.indices[lo:hi]
        vals[r, :hi - lo] = a.data[lo:hi]
    return cols.T.copy(), vals.T.copy()


def _legacy_setup(a, method):
    """Seed pipeline end to end: ordering -> sequential IC(0) -> per-row
    packing -> fused tables + ELL SpMV operand, moved to device (the same
    endpoint ``build_plan`` is charged for).  Returns the per-stage split
    (ordering_s, factor_s, pack_s)."""
    import jax.numpy as jnp

    from repro.core.trisolve import DeviceFusedTables
    t0 = time.perf_counter()
    orig = plan_mod.build_blocks
    plan_mod.build_blocks = _seed_build_blocks_partition
    try:
        sysd = _order_system(a, None, method, BS, W)
    finally:
        plan_mod.build_blocks = orig
    t1 = time.perf_counter()
    l_bar = ic0(sysd.a_bar)
    t2 = time.perf_counter()
    diag = l_bar.diagonal()
    strict_lower = sp.tril(l_bar, k=-1, format="csr")
    fwd = _seed_pack_steps(strict_lower, diag, sysd.fwd_rounds, sysd.drop)
    bwd = _seed_pack_steps(sp.csr_matrix(strict_lower.T), diag,
                           sysd.bwd_rounds, sysd.drop)
    fused = sell.fuse_round_major(fwd, bwd)
    DeviceFusedTables.from_host(fused)
    cols, vals = _seed_pack_ell(
        sell.permute_round_major(sysd.a_bar, fused.layout))
    jnp.asarray(vals), jnp.asarray(cols)
    t3 = time.perf_counter()
    return t1 - t0, t2 - t1, t3 - t2


def _problems(smoke: bool):
    if smoke:
        return [("lap2d_tiny", laplace_2d(16, 14)),
                ("lap3d_tiny_27", laplace_3d(6, 6, 5, stencil=27))]
    return [("lap2d_64", laplace_2d(64, 64)),
            ("lap3d_16_27", laplace_3d(16, 16, 16, stencil=27))]


def _best(fn, reps):
    """Best-of-reps wall-clock (min is robust to scheduler noise)."""
    best, out = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def bench_setup_breakdown(name, a, method, reps):
    """Cold plan setup (with stage breakdown) vs the legacy sequential path.

    Plan and legacy reps are interleaved so scheduler noise hits both sides
    alike; best-of-reps on each."""
    a = sp.csr_matrix(a)
    breakdown = {"ordering": float("inf"), "factor": float("inf"),
                 "pack": float("inf"), "block_build": float("inf"),
                 "color": float("inf"), "aggregate": float("inf")}
    lg = {"ordering": float("inf"), "factor": float("inf"),
          "pack": float("inf")}
    plan_s = legacy_s = seed_build_s = float("inf")
    for _ in range(reps):
        plan = build_plan(a, method=method, block_size=BS, w=W)
        t = plan.timings
        plan_s = min(plan_s, t.total)
        for k in breakdown:
            breakdown[k] = min(breakdown[k], getattr(t, k))
        t0 = time.perf_counter()
        lo, lf, lp = _legacy_setup(a, method)
        legacy_s = min(legacy_s, time.perf_counter() - t0)
        lg["ordering"] = min(lg["ordering"], lo)
        lg["factor"] = min(lg["factor"], lf)
        lg["pack"] = min(lg["pack"], lp)
        if method != "mc":
            t0 = time.perf_counter()
            _seed_build_blocks(a, BS)
            seed_build_s = min(seed_build_s, time.perf_counter() - t0)
    # the stages the round-parallel pipeline vectorizes (the ordering
    # front-end is itself an array program since bench_setup/v2)
    fp_plan = breakdown["factor"] + breakdown["pack"]
    fp_legacy = lg["factor"] + lg["pack"]
    out = {
        "problem": name, "n": int(a.shape[0]), "method": method,
        "plan_setup_s": round(plan_s, 5),
        "ordering_s": round(breakdown["ordering"], 5),
        "block_build_s": round(breakdown["block_build"], 5),
        "color_s": round(breakdown["color"], 5),
        "aggregate_s": round(breakdown["aggregate"], 5),
        "factor_s": round(breakdown["factor"], 5),
        "pack_s": round(breakdown["pack"], 5),
        "legacy_setup_s": round(legacy_s, 5),
        "legacy_ordering_s": round(lg["ordering"], 5),
        "legacy_factor_s": round(lg["factor"], 5),
        "legacy_pack_s": round(lg["pack"], 5),
        "legacy_over_plan": round(legacy_s / plan_s, 2),
        "factor_pack_speedup": round(fp_legacy / fp_plan, 2),
    }
    if method != "mc":
        out["legacy_block_build_s"] = round(seed_build_s, 5)
        out["block_build_speedup"] = round(
            seed_build_s / max(breakdown["block_build"], 1e-9), 2)
    return out


def bench_scheduler_compare(name, a, reps, maxiter):
    """coloring vs levelset rounds on the same (hbmc-ordered) system."""
    a = sp.csr_matrix(a)
    b = np.random.default_rng(2).normal(size=a.shape[0])
    out = []
    for scheduler in ("coloring", "levelset"):
        setup_s = schedule_s = float("inf")
        plan = None
        for _ in range(reps):
            plan = build_plan(a, method="hbmc", block_size=BS, w=W,
                              scheduler=scheduler)
            setup_s = min(setup_s, plan.timings.total)
            schedule_s = min(schedule_s, plan.timings.schedule)
        plan.solve(b, rtol=0.0, maxiter=maxiter)   # warm the jit cache
        solve_s, rep = _best(
            lambda: plan.solve(b, rtol=0.0, maxiter=maxiter), reps)
        out.append({
            "problem": name, "n": int(a.shape[0]), "method": "hbmc",
            "scheduler": scheduler,
            "setup_s": round(setup_s, 5),
            "schedule_s": round(schedule_s, 5),
            "n_rounds": int(plan.n_rounds),
            "warm_solve_s": round(solve_s, 5),
            "iterations": int(rep.result.iterations),
        })
    return out


def bench_large_n(reps):
    """n >= 250k cold setup through the vectorized pipeline.

    The committed row the legacy path could not reach: the seed block
    walk alone (one rep — it is the only legacy stage that finishes in
    comparable time at this size; the sequential IC(0) would take
    minutes) is compared against the full vectorized ordering stage.
    """
    a = sp.csr_matrix(laplace_2d(512, 512))
    breakdown = {"block_build": float("inf"), "color": float("inf"),
                 "aggregate": float("inf"), "ordering": float("inf"),
                 "factor": float("inf"), "pack": float("inf")}
    plan_s = float("inf")
    for _ in range(reps):
        plan = build_plan(a, method="hbmc", block_size=BS, w=W)
        plan_s = min(plan_s, plan.timings.total)
        for k in breakdown:
            breakdown[k] = min(breakdown[k], getattr(plan.timings, k))
    t0 = time.perf_counter()
    _seed_build_blocks(a, BS)
    seed_build_s = time.perf_counter() - t0
    return [{
        "problem": "lap2d_512", "n": int(a.shape[0]), "method": "hbmc",
        "plan_setup_s": round(plan_s, 5),
        "ordering_s": round(breakdown["ordering"], 5),
        "block_build_s": round(breakdown["block_build"], 5),
        "color_s": round(breakdown["color"], 5),
        "aggregate_s": round(breakdown["aggregate"], 5),
        "factor_s": round(breakdown["factor"], 5),
        "pack_s": round(breakdown["pack"], 5),
        "legacy_block_build_s": round(seed_build_s, 5),
        "block_build_speedup": round(
            seed_build_s / max(breakdown["block_build"], 1e-9), 2),
    }]


def bench_plan_reuse(name, a, reps, maxiter):
    """Cold solve_iccg vs warm plan.solve on the same system."""
    a = sp.csr_matrix(a)
    b = np.random.default_rng(0).normal(size=a.shape[0])
    kw = dict(method="hbmc", block_size=BS, w=W, rtol=0.0, maxiter=maxiter)

    cold_s, rep = _best(lambda: solve_iccg(a, b, **kw), reps)
    plan = build_plan(a, method="hbmc", block_size=BS, w=W)
    plan.solve(b, rtol=0.0, maxiter=maxiter)       # warm the jit cache
    warm_s, wrep = _best(lambda: plan.solve(b, rtol=0.0, maxiter=maxiter),
                         reps)
    warm_setup = wrep.setup_seconds
    setup_s = plan.timings.total
    gain = cold_s - warm_s
    return {
        "problem": name, "n": int(a.shape[0]), "maxiter": maxiter,
        "cold_solve_iccg_s": round(cold_s, 5),
        "warm_plan_solve_s": round(warm_s, 5),
        "warm_setup_s": round(warm_setup, 6),
        "plan_setup_s": round(setup_s, 5),
        "cold_over_warm": round(cold_s / warm_s, 2),
        # solves until holding the plan has paid for building it
        "breakeven_solves": (int(np.ceil(setup_s / gain))
                             if gain > 0 else None),
    }


def bench_refactor(name, a, reps):
    """plan.refactor (values change, same pattern) vs a fresh build_plan."""
    a = sp.csr_matrix(a)
    plan = build_plan(a, method="hbmc", block_size=BS, w=W)
    full_s = plan.timings.total
    for _ in range(max(reps - 1, 0)):
        full_s = min(full_s, build_plan(a, method="hbmc", block_size=BS,
                                        w=W).timings.total)
    a2 = (a + 0.1 * sp.diags(a.diagonal())).tocsr()
    b = np.random.default_rng(1).normal(size=a.shape[0])
    plan.solve(b, rtol=0.0, maxiter=5)            # trace the PCG once
    refac_s = post_s = float("inf")
    for _ in range(reps):
        refac_s = min(refac_s, plan.refactor(a2).total)
        # first solve after a refactor: operands are jit ARGUMENTS, so the
        # cached executable is reused — no retrace, no recompile
        rep = plan.solve(b, rtol=0.0, maxiter=5)
        post_s = min(post_s, rep.solve_seconds)
    return {
        "problem": name, "n": int(a.shape[0]),
        "full_setup_s": round(full_s, 5),
        "refactor_s": round(refac_s, 5),
        "post_refactor_solve_s": round(post_s, 5),
        "retraces": plan._trace_count,
        "full_over_refactor": round(full_s / refac_s, 2),
    }


def bench_validate_overhead(name, a, reps):
    """Cold build_plan with the static race detector on vs off.

    ``validate="cheap"`` (round/DAG audit only) must stay under 5% of the
    cold setup on lap3d_16_27 — the knob is meant to be affordable enough
    to leave on in serving admission control.  ``full`` (adds the packed
    table and IC(0) structure proofs) is reported for the trajectory."""
    a = sp.csr_matrix(a)
    kw = dict(method="hbmc", block_size=BS, w=W)
    off_s, _ = _best(lambda: build_plan(a, validate="off", **kw), reps)
    cheap_s, _ = _best(lambda: build_plan(a, validate="cheap", **kw), reps)
    full_s, _ = _best(lambda: build_plan(a, validate="full", **kw), reps)
    return {
        "problem": name, "n": int(a.shape[0]),
        "build_off_s": round(off_s, 5),
        "build_cheap_s": round(cheap_s, 5),
        "build_full_s": round(full_s, 5),
        "cheap_overhead_pct": round(100.0 * (cheap_s - off_s) / off_s, 2),
        "full_overhead_pct": round(100.0 * (full_s - off_s) / off_s, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problems, fewer reps (CI)")
    ap.add_argument("--large-n", action="store_true",
                    help="also run the n >= 250k cold-setup row (the "
                         "host-side scaling tripwire)")
    ap.add_argument("--out", default="BENCH_setup.json")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--maxiter", type=int, default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    reps = args.reps or (2 if args.smoke else 5)
    maxiter = args.maxiter or (10 if args.smoke else 40)

    problems = _problems(args.smoke)
    breakdown = [bench_setup_breakdown(name, a, method, reps)
                 for name, a in problems
                 for method in ("hbmc", "bmc", "mc")]
    schedulers = [row for name, a in problems
                  for row in bench_scheduler_compare(name, a, reps, maxiter)]
    large_n = bench_large_n(1 if args.smoke else 2) if args.large_n else []
    reuse = [bench_plan_reuse(name, a, reps, maxiter)
             for name, a in problems]
    refactor = [bench_refactor(name, a, reps) for name, a in problems]
    validate = [bench_validate_overhead(name, a, reps)
                for name, a in problems]

    doc = {
        "schema": "bench_setup/v2",
        "platform": jax.default_backend(),
        "smoke": bool(args.smoke),
        "block_size": BS,
        "w": W,
        "setup_breakdown": breakdown,
        "scheduler_compare": schedulers,
        "large_n": large_n,
        "plan_reuse": reuse,
        "refactor": refactor,
        "validate_overhead": validate,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")

    print(f"{'problem':14s} {'method':6s} {'plan s':>8s} {'legacy s':>9s} "
          f"{'total':>7s} {'fac+pack':>9s} {'blk-build':>10s}   "
          f"(build/color/agg | factor/pack)")
    for r in breakdown:
        bb = (f"{r['block_build_speedup']:8.1f}x"
              if "block_build_speedup" in r else " " * 9)
        print(f"{r['problem']:14s} {r['method']:6s} {r['plan_setup_s']:8.3f} "
              f"{r['legacy_setup_s']:9.3f} {r['legacy_over_plan']:6.1f}x "
              f"{r['factor_pack_speedup']:8.1f}x {bb}   "
              f"({r['block_build_s']:.3f}/{r['color_s']:.3f}/"
              f"{r['aggregate_s']:.3f} | "
              f"{r['factor_s']:.3f}/{r['pack_s']:.3f})")
    print(f"\n{'problem':14s} {'scheduler':9s} {'setup s':>8s} "
          f"{'sched s':>8s} {'rounds':>7s} {'solve s':>8s} {'iters':>6s}")
    for r in schedulers:
        print(f"{r['problem']:14s} {r['scheduler']:9s} {r['setup_s']:8.3f} "
              f"{r['schedule_s']:8.4f} {r['n_rounds']:7d} "
              f"{r['warm_solve_s']:8.4f} {r['iterations']:6d}")
    for r in large_n:
        print(f"\nlarge-n {r['problem']} (n={r['n']}): "
              f"setup {r['plan_setup_s']:.3f}s "
              f"(build {r['block_build_s']:.3f} / color {r['color_s']:.3f} "
              f"/ agg {r['aggregate_s']:.3f} / factor {r['factor_s']:.3f} "
              f"/ pack {r['pack_s']:.3f}); seed block walk "
              f"{r['legacy_block_build_s']:.3f}s "
              f"-> {r['block_build_speedup']:.1f}x")
    print(f"\n{'problem':14s} {'cold s':>8s} {'warm s':>8s} {'ratio':>6s} "
          f"{'warm setup s':>13s} {'breakeven':>10s}")
    for r in reuse:
        print(f"{r['problem']:14s} {r['cold_solve_iccg_s']:8.3f} "
              f"{r['warm_plan_solve_s']:8.3f} {r['cold_over_warm']:5.1f}x "
              f"{r['warm_setup_s']:13.6f} {str(r['breakeven_solves']):>10s}")
    print(f"\n{'problem':14s} {'full s':>8s} {'refactor s':>11s} "
          f"{'ratio':>6s} {'post-solve s':>13s} {'retraces':>9s}")
    for r in refactor:
        print(f"{r['problem']:14s} {r['full_setup_s']:8.3f} "
              f"{r['refactor_s']:11.3f} {r['full_over_refactor']:5.1f}x "
              f"{r['post_refactor_solve_s']:13.5f} {r['retraces']:9d}")
    print(f"\n{'problem':14s} {'off s':>8s} {'cheap s':>8s} {'full s':>8s} "
          f"{'cheap +%':>9s} {'full +%':>9s}")
    for r in validate:
        print(f"{r['problem']:14s} {r['build_off_s']:8.3f} "
              f"{r['build_cheap_s']:8.3f} {r['build_full_s']:8.3f} "
              f"{r['cheap_overhead_pct']:8.2f}% {r['full_overhead_pct']:8.2f}%")
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
