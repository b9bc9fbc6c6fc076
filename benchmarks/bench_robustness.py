"""Robustness benchmark: what does fault tolerance cost on the healthy path,
and how fast does the service shed an unhealthy solve?

Three questions, one JSON answer (schema ``bench_robustness/v1``):

  1. **Healthy-path monitoring overhead** — the in-loop health monitor
     (curvature / finiteness / divergence / stagnation guards) vs a
     reference unmonitored PCG loop (``pcg_iteration``, the pre-monitor
     body) over the *same* round-major trisolve + ELL SpMV operator, at a
     pinned iteration count.  The acceptance bar: < 5% per-iteration
     overhead.  (The guards are selects on scalars already in registers —
     the loop body is dominated by the two triangular sweeps + SpMV.)
  2. **Time to quarantine** — virtual-clock dispatches from submission to
     retirement for a NaN-RHS request (caught at slab entry) and an
     indefinite-matrix request (caught mid-iteration), vs the
     ``maxiter/quantum`` dispatch ceiling an unmonitored service would
     burn while the column iterated on garbage.
  3. **Fault-mix summary** — a seeded :class:`repro.serve.FaultInjector`
     trace drained to completion: status histogram per kind, quarantine
     count, and the wall-clock cost of the whole adversarial trace.

    PYTHONPATH=src python -m benchmarks.bench_robustness [--smoke]
        [--out BENCH_robustness.json]

CI runs ``--smoke`` and uploads the artifact; the committed snapshot is
the tracked trajectory sample.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402

from repro.core import ic0, pcg_iteration  # noqa: E402
from repro.core import sell  # noqa: E402
from repro.core.iccg import _pcg_device  # noqa: E402
from repro.core.matrices import laplace_2d  # noqa: E402
from repro.core.solvers import _order_system  # noqa: E402
from repro.core.trisolve import \
    build_round_major_preconditioner_from_rounds  # noqa: E402
from repro.serve import (FaultInjector, SolverService,  # noqa: E402
                         VirtualClock)
from repro.serve.faults import indefinite_matrix  # noqa: E402

KNOBS = dict(method="hbmc", block_size=8, w=4)


def _operator(a):
    """Round-major preconditioner + ELL SpMV closures for ``a`` — the same
    operator pair a SolverPlan lowers, built once for both loops."""
    sysd = _order_system(sp.csr_matrix(a), None, KNOBS["method"],
                         KNOBS["block_size"], KNOBS["w"])
    pre, rm = build_round_major_preconditioner_from_rounds(
        ic0(sysd.a_bar), sysd.fwd_rounds, sysd.bwd_rounds,
        drop_mask=sysd.drop)
    a_rm = sell.permute_round_major(sysd.a_bar, rm)
    cols, vals = sell.pack_ell(a_rm)
    vals_d, cols_d = jnp.asarray(vals), jnp.asarray(cols)

    def spmv(x):
        return jnp.einsum("kr,kr->r", vals_d, x[cols_d])

    b = np.random.default_rng(0).normal(size=a.shape[0])
    sysd_b = _order_system(sp.csr_matrix(a), b, KNOBS["method"],
                           KNOBS["block_size"], KNOBS["w"])
    return spmv, pre, jnp.asarray(rm.embed(sysd_b.b_bar))


def _time_best_pair(fn_a, fn_b, repeats):
    """Interleaved best-of timing of two callables (alternating draws, so
    machine-load drift hits both fairly)."""
    best_a = best_b = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a, best_b


def bench_monitor_overhead(a, n_iters, repeats=9):
    """Monitored vs reference unmonitored PCG at a pinned iteration count.

    ``rtol=0`` makes convergence unreachable, so both loops run exactly
    ``n_iters`` iterations (the default monitor windows are wider than
    the budget and never trip on this healthy system) — the timing ratio
    is a clean per-iteration overhead measurement.
    """
    spmv, pre, b = _operator(a)

    monitored = jax.jit(lambda q: _pcg_device(spmv, pre, q, rtol=0.0,
                                              maxiter=n_iters))

    # the pre-monitor loop body: pcg_iteration plus the carried ||r||
    # reduction the convergence cond always read
    step = pcg_iteration(spmv, pre)

    def reference(q):
        bnorm = jnp.linalg.norm(q)
        z0 = pre(q)

        def cond(s):
            return (s[4] / bnorm >= 0.0) & (s[5] < n_iters)

        def body(s):
            x, r, p, rz, _, it = s
            x, r, p, rz = step(x, r, p, rz)
            return (x, r, p, rz, jnp.linalg.norm(r), it + 1)

        state = (jnp.zeros_like(q), q, z0, jnp.vdot(q, z0),
                 jnp.linalg.norm(q), jnp.asarray(0))
        x, _, _, _, rnorm, it = jax.lax.while_loop(cond, body, state)
        return x, it, rnorm / bnorm

    reference = jax.jit(reference)

    jax.block_until_ready(monitored(b))   # compile
    jax.block_until_ready(reference(b))
    t_mon, t_ref = _time_best_pair(lambda: monitored(b),
                                   lambda: reference(b), repeats)
    it_mon = int(monitored(b)[1])
    assert it_mon == n_iters, f"monitored loop ran {it_mon} != {n_iters}"
    return {
        "n_iters": n_iters,
        "monitored_s": round(t_mon, 5),
        "reference_s": round(t_ref, 5),
        "monitored_us_per_iter": round(t_mon / n_iters * 1e6, 2),
        "reference_us_per_iter": round(t_ref / n_iters * 1e6, 2),
        "overhead_pct": round((t_mon / t_ref - 1.0) * 100.0, 2),
    }


def bench_time_to_quarantine(n_side, quantum=8, maxiter=3000):
    """Dispatches from submission to retirement for injected faults, vs
    the maxiter/quantum ceiling an unmonitored column would hold its slot.
    """
    inj = FaultInjector(seed=0, n_side=n_side)
    rows = {}
    for kind, mat, b in [
            ("nan_rhs", inj.base, None),
            ("indefinite", indefinite_matrix(n_side), None)]:
        svc = SolverService(slab_width=4, quantum=quantum, maxiter=maxiter,
                            clock=VirtualClock(), **KNOBS)
        fp = inj.make(kind) if b is None else None
        rid = svc.submit(mat, fp.b if fp else b)
        steps = 0
        while rid not in svc.completed and steps < 100_000:
            svc.step()
            steps += 1
        c = svc.completed[rid]
        rows[kind] = {
            "status": c.status,
            "dispatches_to_retire": steps,
            "iterations": c.iterations,
            "virtual_latency_s": round(c.latency, 5),
            "unmonitored_dispatch_ceiling": maxiter // quantum,
        }
        assert c.failed, f"{kind} unexpectedly reported {c.status}"
    return rows


def bench_fault_mix(n_side, n_requests):
    """A seeded mixed adversarial trace drained to completion."""
    inj = FaultInjector(seed=3, n_side=n_side)
    svc = SolverService(slab_width=4, quantum=8, maxiter=3000,
                        clock=VirtualClock(), max_queue=64, **KNOBS)
    t0 = time.perf_counter()
    rids, shed = inj.inject(svc, n_requests, spacing=0.01)
    svc.drain(max_steps=200_000)
    elapsed = time.perf_counter() - t0

    by_kind: dict[str, dict[str, int]] = {}
    violations = 0
    for rid, fp in rids.items():
        st = svc.completed[rid].status
        by_kind.setdefault(fp.kind, {}).setdefault(st, 0)
        by_kind[fp.kind][st] += 1
        if st not in fp.expected:
            violations += 1
    return {
        "n_requests": n_requests,
        "n_shed": len(shed),
        "n_quarantined": svc.n_quarantined,
        "out_of_contract": violations,
        "wall_s": round(elapsed, 3),
        "statuses_by_kind": {k: dict(sorted(v.items()))
                             for k, v in sorted(by_kind.items())},
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem, fewer iterations/requests (CI)")
    ap.add_argument("--out", default="BENCH_robustness.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        problems = [("lap2d_12", laplace_2d(12, 12), 100)]
        n_side, n_req = 6, 20
    else:
        # the monitor cost is O(1) scalars per iteration against an
        # O(nnz) loop body: measure a small serving-sized problem AND a
        # paper-representative size to show the overhead vanishing
        problems = [("lap2d_32", laplace_2d(32, 32), 400),
                    ("lap2d_64", laplace_2d(64, 64), 300)]
        n_side, n_req = 6, 60

    overhead = [dict(problem=name, n=int(a.shape[0]),
                     **bench_monitor_overhead(a, n_iters))
                for name, a, n_iters in problems]
    quarantine = bench_time_to_quarantine(n_side)
    mix = bench_fault_mix(n_side, n_req)

    doc = {
        "schema": "bench_robustness/v1",
        "platform": jax.default_backend(),
        "smoke": bool(args.smoke),
        "knobs": {k: v for k, v in KNOBS.items()},
        "monitor_overhead": overhead,
        "time_to_quarantine": quarantine,
        "fault_mix": mix,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")

    for row in overhead:
        print(f"monitor overhead[{row['problem']}]: "
              f"{row['overhead_pct']:+.2f}% "
              f"({row['monitored_us_per_iter']:.2f} vs "
              f"{row['reference_us_per_iter']:.2f} us/iter over "
              f"{row['n_iters']} iters)")
    for kind, r in quarantine.items():
        print(f"time-to-quarantine[{kind}]: {r['dispatches_to_retire']} "
              f"dispatch(es) -> {r['status']} "
              f"(unmonitored ceiling {r['unmonitored_dispatch_ceiling']})")
    print(f"fault mix: {mix['n_requests']} requests, "
          f"{mix['n_quarantined']} quarantined, {mix['n_shed']} shed, "
          f"{mix['out_of_contract']} out-of-contract, "
          f"{mix['wall_s']}s wall")
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
