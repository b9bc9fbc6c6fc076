"""Solver-serving benchmark: continuous batching over a warm plan cache.

Three questions, one JSON answer (schema ``bench_serve/v1``):

  1. **Offline throughput vs slab width** — N requests against one warm
     cached plan, served through ``SolverService`` at B ∈ {1, 4, 8, 16}:
     RHS/sec, p50/p99 request latency, and mean slab occupancy per width.
     The acceptance comparison: warm slab serving at B >= 4 must beat the
     one-request-at-a-time **cold baseline** (build_plan + solve per
     request — what a client pays without the serving layer) on RHS/sec.
  2. **Server-style load** — seeded arrival pacing against the wall
     clock at the same widths: p50/p99 latency under queueing, not just
     back-to-back throughput.
  3. **Cache behavior** — hit/refactor/miss/eviction rates for a warm
     single-pattern stream vs a mixed-pattern stream with value changes
     (the time-stepping fleet) through a small-capacity ``PlanCache``.

    PYTHONPATH=src python -m benchmarks.bench_serve [--smoke]
        [--out BENCH_serve.json]

CI runs ``--smoke`` and uploads the artifact; the committed snapshot is
the tracked trajectory sample.  (This benchmark paces real submissions,
so unlike tier-1 tests it may sleep between arrivals.)
"""
from __future__ import annotations

import argparse
import json
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro.core import build_plan  # noqa: E402
from repro.core.matrices import laplace_2d  # noqa: E402
from repro.serve import PlanCache, SolverService, VirtualClock  # noqa: E402

KNOBS = dict(method="hbmc", block_size=32, w=8)
QUANTUM = 16


def _mean_occupancy(svc) -> float:
    occ = [sum(r is not None for r in e["rids"]) / len(e["rids"])
           for e in svc.dispatch_log]
    return float(np.mean(occ)) if occ else 0.0


def _pcts(latencies):
    return (float(np.percentile(latencies, 50)),
            float(np.percentile(latencies, 99)))


def bench_offline(a, n_req, widths, cache):
    """Back-to-back serving throughput at each slab width, warm cache."""
    rng = np.random.default_rng(0)
    bs = [rng.standard_normal(a.shape[0]) for _ in range(n_req)]
    rows = []
    for width in widths:
        svc = SolverService(cache, slab_width=width, quantum=QUANTUM,
                            record_dispatches=True, **KNOBS)
        svc.submit(a, bs[0])
        svc.drain()                    # warm: plan cached, slab fn compiled
        svc = SolverService(cache, slab_width=width, quantum=QUANTUM,
                            record_dispatches=True, **KNOBS)
        t0 = time.perf_counter()
        for b in bs:
            svc.submit(a, b)
        done = svc.drain()
        elapsed = time.perf_counter() - t0
        lat = [c.latency for c in done]
        p50, p99 = _pcts(lat)
        assert all(c.converged for c in done)
        assert all(c.plan_status == "hit" for c in done)
        rows.append({
            "slab_width": width,
            "rhs_per_s": round(n_req / elapsed, 2),
            "elapsed_s": round(elapsed, 4),
            "p50_latency_s": round(p50, 5),
            "p99_latency_s": round(p99, 5),
            "mean_occupancy": round(_mean_occupancy(svc), 3),
            "mean_iterations": round(float(np.mean(
                [c.iterations for c in done])), 1),
        })
    return rows


def bench_cold_baseline(a, n_req):
    """One-request-at-a-time cold solves: build_plan + solve per request,
    no cache — the cost every client pays without the serving layer."""
    rng = np.random.default_rng(0)
    bs = [rng.standard_normal(a.shape[0]) for _ in range(n_req)]
    build_plan(a, **KNOBS).solve(bs[0])   # exclude one-time jit compile
    lat = []
    t0 = time.perf_counter()
    for b in bs:
        t1 = time.perf_counter()
        plan = build_plan(a, **KNOBS)
        rep = plan.solve(b)
        assert rep.result.converged
        lat.append(time.perf_counter() - t1)
    elapsed = time.perf_counter() - t0
    p50, p99 = _pcts(lat)
    return {
        "rhs_per_s": round(n_req / elapsed, 2),
        "elapsed_s": round(elapsed, 4),
        "p50_latency_s": round(p50, 5),
        "p99_latency_s": round(p99, 5),
    }


def bench_server(a, n_req, widths, cache, mean_gap):
    """Seeded arrivals paced against the wall clock: latency under load."""
    rng = np.random.default_rng(7)
    bs = [rng.standard_normal(a.shape[0]) for _ in range(n_req)]
    offsets = np.cumsum(rng.exponential(mean_gap, size=n_req))
    rows = []
    for width in widths:
        svc = SolverService(cache, slab_width=width, quantum=QUANTUM,
                            **KNOBS)
        svc.submit(a, bs[0])
        svc.drain()                    # warm
        svc = SolverService(cache, slab_width=width, quantum=QUANTUM,
                            **KNOBS)
        t0 = time.perf_counter()
        i = 0
        while i < n_req or svc.n_queued or svc.n_in_flight:
            now = time.perf_counter() - t0
            while i < n_req and offsets[i] <= now:
                svc.submit(a, bs[i])
                i += 1
            if svc.n_queued or svc.n_in_flight:
                svc.step()
            elif i < n_req:            # idle: wait for the next arrival
                time.sleep(max(min(offsets[i] - now, 0.001), 0.0))
        elapsed = time.perf_counter() - t0
        lat = [c.latency for c in svc.completed.values()]
        p50, p99 = _pcts(lat)
        rows.append({
            "slab_width": width,
            "mean_gap_s": mean_gap,
            "rhs_per_s": round(n_req / elapsed, 2),
            "p50_latency_s": round(p50, 5),
            "p99_latency_s": round(p99, 5),
        })
    return rows


def bench_cache(a, n_req):
    """Cache hit rates: warm single-pattern stream vs a mixed stream with
    value changes through a capacity-2 cache (deterministic virtual
    clock — only the cache counters matter here)."""
    rng = np.random.default_rng(3)

    def _stats(svc):
        s = svc.cache.stats
        return {"hits": s.hits, "misses": s.misses,
                "refactors": s.refactors, "evictions": s.evictions,
                "hit_rate": round(s.hit_rate, 3)}

    # gaps wider than a request's virtual service time, so each arrival
    # finds an empty service and must consult the cache anew
    gap = 5.0
    warm = SolverService(PlanCache(capacity=2), slab_width=4,
                         quantum=QUANTUM, clock=VirtualClock(), **KNOBS)
    for i in range(n_req):
        warm.submit(a, rng.standard_normal(a.shape[0]),
                    arrival_time=gap * i)
    warm.drain()

    mats = [a]
    a2 = laplace_2d(a.shape[0] // 16, 16)
    a3 = a.copy()
    a3.data = a3.data * 1.5            # same pattern, new values
    mats += [a2, a3]
    mixed = SolverService(PlanCache(capacity=2), slab_width=4,
                          quantum=QUANTUM, clock=VirtualClock(), **KNOBS)
    for i in range(n_req):
        m = mats[int(rng.integers(len(mats)))]
        mixed.submit(m, rng.standard_normal(m.shape[0]),
                     arrival_time=gap * i)
    mixed.drain()
    return {"warm_single_pattern": _stats(warm),
            "mixed_with_value_changes": _stats(mixed)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem, fewer requests/widths (CI)")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--requests", type=int, default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        a, name = laplace_2d(12, 12), "lap2d_12"
        widths = [1, 4]
        n_req = args.requests or 6
        mean_gap = 0.02
    else:
        a, name = laplace_2d(32, 32), "lap2d_32"
        widths = [1, 4, 8, 16]
        n_req = args.requests or 48
        mean_gap = 0.01

    cache = PlanCache(capacity=4)
    offline = bench_offline(a, n_req, widths, cache)
    cold = bench_cold_baseline(a, n_req)
    for row in offline:
        row["speedup_vs_cold"] = round(row["rhs_per_s"]
                                       / cold["rhs_per_s"], 2)
    server = bench_server(a, n_req, widths, cache, mean_gap)
    cache_rates = bench_cache(a, max(n_req, 12))

    doc = {
        "schema": "bench_serve/v1",
        "platform": jax.default_backend(),
        "smoke": bool(args.smoke),
        "problem": {"name": name, "n": int(a.shape[0])},
        "n_requests": n_req,
        "quantum": QUANTUM,
        "knobs": {k: v for k, v in KNOBS.items()},
        "offline": offline,
        "cold_baseline": cold,
        "server": server,
        "cache": cache_rates,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")

    print(f"cold baseline: {cold['rhs_per_s']:8.2f} RHS/s  "
          f"(p50 {cold['p50_latency_s'] * 1e3:7.2f} ms, "
          f"p99 {cold['p99_latency_s'] * 1e3:7.2f} ms)")
    print(f"\n{'B':>3s} {'RHS/s':>9s} {'vs cold':>8s} {'p50 ms':>8s} "
          f"{'p99 ms':>8s} {'occupancy':>10s}")
    for r in offline:
        print(f"{r['slab_width']:3d} {r['rhs_per_s']:9.2f} "
              f"{r['speedup_vs_cold']:7.2f}x "
              f"{r['p50_latency_s'] * 1e3:8.2f} "
              f"{r['p99_latency_s'] * 1e3:8.2f} "
              f"{r['mean_occupancy']:10.3f}")
    print(f"\nserver (mean gap {mean_gap * 1e3:.0f} ms):")
    for r in server:
        print(f"  B={r['slab_width']:2d}  {r['rhs_per_s']:8.2f} RHS/s  "
              f"p50 {r['p50_latency_s'] * 1e3:7.2f} ms  "
              f"p99 {r['p99_latency_s'] * 1e3:7.2f} ms")
    for kind, s in cache_rates.items():
        print(f"cache[{kind}]: hit_rate {s['hit_rate']:.3f} "
              f"(h {s['hits']} / m {s['misses']} / r {s['refactors']} "
              f"/ e {s['evictions']})")
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
