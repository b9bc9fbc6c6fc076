"""Multi-device solver benchmark: the mesh-aware SolverPlan across device
counts.

    PYTHONPATH=src python -m benchmarks.bench_multidevice [--smoke]
        [--out BENCH_multidevice.json]

Per device count d: a mesh plan over a (d,)-mesh for hbmc/bmc x B in
{1, 8}, timing the raw distributed preconditioner apply (the fused sweep,
one all-gather per round) and the warm ``plan.solve``/``solve_batched``
wall-clock at a fixed iteration count.  ``d=1`` additionally records the
meshless plan as the no-collectives baseline.

How the device counts are made depends on the platform:

* On a TPU host one process holds every chip (a chip belongs to one
  process) and builds sub-meshes of the first d chips, for each d in
  ``DEVICE_COUNTS`` that the host has.
* Elsewhere JAX pins the device count at first init, so the parent spawns
  one child per forced host device count
  (``XLA_FLAGS=--xla_force_host_platform_device_count``) and merges their
  rows.  On a CPU host those "devices" are XLA host-platform threads, so
  the rows track the COST of distribution (collective per round +
  replicated state) rather than a speedup — the tripwire is that
  semantics hold (identical iteration counts, see ``iters_equal``).

Emits ``BENCH_multidevice.json`` (schema ``bench_multidevice/v1``).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

BS_DEFAULT, W_DEFAULT = 16, 8
BATCHES = (1, 8)
DEVICE_COUNTS = (1, 2, 4, 8)
METHODS = ("hbmc", "bmc")


# ---------------------------------------------------------------------------
# Rows for one device count: a mesh over the given devices.
# ---------------------------------------------------------------------------

def _rows(args, devices) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.matrices import laplace_2d
    from repro.core.plan import build_plan

    n_dev = len(devices)
    if args.smoke:
        a, bs, w = laplace_2d(16, 14), 8, 4
    else:
        a, bs, w = laplace_2d(64, 64), BS_DEFAULT, W_DEFAULT
    n = a.shape[0]
    rng = np.random.default_rng(42)
    b1 = rng.normal(size=n)
    bb = rng.normal(size=(n, max(BATCHES)))
    mesh = Mesh(np.array(devices), ("data",))

    def time_best(fn, reps):
        fn()                                   # compile + warm caches
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    rows = []
    plans = {}
    for method in METHODS:
        plans[(method, True)] = build_plan(a, method=method, block_size=bs,
                                           w=w, mesh=mesh)
        if n_dev == 1:                         # meshless baseline
            plans[(method, False)] = build_plan(a, method=method,
                                                block_size=bs, w=w)
    for (method, meshed), plan in sorted(plans.items()):
        tab = plan._precond.tables
        dim = tab.m
        for batch in BATCHES:
            r = jnp.asarray(rng.normal(
                size=(dim,) if batch == 1 else (dim, batch)))
            if plan.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                r = jax.device_put(r, NamedSharding(mesh, P()))
            apply_fn = (plan._precond if batch == 1
                        else plan._precond.apply_batched)
            # jit the apply: an eager shard_map closure would re-trace per
            # call, so the un-jitted number is compile time, not the sweep
            apply_jit = jax.jit(lambda rr, f=apply_fn: f(rr))
            apply_us = time_best(
                lambda: apply_jit(r).block_until_ready(), args.reps)
            # real tolerance (not rtol=0): the recorded iteration counts are
            # the actual Krylov trajectory, so `iters_equal` across device
            # counts is a meaningful semantics tripwire
            kw = dict(rtol=1e-7, maxiter=args.maxiter)
            if batch == 1:
                plan.solve(b1, **kw)           # warm compile
                rep = plan.solve(b1, **kw)
                its = int(rep.result.iterations)
            else:
                plan.solve_batched(bb[:, :batch], **kw)
                rep = plan.solve_batched(bb[:, :batch], **kw)
                its = int(np.max(rep.result.iterations))
            rows.append({
                "n_devices": n_dev, "mesh": meshed, "method": method,
                "B": batch, "n": int(n),
                "rounds": int(tab.n_steps),
                "lanes": [int(r) for _, r in tab.segments],
                "apply_us": round(apply_us, 1),
                "solve_us": round(rep.solve_seconds * 1e6, 1),
                "iterations": its,
            })
    return rows


def _child(args) -> None:
    """Runs under a forced host device count; writes its rows to
    ``--child-out``."""
    import jax

    n_dev = args.devices
    assert len(jax.devices()) == n_dev, (len(jax.devices()), n_dev)
    with open(args.child_out, "w") as f:
        json.dump(_rows(args, jax.devices()), f)


# ---------------------------------------------------------------------------
# Parent: rows per device count, merged doc + derived breakdown.
# ---------------------------------------------------------------------------

def _derived(rows):
    """Per-(method, B) apply/solve trajectory over device counts, relative
    to the 1-device mesh row, plus the semantics tripwire."""
    out = {}
    base = {(r["method"], r["B"]): r for r in rows
            if r["mesh"] and r["n_devices"] == 1}
    for r in rows:
        if not r["mesh"]:
            continue
        b = base.get((r["method"], r["B"]))
        if b is None:
            continue
        key = f"{r['method']}_B{r['B']}"
        entry = out.setdefault(key, {"apply_us_by_devices": {},
                                     "solve_us_by_devices": {},
                                     "iters_equal": True})
        d = str(r["n_devices"])
        entry["apply_us_by_devices"][d] = r["apply_us"]
        entry["solve_us_by_devices"][d] = r["solve_us"]
        entry["iters_equal"] &= (r["iterations"] == b["iterations"])
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem + few reps (CI)")
    ap.add_argument("--out", default="BENCH_multidevice.json")
    ap.add_argument("--maxiter", type=int, default=None)
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--devices", type=int, default=None,
                    help="(child) forced device count")
    ap.add_argument("--child-out", default=None, help="(child) row file")
    args = ap.parse_args()
    # defaults sit ABOVE the convergence point of the bench problems (~8
    # iters smoke, ~43 full), so the recorded counts are the real Krylov
    # trajectory and `iters_equal` is a meaningful tripwire, never the cap
    args.maxiter = args.maxiter or (50 if args.smoke else 120)
    args.reps = args.reps or (3 if args.smoke else 10)

    import jax

    jax.config.update("jax_enable_x64", True)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.child_out is not None:
        _child(args)
        return

    platform = jax.default_backend()
    if platform == "tpu":
        devs = jax.devices()
        rows = []
        for n_dev in DEVICE_COUNTS:
            if n_dev <= len(devs):
                print(f"[bench_multidevice] devices={n_dev} ...", flush=True)
                rows.extend(_rows(args, devs[:n_dev]))
    else:
        rows = _spawn_children(args)

    doc = {
        "schema": "bench_multidevice/v1",
        "platform": platform,
        "smoke": bool(args.smoke),
        "maxiter": args.maxiter,
        "device_counts": sorted({r["n_devices"] for r in rows}),
        "results": rows,
        "derived": _derived(rows),
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")

    hdr = (f"{'devices':>7s} {'mesh':>5s} {'method':7s} {'B':>2s} "
           f"{'apply us':>10s} {'solve us':>12s} {'iters':>6s}")
    print(hdr)
    for r in rows:
        print(f"{r['n_devices']:7d} {str(r['mesh']):>5s} {r['method']:7s} "
              f"{r['B']:2d} {r['apply_us']:10.1f} {r['solve_us']:12.0f} "
              f"{r['iterations']:6d}")
    for k, v in doc["derived"].items():
        flag = "OK" if v["iters_equal"] else "MISMATCH"
        print(f"  {k:12s} iters {flag}  apply {v['apply_us_by_devices']}")
    print(f"wrote {args.out}")


def _spawn_children(args) -> list[dict]:
    """One child process per forced host device count (CPU only)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = []
    for n_dev in DEVICE_COUNTS:
        with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
            child_out = f.name
        env = dict(os.environ)
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_dev} "
                            + env.get("XLA_FLAGS", "")).strip()
        env["PYTHONPATH"] = (os.path.join(repo, "src") + os.pathsep
                             + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
        cmd = [sys.executable, "-m", "benchmarks.bench_multidevice",
               "--devices", str(n_dev), "--child-out", child_out,
               "--maxiter", str(args.maxiter), "--reps", str(args.reps)]
        if args.smoke:
            cmd.append("--smoke")
        print(f"[bench_multidevice] devices={n_dev} ...", flush=True)
        proc = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                              text=True, timeout=1800)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-4000:])
            raise SystemExit(f"child failed for devices={n_dev}")
        with open(child_out) as f:
            rows.extend(json.load(f))
        os.unlink(child_out)
    return rows


if __name__ == "__main__":
    main()
