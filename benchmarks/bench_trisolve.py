"""Trisolve + SpMV hot-loop benchmark: layouts, backends, iteration parts.

Compares the two PCG-loop layouts (``layout="index"`` — the pre-refactor
path that gathers/scatters between index space and the solve layout on
every preconditioner apply — against ``layout="round_major"`` — the native
path where the whole loop lives in execution-order coordinates and the
fwd+bwd sweeps run fused), across backends and batch sizes, and breaks ONE
PCG iteration into its parts (SpMV, preconditioner apply, vector work —
dots/axpys/norm) per backend pair so the trajectory tracks the full
iteration, not just the apply.

    PYTHONPATH=src python -m benchmarks.bench_trisolve [--smoke]
        [--out BENCH_trisolve.json]

Emits machine-readable ``BENCH_trisolve.json`` (schema ``bench_trisolve/v2``)
so the perf trajectory is tracked PR over PR; CI runs ``--smoke`` and
uploads the file as an artifact.  Off-TPU the Pallas rows (trisolve AND
SpMV kernels) run in interpret mode — they measure semantics/dispatch, not
TPU performance (``derived`` speedups therefore come from the compiled XLA
rows).
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

from repro.core import (LAYOUTS,  # noqa: E402
                        build_round_major_preconditioner_from_rounds, sell,
                        solve_iccg, solve_iccg_batched)
from repro.core.ic0 import ic0_refactor, ic0_structure  # noqa: E402
from repro.core.matrices import laplace_2d, laplace_3d  # noqa: E402
from repro.core.plan import _make_spmv  # noqa: E402
from repro.core.solvers import _build_operators, _order_system  # noqa: E402

BS, W = 8, 8
BATCHES = (1, 8)
SPMV_BACKENDS = ("xla", "pallas")


def _problems(smoke: bool):
    if smoke:
        return [("lap2d_tiny", laplace_2d(16, 14)),
                ("lap3d_tiny", laplace_3d(6, 6, 5))]
    return [("lap2d_64", laplace_2d(64, 64)),
            ("lap3d_16", laplace_3d(16, 16, 16))]


def _time_call(fn, args, reps):
    """Best-of-reps call time for a function returning any pytree (min is
    robust to scheduler noise)."""
    jax.block_until_ready(fn(*args))         # compile + warm cache
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _time_apply(apply_fn, r, reps):
    """Best-of-reps per-apply time."""
    return _time_call(apply_fn, (r,), reps)


@jax.jit
def _vec_work_single(x, r, p, ap, z, rz):
    """The non-SpMV, non-precond part of one PCG step (dots/axpys/norm)."""
    alpha = rz / jnp.vdot(p, ap)
    x = x + alpha * p
    r = r - alpha * ap
    rz_new = jnp.vdot(r, z)
    beta = rz_new / rz
    p = z + beta * p
    return x, r, p, rz_new, jnp.linalg.norm(r)


@jax.jit
def _vec_work_batched(x, r, p, ap, z, rz):
    pap = jnp.einsum("nb,nb->b", p, ap)
    alpha = rz / pap
    x = x + alpha[None, :] * p
    r = r - alpha[None, :] * ap
    rz_new = jnp.einsum("nb,nb->b", r, z)
    beta = rz_new / rz
    p = z + beta[None, :] * p
    return x, r, p, rz_new, jnp.linalg.norm(r, axis=0)


def bench_iteration_breakdown(name, a, *, reps):
    """One PCG iteration split into its parts, native round-major layout.

    Rows: (component ∈ {spmv, precond, vector}) × (backend ∈ {xla, pallas};
    vector work is always compiled XLA) × B ∈ {1, 8}, all on the SELL-w
    operand so the two SpMV backends price the same layout.
    """
    rng = np.random.default_rng(7)
    sysd = _order_system(sp.csr_matrix(a), None, "hbmc", BS, W)
    # factor once; the Pallas sweep packs its rounds at one width, the XLA
    # sweep per segment, so each backend prices its own layout
    st = ic0_structure(sysd.a_bar, sysd.fwd_rounds)
    l_bar = ic0_refactor(st, sysd.a_bar)
    precs = {tb: build_round_major_preconditioner_from_rounds(
                 l_bar, sysd.fwd_rounds, sysd.bwd_rounds,
                 drop_mask=sysd.drop, backend=tb)
             for tb in SPMV_BACKENDS}
    rm = precs["xla"][1]
    a_rm = sell.permute_round_major(sysd.a_bar, rm)
    sm = sell.pack_sell(a_rm, W)
    vals, cols = jnp.asarray(sm.vals), jnp.asarray(sm.cols)
    m = rm.m
    rows = []

    def row(component, backend, batch, us):
        rows.append({"problem": name, "n": int(a.shape[0]), "m": int(m),
                     "component": component, "backend": backend,
                     "B": batch, "us": round(us, 1)})

    for batch in BATCHES:
        shape = (m,) if batch == 1 else (m, batch)
        r = jnp.asarray(rng.normal(size=shape))
        for sb in SPMV_BACKENDS:
            spmv = jax.jit(_make_spmv("sell", m, vals, cols,
                                      batched=batch != 1, spmv_backend=sb))
            row("spmv", sb, batch, _time_apply(spmv, r, reps))
        for tb in SPMV_BACKENDS:
            pre, lay = precs[tb]
            apply_fn = pre if batch == 1 else pre.apply_batched
            r_tb = r if lay.m == m else jnp.asarray(
                rng.normal(size=(lay.m,) + shape[1:]))
            row("precond", tb, batch, _time_apply(apply_fn, r_tb, reps))
        vw = _vec_work_single if batch == 1 else _vec_work_batched
        rz = jnp.asarray(1.0) if batch == 1 else jnp.ones(batch)
        row("vector", "xla", batch, _time_call(vw, (r, r, r, r, r, rz),
                                               reps))
    return rows


def bench_problem(name, a, *, maxiter, reps, smoke, backends):
    """One row per (layout, backend, B): precond-apply and PCG wall-clock."""
    rng = np.random.default_rng(42)
    n = a.shape[0]
    b1 = rng.normal(size=n)
    bb = rng.normal(size=(n, max(BATCHES)))
    rows = []
    sysd = _order_system(sp.csr_matrix(a), None, "hbmc", BS, W)
    for layout in LAYOUTS:
        for backend in backends:
            # --- raw preconditioner apply (the per-iteration hot spot) ----
            # one operator build serves both batch sizes (single-RHS apply
            # via __call__, multi-RHS via apply_batched)
            precond, _, rm = _build_operators(
                sysd, 0.0, "ell", W, jnp.float64, backend, None, layout,
                batched=False)
            dim = rm.m if rm is not None else sysd.n_padded
            apply_us = {}
            for batch in BATCHES:
                apply_fn = precond if batch == 1 else precond.apply_batched
                r = jnp.asarray(rng.normal(
                    size=(dim,) if batch == 1 else (dim, batch)))
                apply_us[batch] = _time_apply(apply_fn, r, reps)
            # --- full PCG loop at fixed maxiter (rtol=0 -> exact count) ---
            # Pallas solves off-TPU run the interpreter inside a while_loop;
            # skip them outside smoke mode (apply timing above still covers
            # the kernel), matching paper_tables.backend_table's caveat.
            solve_us = {}
            iterations = {}
            if backend == "xla" or smoke:
                for batch in BATCHES:
                    kw = dict(method="hbmc", block_size=BS, w=W, rtol=0.0,
                              maxiter=maxiter, backend=backend, layout=layout)
                    if batch == 1:
                        solve_iccg(a, b1, **kw)            # warm compile
                        rep = solve_iccg(a, b1, **kw)
                        its = rep.result.iterations
                    else:
                        bj = bb[:, :batch]
                        solve_iccg_batched(a, bj, **kw)
                        rep = solve_iccg_batched(a, bj, **kw)
                        its = int(np.max(rep.result.iterations))
                    solve_us[batch] = rep.solve_seconds * 1e6
                    iterations[batch] = int(its)
            for batch in BATCHES:
                rows.append({
                    "problem": name, "n": int(n), "layout": layout,
                    "backend": backend, "B": batch,
                    "apply_us": round(apply_us[batch], 1),
                    "solve_us": (round(solve_us[batch], 1)
                                 if batch in solve_us else None),
                    "iterations": iterations.get(batch),
                })
    return rows


def derive_speedups(rows):
    """round-major-native speedup over the index path, compiled XLA rows."""
    out = {}
    key = lambda r: (r["problem"], r["B"])
    index_rows = {key(r): r for r in rows
                  if r["layout"] == "index" and r["backend"] == "xla"}
    for r in rows:
        if r["layout"] != "round_major" or r["backend"] != "xla":
            continue
        base = index_rows.get(key(r))
        if base is None:
            continue
        entry = {"apply_speedup": round(base["apply_us"] / r["apply_us"], 3)}
        if base["solve_us"] and r["solve_us"]:
            entry["solve_speedup"] = round(base["solve_us"] / r["solve_us"],
                                           3)
        out[f"{r['problem']}_B{r['B']}"] = entry
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problems + interpret-mode pallas (CI)")
    ap.add_argument("--out", default="BENCH_trisolve.json")
    ap.add_argument("--maxiter", type=int, default=None)
    ap.add_argument("--reps", type=int, default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    maxiter = args.maxiter or (10 if args.smoke else 60)
    reps = args.reps or (3 if args.smoke else 10)
    backends = ("xla", "pallas")

    rows = []
    breakdown = []
    for name, a in _problems(args.smoke):
        rows.extend(bench_problem(name, a, maxiter=maxiter, reps=reps,
                                  smoke=args.smoke, backends=backends))
        breakdown.extend(bench_iteration_breakdown(name, a, reps=reps))

    doc = {
        "schema": "bench_trisolve/v2",
        "platform": jax.default_backend(),
        "smoke": bool(args.smoke),
        "maxiter": maxiter,
        "block_size": BS,
        "w": W,
        "results": rows,
        "iteration_breakdown": breakdown,
        "derived": derive_speedups(rows),
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")

    hdr = (f"{'problem':12s} {'layout':12s} {'backend':7s} {'B':>2s} "
           f"{'apply us':>10s} {'solve us':>12s}")
    print(hdr)
    for r in rows:
        solve = f"{r['solve_us']:12.0f}" if r["solve_us"] else " " * 12
        print(f"{r['problem']:12s} {r['layout']:12s} {r['backend']:7s} "
              f"{r['B']:2d} {r['apply_us']:10.1f} {solve}")
    print("\nper-iteration breakdown (round-major, SELL operand):")
    print(f"{'problem':12s} {'component':10s} {'backend':7s} {'B':>2s} "
          f"{'us':>10s}")
    for r in breakdown:
        print(f"{r['problem']:12s} {r['component']:10s} {r['backend']:7s} "
              f"{r['B']:2d} {r['us']:10.1f}")

    print("\nround-major-native speedup over index layout (xla):")
    for k, v in doc["derived"].items():
        parts = [f"apply {v['apply_speedup']:.2f}x"]
        if "solve_speedup" in v:
            parts.append(f"solve {v['solve_speedup']:.2f}x")
        print(f"  {k:20s} {'  '.join(parts)}")
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
