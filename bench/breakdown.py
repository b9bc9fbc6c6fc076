"""Where a cell's solve spends its time, by the program's scopes and spans.

    python3 -m bench.breakdown --workload <name> --seed <n> \
        [--host-solves 6] [--keep <dir>]

From the root of a checkout, on the chip.  The cell is set up as
``bench.run`` sets it up; then

1. ``--host-solves`` untraced solves of the window's first right-hand
   side read the host path from the program's report
   (``ICCGReport.embed_seconds``, ``extract_seconds``) and from the wall
   clock (wall less ``solve_seconds``, as ``solve_host_ms`` reads it);
2. the driver's window runs traced for ``TRACE_SECONDS`` as in
   ``bench.run --trace 1``, and the trace is reduced twice: by ``bench.trace`` (the
   harness's reduction) and by ``bench.scopes``, each timed.

One JSON line goes to standard output: the readings by the names a
per-layer metric would give them, the breakdowns, and the reduction
times.  The kernel readings take the median of the window's whole
calls (``bench.scopes``); a reading is ``None`` where the ops carry no
scope (a program without them), where the window holds no whole call,
or where the trace lost events.

The device ops of a TPU trace carry no ``op_name``, so the scopes come
from the HLO text that XLA dumps for each optimized module
(``--xla_dump_to``).  The command therefore compiles every executable
afresh, with the persistent compilation cache off; besides, JAX leaves
metadata out of the cache key, so a cache filled before the scopes
existed would hand back executables without them.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

from bench import kernel_bytes, run as harness, scopes, spec, trace as T
from bench.records import Run
from bench.yardstick import peaks

SWEEP, SPMV = scopes.SWEEP, "pcg.spmv"
EMBED, EXTRACT = "repro.solve.embed", "repro.solve.extract"


def readings(summary, cfg, device_kind, n_devices, steps_per_apply):
    """The scope readings of one reduced trace: the sweep's time per
    fused step and the sweep's and SpMV's shares of the bandwidth
    roofline (their least bytes over the peak times a call's median
    seconds), and the traced host path."""
    bw = peaks(device_kind)["hbm_bytes_per_s"] * n_devices
    n, nnz, dt = cfg["n"], cfg["nnz"], cfg["dtype"]
    sweep_s = scopes.median_call_s(summary, SWEEP)
    spmv_s = scopes.median_call_s(summary, SPMV)
    host = {} if summary.lost_events else summary.host_spans

    def mean_ms(name):
        count, seconds = host.get(name, (0, 0.0))
        return 1e3 * seconds / count if count else None
    return {
        "sweep_step_us": (1e6 * sweep_s / steps_per_apply
                          if sweep_s else None),
        "sweep_roofline": (100.0 * kernel_bytes.sweep_least_bytes(n, nnz, dt)
                           / (bw * sweep_s) if sweep_s else None),
        "spmv_roofline": (100.0 * kernel_bytes.spmv_least_bytes(n, nnz, dt)
                          / (bw * spmv_s) if spmv_s else None),
        "embed_ms_traced": mean_ms(EMBED),
        "extract_ms_traced": mean_ms(EXTRACT),
    }


def host_path(plan, b, cfg, solves: int) -> dict:
    """Mean host milliseconds per untraced solve: the report's embed and
    extract (None where the program lacks them) and wall less the PCG."""
    embed, extract, outside = [], [], []
    for _ in range(solves):
        t0 = time.perf_counter()
        rep = plan.solve(b, rtol=cfg["rtol"], maxiter=cfg["maxiter"])
        wall = time.perf_counter() - t0
        outside.append(wall - rep.solve_seconds)
        embed.append(getattr(rep, "embed_seconds", None))
        extract.append(getattr(rep, "extract_seconds", None))

    def mean_ms(xs):
        return (1e3 * sum(xs) / len(xs)
                if xs and None not in xs else None)
    return {"embed_ms": mean_ms(embed), "extract_ms": mean_ms(extract),
            "solve_host_ms": mean_ms(outside)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--host-solves", type=int, default=6)
    ap.add_argument("--keep", default=None,
                    help="copy the trace file into this directory")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    hlo_dir = tempfile.mkdtemp(prefix="bench_hlo_")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + f" "
                               f"--xla_dump_to={hlo_dir} "
                               f"--xla_dump_hlo_as_text").strip()
    try:
        devs = harness.devices_for(cell.chips)
    except harness.NoChip as e:
        print(f"bench.breakdown: {e}", file=sys.stderr)
        shutil.rmtree(hlo_dir, ignore_errors=True)
        return harness.NO_CHIP
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = cell.config
    harness.enable_precision(cfg)
    a = cell.matrix_module.matrix(cfg)
    driver = cell.driver_module.Driver(a, cfg, cell.traffic)
    driver.setup()
    seconds = harness.TRACE_SECONDS
    driver.prepare(args.seed, seconds)
    steps = 2 * driver.plan.n_rounds
    host = host_path(driver.plan, driver.b0, cfg, args.host_solves)

    run = Run(workload=cell.name, config=cfg, traffic=cell.traffic,
              device_kind=devs[0].device_kind, n_devices=len(devs),
              seconds=seconds)
    tdir = tempfile.mkdtemp(prefix="bench_breakdown_")
    try:
        harness.traced_window(driver, run, seconds, tdir)
        driver.release()
        path = T.find_xplane(tdir)
        if args.keep:
            shutil.copy(path, args.keep)
        t0 = time.perf_counter()
        T.reduce(T.read_xplane(path))
        t_trace = time.perf_counter() - t0
        t0 = time.perf_counter()
        hlo = scopes.hlo_scopes(p.read_text() for p in
                                Path(hlo_dir).glob("*after_optimizations*.txt"))
        summary = scopes.reduce(scopes.read(path, hlo))
        t_scopes = time.perf_counter() - t0
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
        shutil.rmtree(hlo_dir, ignore_errors=True)
    print(f"trace reduced in {t_trace:.1f} s (bench.trace), "
          f"{t_scopes:.1f} s (bench.scopes)", file=sys.stderr)
    traced = [s.wall_s - s.device_s for s in run.solves]
    out = {
        "workload": cell.name, "seed": args.seed,
        "steps_per_apply": steps,
        "readings": readings(summary, cfg, run.device_kind, run.n_devices,
                             steps),
        "host_path_untraced": host,
        "solve_host_ms_traced": 1e3 * sum(traced) / len(traced),
        "window_s": summary.base.window_s, "busy_s": summary.base.busy_s,
        "idle_share": summary.base.idle_share,
        "scoped_share": summary.scoped_share,
        "scope_s": summary.scope_s,
        "calls": {k: {"count": len(v), "median_s": statistics.median(v),
                      "min_s": min(v), "max_s": max(v)}
                  for k, v in summary.calls.items()},
        "host_spans": summary.host_spans,
        "lost_events": summary.lost_events,
        "device_ops": summary.device_ops, "idle_gaps": summary.idle_gaps,
        "reduce_s": {"bench.trace": t_trace, "bench.scopes": t_scopes},
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
