"""Run one benchmark cell on the chip and print one JSON result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``, ``bench/`` and
the program under ``src/``.  The run:

1. refuses to measure off the chip: it exits 2 and prints no result
   where JAX finds no TPU, or fewer chips than the cell asks for;
2. sets up (the configuration's matrix, the plan, the compile of every
   shape the cell's traffic uses, with JAX's persistent compilation cache
   inside the checkout) and counts all of it as ``setup_s``;
3. runs the cell's driver for ``--seconds``; with ``--trace 1`` the
   profiler records the first ``TRACE_SECONDS`` only, stopping in the
   middle of a solve if need be, and the driver runs on until that
   solve is done; the trace is reduced afterwards (``bench.trace``).
   A solve makes up to a million device events, which take minutes to
   write and reduce, and the profiler drops events past its buffer;
4. reads the device's peak memory, frees the program's state, and checks
   every answer of the window against the plain reference
   (``bench.reference``);
5. prints the compared numbers with their limits as the last lines of
   standard error, and the result as the last line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, spec, trace as tracing  # noqa: E402
from bench.records import Run, span  # noqa: E402

T_IMPORTS = time.perf_counter()

NO_CHIP = 2
TRACE_SECONDS = 1.0


class NoChip(RuntimeError):
    pass


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(chips: int, platform: str = "tpu"):
    """The chips a cell runs on; raises NoChip off the chip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"no {platform.upper()}: JAX's platform is "
                     f"{devs[0].platform!r}; this benchmark measures only "
                     f"on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache, at the program's fixed path
    inside the checkout (or ``JAX_COMPILATION_CACHE_DIR``), for every
    program however quick its compile."""
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache as enable
    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def enable_precision(config: dict) -> None:
    """A float64 configuration needs JAX's 64-bit mode, set before any
    array exists; other configurations run without it."""
    if config["dtype"] == "float64":
        import jax
        jax.config.update("jax_enable_x64", True)


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def measure(cell, args, devs, config=None,
            t_chip: float = 0.0) -> tuple[Run, dict, object]:
    """Set up, run the window (traced or not); returns the run, the
    device record and the matrix for the reference.  ``t_chip``: the
    seconds the caller spent finding the chips, for the set-up report."""
    config = cell.config if config is None else config
    enable_precision(config)
    t_matrix = time.perf_counter()
    run = Run(workload=cell.name, config=config, traffic=cell.traffic,
              device_kind=devs[0].device_kind, n_devices=len(devs),
              seconds=args.seconds)
    a = cell.matrix_module.matrix(config)
    t_plan = time.perf_counter()
    # the program gets its own copy: the reference's matrix stays the one
    # the benchmark generated, whatever the program does to its input
    driver = cell.driver_module.Driver(a.copy(), config, cell.traffic)
    run.plan_build_s = driver.setup()
    t_rhs = time.perf_counter()
    driver.prepare(args.seed, args.seconds)
    run.setup_s = time.perf_counter() - T_START
    print(f"setup {run.setup_s:.3f} s: imports {T_IMPORTS - T_START:.3f}, "
          f"jax and chip {t_chip:.3f}, "
          f"cell and cache {t_matrix - T_IMPORTS - t_chip:.3f}, "
          f"matrix {t_plan - t_matrix:.3f}, plan {run.plan_build_s:.3f}, "
          f"compile and warm-up {t_rhs - t_plan - run.plan_build_s:.3f}, "
          f"first rhs {T_START + run.setup_s - t_rhs:.3f}", file=sys.stderr,
          flush=True)
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        traced_window(driver, run, min(args.seconds, TRACE_SECONDS), tdir)
    else:
        driver.window(args.seconds, run)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}
    driver.release()
    if args.trace:
        import shutil
        t0 = time.perf_counter()
        try:
            run.trace = tracing.reduce(
                tracing.read_xplane(tracing.find_xplane(tdir)))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        print(f"trace reduced in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    return run, device, a


def traced_window(driver, run, seconds: float, tdir: str) -> None:
    """The driver's window of ``seconds`` on a worker thread, with the
    profiler on for exactly ``seconds`` of wall time; the solve running
    when the profiler stops completes untraced."""
    import threading

    import jax
    failure = []

    def work():
        try:
            driver.window(seconds, run)
        except BaseException as e:  # re-raised on the main thread
            failure.append(e)
    worker = threading.Thread(target=work, name="bench-window")
    jax.profiler.start_trace(tdir)
    try:
        with span(tracing.WINDOW_SPAN):
            worker.start()
            worker.join(seconds)
    finally:
        jax.profiler.stop_trace()
        worker.join()
    if failure:
        raise failure[0]


def _number(v):
    """A JSON number: non-finite values become the largest float."""
    if isinstance(v, int):
        return v
    v = float(v)
    return v if math.isfinite(v) else sys.float_info.max


def result_line(cell, run, device, checks, trace: bool) -> dict:
    metrics = {}
    for m in cell.reported(trace):
        value = m.reader.read(run)
        if value is not None:
            metrics[m.name] = {"value": _number(value), "unit": m.unit}
    answers = run.answers()
    failed = sum(1 for _, x, st in answers
                 if x is None or st != reference.CONVERGED)
    out = {"correct": reference.is_correct(checks),
           "attempted": len(answers), "failed": failed,
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = {k: {"value": _number(c["value"]), "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.resolve(args.workload)
    t0 = time.perf_counter()
    try:
        devs = devices_for(cell.chips)
    except NoChip as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return NO_CHIP
    t_chip = time.perf_counter() - t0
    enable_compile_cache()
    run, device, a = measure(cell, args, devs, t_chip=t_chip)
    checks = reference.judge(a, run.answers(), cell.config["limits"])
    out = result_line(cell, run, device, checks, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
