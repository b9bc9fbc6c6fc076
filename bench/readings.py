"""Readings that the correctness limits are set from, on the chip.

    python3 -m bench.readings --workload <name> --seeds 12 --control-seeds 3 \
        --seconds <s> [--out readings.jsonl]

One process sets the cell up once, then runs its window on each program
seed and judges the answers as ``bench.run`` does; then it sets up the
control, the same cell with the plan one precision below the
configuration's (``CONTROL``: float32 for float64, bfloat16 for float32,
through the program's own dtype option), and does the same on the
control seeds.  Each seed prints one JSON line with
the compared numbers.  The lower reading of a number is its largest
value over the program's seeds, the upper its smallest over the
control's; the limits in ``bench/configs`` lie between (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import reference, run as harness, spec
from bench.records import Run

CONTROL = {"float64": "float32", "float32": "bfloat16"}


def readings(cell, config, seeds, seconds, devs, role):
    harness.enable_precision(config)
    a = cell.matrix_module.matrix(config)
    driver = cell.driver_module.Driver(a, config, cell.traffic)
    t0 = time.perf_counter()
    driver.setup()
    setup = time.perf_counter() - t0
    for seed in seeds:
        run = Run(workload=cell.name, config=config, traffic=cell.traffic,
                  device_kind=devs[0].device_kind, n_devices=len(devs),
                  seconds=seconds)
        driver.prepare(seed, seconds)
        driver.window(seconds, run)
        checks = reference.judge(a, run.answers(), config["limits"])
        iters = [s.iterations for s in run.solves]
        yield {"role": role, "workload": cell.name, "seed": seed,
               "dtype": config["dtype"], "setup_s": setup,
               "answers": len(run.answers()),
               "iterations": [min(iters), max(iters)] if iters else None,
               "statuses": sorted({st for _, _, st in run.answers()}),
               "correct": reference.is_correct(checks),
               "checks": {k: c["value"] for k, c in checks.items()}}
    driver.release()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    try:
        devs = harness.devices_for(cell.chips)
    except harness.NoChip as e:
        print(f"bench.readings: {e}", file=sys.stderr)
        return harness.NO_CHIP
    harness.enable_compile_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    control = [args.first_seed + 104729 * (i + 1)
               for i in range(args.control_seeds)]
    out = open(args.out, "a") if args.out else None
    try:
        for role, cfg, ss in (("program", cell.config, seeds),
                              ("control", dict(cell.config, dtype=CONTROL[
                                  cell.config["dtype"]]), control)):
            for line in readings(cell, cfg, ss, args.seconds, devs, role):
                text = json.dumps(line)
                print(text, flush=True)
                if out:
                    out.write(text + "\n")
                    out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
