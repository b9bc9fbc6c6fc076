"""Drive a cell end to end on the CPU at a small grid, skipping only the
harness's look for a chip."""
import argparse
import copy

import jax

from bench import reference, run as harness, spec

SMALL_M = 40


def small_config(cell, m=SMALL_M, **override):
    cfg = copy.deepcopy(cell.config)
    cfg["matrix"]["m"] = m
    a = cell.matrix_module.matrix(cfg)
    cfg["n"], cfg["nnz"] = a.shape[0], a.nnz
    cfg.update(override)
    return cfg


def drive(workload, seed=2**31 + 11, seconds=0.5, m=SMALL_M, **override):
    """One run of ``workload`` on the CPU: (result line, run)."""
    cell = spec.resolve(workload)
    cfg = small_config(cell, m, **override)
    args = argparse.Namespace(workload=workload, seed=seed,
                              seconds=seconds, trace=0)
    run, device, a = harness.measure(cell, args, jax.devices(), config=cfg)
    checks = reference.judge(a, run.answers(), cfg["limits"])
    return harness.result_line(cell, run, device, checks, False), run
