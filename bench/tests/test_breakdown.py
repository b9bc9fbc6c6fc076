"""The scope breakdown command, as far as the CPU can take it."""
import os
import subprocess
import sys

import numpy as np

from bench import breakdown, spec
from bench.tests.small import small_config


def test_off_the_chip_it_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-m", "bench.breakdown",
                        "--workload", "heat2d.timestep", "--seed", "3"],
                       cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_host_path_reads_the_reports_of_untraced_solves():
    from bench.drivers.common import plan_knobs
    from repro.core import build_plan

    cell = spec.resolve("heat2d.timestep")
    cfg = small_config(cell)
    plan = build_plan(cell.matrix_module.matrix(cfg), **plan_knobs(cfg))
    b = np.ones(plan.n, dtype=np.float32)
    host = breakdown.host_path(plan, b, cfg, solves=2)
    assert host["embed_ms"] > 0 and host["extract_ms"] > 0
    # wall less the PCG is the host path: embed, extract and the rest
    assert host["solve_host_ms"] >= host["embed_ms"] + host["extract_ms"]
