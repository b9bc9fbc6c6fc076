"""``correct`` comes out false for the control and for planted faults.

The control is the program's own lower-precision path: the same plan one
precision below the configuration's (float32 for poisson2d's float64,
bfloat16 for heat2d's float32).  Each fault is planted underneath the
timed path while the harness runs as usual."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.readings import CONTROL
from bench.tests.small import drive
from repro.core import plan as plan_mod

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_one_precision_below_is_not_correct(workload):
    """At m = 64: float32's drift from the true residual grows with the
    grid, and at the default m = 40 it reads under 3x poisson2d's limit
    (2.6e-6), against 3.5e-5 or more at the cell's own size on the chip."""
    dtype = CONTROL[spec.resolve(workload).config["dtype"]]
    out, _ = drive(workload, dtype=dtype, m=64)
    assert out["correct"] is False
    res = out["checks"]["true_res_max"]
    assert res["value"] > 3 * res["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_stopping_test_a_hundred_times_looser_is_not_correct(workload):
    """PCG stopped at 100 x the configured tolerance: fewer iterations,
    and an answer that no longer meets the configuration's limit."""
    rtol = 100 * spec.resolve(workload).config["rtol"]
    out, run = drive(workload, rtol=rtol)
    assert out["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_solve_that_returns_its_state_unchanged(monkeypatch, workload):
    """The PCG loop hands back its initial state (x = 0), reported as
    converged."""
    real = plan_mod.SolverPlan._run_pcg

    def unchanged(self, batched, rtol, maxiter, record_history, b_dev,
                  *a, **k):
        x, it, relres, status, hist = real(self, batched, rtol, maxiter,
                                           record_history, b_dev, *a, **k)
        return jnp.zeros_like(x), it, relres, status, hist
    monkeypatch.setattr(plan_mod.SolverPlan, "_run_pcg", unchanged)
    out, _ = drive(workload)
    assert out["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_answer_altered_where_it_is_produced(monkeypatch, workload):
    """One entry of the solution changed on its way out of the plan."""
    real = plan_mod.SolverPlan._extract

    def altered(self, x_dev):
        x = np.array(real(self, x_dev))
        x[len(x) // 2] += 1.0
        return x
    monkeypatch.setattr(plan_mod.SolverPlan, "_extract", altered)
    out, _ = drive(workload)
    assert out["correct"] is False
