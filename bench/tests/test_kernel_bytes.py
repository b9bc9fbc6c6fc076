"""The per-kernel least bytes add up to the iteration's."""
import json

import pytest

from bench import kernel_bytes as K, spec, yardstick

CONFIGS = [("poisson2d", 11_515_904, 4_182_016),
           ("heat2d", 52_527_700, 21_001_800)]


@pytest.mark.parametrize("name,iteration,sweep", CONFIGS)
def test_addends_sum_to_the_iteration_count(name, iteration, sweep):
    cfg = json.loads((spec.PACKAGE / "configs" / f"{name}.json").read_text())
    n, nnz, dt = cfg["n"], cfg["nnz"], cfg["dtype"]
    assert yardstick.least_bytes_per_iteration(n, nnz, dt) == iteration
    assert K.sweep_least_bytes(n, nnz, dt) == sweep
    assert K.sweep_least_bytes(n, nnz, dt) == \
        2 * K.spmv_least_bytes(n, nnz, dt)
    assert (K.spmv_least_bytes(n, nnz, dt) + K.sweep_least_bytes(n, nnz, dt)
            + K.vector_least_bytes(n, dt)) == iteration
