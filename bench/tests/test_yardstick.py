"""The least-bytes count and the peak table."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import matrices, yardstick


def test_count_on_a_small_matrix_by_hand():
    # 3 x 3 grid: n = 9, 12 edges -> nnz = 9 + 24 = 33
    a = matrices.poisson_2d(3)
    n, nnz = a.shape[0], a.nnz
    assert (n, nnz) == (9, 33)
    # one triangle: 12 off-diagonal + 9 diagonal values, 12 indices
    tri = 21 * 4 + 12 * 4
    assert yardstick.triangle_bytes(n, nnz, 4) == tri
    assert yardstick.least_bytes_per_iteration(n, nnz, np.float32) == \
        3 * tri + 10 * 9 * 4
    # float64 doubles the values, not the indices
    assert yardstick.least_bytes_per_iteration(n, nnz, "float64") == \
        3 * (21 * 8 + 12 * 4) + 10 * 9 * 8


def test_asymmetric_count_is_refused():
    with pytest.raises(ValueError):
        yardstick.triangle_bytes(9, 32, 4)


@pytest.mark.parametrize("lane_multiple", [1, 4, 128])
def test_count_ignores_padding_and_lies_under_the_plans_own_bytes(
        lane_multiple):
    """Plans padded to other lane multiples differ in their tables; the
    yardstick reads only (n, nnz, dtype), so it stays put, and it stays
    under the bytes the program's own traffic model counts for the plan."""
    from repro.analysis.traffic import traffic_report
    from repro.core import build_plan

    a = matrices.poisson_2d(24)
    least = yardstick.least_bytes_per_iteration(a.shape[0], a.nnz,
                                                "float32")
    assert least == yardstick.least_bytes_per_iteration(576, a.nnz,
                                                        np.float32)
    plan = build_plan(a, dtype=jnp.float32, lane_multiple=lane_multiple)
    assert plan.slab_m % lane_multiple == 0
    packed = traffic_report(plan, measure=False).iteration_bytes
    assert least < packed


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        yardstick.peaks("TPU v99")
    assert yardstick.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
