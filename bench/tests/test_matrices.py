"""The benchmark's generators build the model problems their sources
define, checked against a plain element-by-element construction."""
import json

import numpy as np
import pytest
import scipy.sparse as sp

from bench import matrices
from bench.spec import PACKAGE


def poisson_by_stencil(m):
    """gallery('poisson', m) written out: row i*m+j couples to its four
    grid neighbours with -1, 4 on the diagonal, Dirichlet boundaries."""
    a = sp.lil_matrix((m * m, m * m))
    for i in range(m):
        for j in range(m):
            k = i * m + j
            a[k, k] = 4.0
            for di, dj in ((-1, 0), (1, 0), (0, -1), (0, 1)):
                if 0 <= i + di < m and 0 <= j + dj < m:
                    a[k, (i + di) * m + j + dj] = -1.0
    return a.tocsr()


@pytest.mark.parametrize("m", [1, 2, 3, 7, 12])
def test_poisson_is_the_5_point_stencil(m):
    ours = matrices.poisson_2d(m)
    assert (ours != poisson_by_stencil(m)).nnz == 0
    assert ours.nnz == 5 * m * m - 4 * m
    assert ours.has_sorted_indices


@pytest.mark.parametrize("r", [0.25, 1.0, 16.0])
def test_heat_step_is_identity_plus_r_poisson(r):
    m = 9
    ours = matrices.heat_step_2d(m, r)
    want = sp.identity(m * m) + r * poisson_by_stencil(m)
    assert abs(ours - want).max() == 0
    assert np.allclose(ours.diagonal(), 1 + 4 * r)


@pytest.mark.parametrize("name", ["poisson2d", "heat2d"])
def test_configuration_sizes_match_their_generators(name):
    """n and nnz in the configuration files (the yardstick's inputs) are
    those of the matrices the files generate: m^2 rows, 5 m^2 - 4 m
    nonzeros."""
    cfg = json.loads((PACKAGE / "configs" / f"{name}.json").read_text())
    m = cfg["matrix"]["m"]
    assert cfg["n"] == m * m
    assert cfg["nnz"] == 5 * m * m - 4 * m
