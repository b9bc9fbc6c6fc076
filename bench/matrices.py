"""The benchmark's own generators of its configurations' systems.

Both are public model problems on an m x m interior grid of the unit
square with homogeneous Dirichlet boundaries:

* ``poisson_2d(m)``: the 5-point finite-difference Poisson matrix
  without the 1/h^2 scale, MATLAB's ``gallery('poisson', m)``: 4 on the
  diagonal, -1 for each of the up to four grid neighbours, rows in
  natural (lexicographic) order.  It is ``kron(I, T) + kron(T, I)`` with
  ``T = tridiag(-1, 2, -1)``.
* ``heat_step_2d(m, r)``: one backward-Euler step of the heat equation
  u_t = u_xx + u_yy on the same grid, ``I + r * poisson_2d(m)`` with
  ``r = dt / h^2``.

The benchmark builds its matrices itself, so the yardstick cannot move
through a change to the program's generators.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def poisson_2d(m: int) -> sp.csr_matrix:
    """gallery('poisson', m): the m^2 x m^2 5-point Poisson matrix."""
    t = sp.diags([-np.ones(m - 1), np.full(m, 2.0), -np.ones(m - 1)],
                 [-1, 0, 1], format="csr")
    i = sp.identity(m, format="csr")
    a = (sp.kron(i, t, format="csr") + sp.kron(t, i, format="csr")).tocsr()
    a.sort_indices()
    return a


def heat_step_2d(m: int, r: float) -> sp.csr_matrix:
    """I + r * gallery('poisson', m): one backward-Euler step, r = dt/h^2."""
    a = (sp.identity(m * m, format="csr") + r * poisson_2d(m)).tocsr()
    a.sort_indices()
    return a
