"""The hpcg3d configuration's system: the HPCG 3.1 reference benchmark's
operator (``GenerateProblem``), generated here and not taken from the
program.

On an m x m x m grid, row ``i = iz*m*m + iy*m + ix`` (natural order, x
fastest) holds 26 on the diagonal and -1 for each of its up to 26
neighbours ``(ix+sx, iy+sy, iz+sz)``, ``sx, sy, sz`` in {-1, 0, 1}, that
lie inside the grid.  With ``T`` the m x m tridiagonal matrix of ones
that is ``27 I - T (x) T (x) T``: rows of 8, 12, 18 and 27 entries at
corners, edges, faces and the interior, (3m - 2)^3 nonzeros in all.
"""
import numpy as np
import scipy.sparse as sp


def hpcg_27pt(m: int) -> sp.csr_matrix:
    """HPCG's 27-point operator on an m^3 grid (module docstring)."""
    t = sp.diags([np.ones(m - 1), np.ones(m), np.ones(m - 1)], [-1, 0, 1],
                 format="csr")
    cube = sp.kron(t, sp.kron(t, t, format="csr"), format="csr")
    a = (27.0 * sp.identity(m ** 3, format="csr") - cube).tocsr()
    a.sort_indices()
    return a


def matrix(cfg: dict):
    return hpcg_27pt(cfg["matrix"]["m"])
