"""Wall seconds per completed solve: the window (host clock inside
``plan.solve``, right-hand side in, solution out) over the solves in it.
``solve_s.step`` reads the same of the time-stepping cell, where each
implicit step is one solve."""


def read(run):
    return run.window_s / len(run.solves) if run.solves else None
