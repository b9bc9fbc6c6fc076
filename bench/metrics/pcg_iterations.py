"""Mean PCG iterations per completed solve, as the program counts them."""


def read(run):
    if not run.solves:
        return None
    return sum(s.iterations for s in run.solves) / len(run.solves)
