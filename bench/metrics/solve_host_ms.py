"""Mean host milliseconds per solve outside the device PCG: the wall time
of ``plan.solve`` less the program's ``ICCGReport.solve_seconds`` (the
span around the jitted PCG and its ``block_until_ready``).  This is the
permute, embed, transfer in, extract and un-permute of the host path."""


def read(run):
    if not run.solves:
        return None
    return 1e3 * sum(s.wall_s - s.device_s for s in run.solves) / len(run.solves)
