"""Share of the bandwidth roofline one PCG iteration reaches, in %.

Least bytes of an iteration (``bench.yardstick``, from the
configuration's n, nnz and dtype alone) times the iterations of the
window's solves, over the peak bandwidth of the device kind times the
seconds those solves spent in the program's device PCG
(``ICCGReport.solve_seconds``: the jitted loop and its
``block_until_ready``, the host path left out)."""
from bench.yardstick import least_bytes_per_iteration, peaks


def read(run):
    iters = sum(s.iterations for s in run.solves)
    device_s = sum(s.device_s for s in run.solves)
    if iters == 0 or device_s <= 0:
        return None
    cfg = run.config
    need = least_bytes_per_iteration(cfg["n"], cfg["nnz"], cfg["dtype"]) * iters
    bw = peaks(run.device_kind)["hbm_bytes_per_s"] * run.n_devices
    return 100.0 * need / (bw * device_s)
