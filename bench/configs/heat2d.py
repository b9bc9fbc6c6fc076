"""The heat2d configuration's system: one backward-Euler step
I + r * gallery('poisson', m)."""
from bench import matrices


def matrix(cfg: dict):
    m = cfg["matrix"]
    return matrices.heat_step_2d(m["m"], m["r"])
