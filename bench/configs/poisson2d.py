"""The poisson2d configuration's system: gallery('poisson', m)."""
from bench import matrices


def matrix(cfg: dict):
    return matrices.poisson_2d(cfg["matrix"]["m"])
