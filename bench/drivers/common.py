"""Plan construction and right-hand sides shared by the drivers."""
from __future__ import annotations

import numpy as np


def plan_knobs(cfg: dict) -> dict:
    """``build_plan`` keyword arguments of a configuration."""
    import jax.numpy as jnp
    return dict(cfg["plan"], dtype=jnp.dtype(cfg["dtype"]))


def rhs_dtype(cfg: dict):
    """Host dtype of the right-hand sides: the plan's."""
    import jax.numpy as jnp
    return np.dtype(jnp.dtype(cfg["dtype"]))


def draw(rng: np.random.Generator, law: str, n: int, dtype) -> np.ndarray:
    """One seeded right-hand side of the traffic's law."""
    if law != "standard_normal":
        raise ValueError(f"unknown right-hand-side law {law!r}")
    return rng.standard_normal(n, dtype=np.float32).astype(dtype, copy=False)
