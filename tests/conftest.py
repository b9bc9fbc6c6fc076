import os

import jax
import numpy as np
import pytest

# float64 is required for the solver-equivalence guarantees (the paper's
# Table 5.2 iteration counts are only bitwise-stable in double precision).
jax.config.update("jax_enable_x64", True)

try:
    # Bounded CI profile: capped examples, no deadline flakes, derandomized
    # so every CI run covers the same example set.  Local runs keep
    # hypothesis defaults (or the deterministic fallback engine in
    # tests/_hypothesis_stub.py when hypothesis is absent).
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("ci", max_examples=25, deadline=None,
                                   derandomize=True)
    if os.environ.get("CI"):
        _hyp_settings.load_profile("ci")
except ImportError:
    pass


@pytest.fixture(scope="module", autouse=True)
def _clear_jax_caches():
    """XLA:CPU's JIT linker accumulates dylibs per compiled executable; a
    full-suite run (~1000 compilations) can exhaust it ("Failed to
    materialize symbols").  Dropping the compilation cache between test
    modules keeps the process well under the limit."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
