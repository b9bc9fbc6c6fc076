"""CPU tests of the benchmark: ``JAX_PLATFORMS=cpu python3 -m pytest bench/tests``
from the root of the checkout."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
