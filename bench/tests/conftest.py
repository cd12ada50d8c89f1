import os
import sys
from pathlib import Path

# the benchmark's tests run on the CPU, off the persistent compile cache
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
