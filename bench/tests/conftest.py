"""Tests of the benchmark's own yardstick. They run on the CPU, by hand:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

(they lie outside the repository's ``testpaths``)."""
import os
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
jax.config.update("jax_enable_x64", True)
