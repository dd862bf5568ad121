"""The benchmark's own tests, run by hand (the repository's pytest.ini
collects ``tests/`` only):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402

# CPU runs keep their compiled programs out of the checkout's cache.
run.CACHE_DIR = tempfile.mkdtemp(prefix="bench-tests-jax-cache-")
