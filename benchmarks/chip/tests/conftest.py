"""Self-tests of the benchmark, on the CPU at tiny sizes:
``python -m pytest benchmarks/chip/tests``."""
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# compiles of these CPU tests go to a cache of their own, never into the
# checkout's, which the benchmark reads on the chip
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="bench-tests-jax-cache-")
