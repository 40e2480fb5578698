import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (BENCH_DIR, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
# Children of these tests run on the CPU and keep no compile cache.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["RBT_JAX_CACHE"] = "0"
