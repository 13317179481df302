import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# the benchmark's tests run on the CPU at smoke widths; they load no TPU
# library (the chip runs are the benchmark's own, through run.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
