"""One cell, once, in a new process:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds everything from the seed, warms the cell's own shapes, measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard
output. Without an accelerator (or with fewer chips than the cell asks
for) it exits non-zero and prints no result."""

import os
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# jax reads the variable when it is imported, and the program takes the
# directory it names: a fixed path inside the checkout, unless the
# machine placed one from outside
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_compile_cache")
)
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark.harness import core

    sys.exit(core.main(sys.argv[1:], t0=T0))
