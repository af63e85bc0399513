"""Entry point: ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.

Runs on the chip it is started on; exits non-zero without a result line
where JAX finds no TPU or fewer chips than the cell asks for.  JAX's
persistent compilation cache is kept at ``.jax_cache/`` in the checkout,
so only the first run of a cell there compiles.
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    from chipbench.harness import main

    sys.exit(main())
