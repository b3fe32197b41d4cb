"""Run one benchmark cell once, from the root of a checkout:

    python3 chipbench/run.py --workload <config>.<traffic> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers compared with the reference, each with its limit, are the last
lines of standard error. Exits 2, printing no result, where JAX finds no
TPU, fewer chips than the cell asks for, a chip that ``peaks.json`` does
not list, or no program under ``src/``.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench.harness import main

    sys.exit(main(t_process=T_PROCESS))
