"""python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell once in this process, which owns the chip(s).  The last
line of standard output is the result; without a TPU (or with fewer
chips than the cell asks for) it exits non-zero and prints none.
"""

import time

_PROC_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

from benchmarks.harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main(proc_t0=_PROC_T0))
