"""Entry point of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  The last line of standard output is the result object; without a TPU
(or outside a checkout) the run exits nonzero and prints no result.  See
``harness.py`` for what a run does.
"""

import pathlib
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import harness

    sys.exit(harness.main(t_start=T_START))
