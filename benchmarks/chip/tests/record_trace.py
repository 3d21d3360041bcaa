"""Record the small chip trace that the CPU tests reduce.

    python3 benchmarks/chip/tests/record_trace.py --out <file.json> \\
        --workload sage-products.offline --seed <n> --seconds 5 --trace 1

Makes one run of the cell as ``run.py`` does (its result line printed as
usual), keeps the profile that the harness reduced, and writes
``trace_reduce.trim`` of it to ``--out`` (``tests/data/products_trace_small.json``
is such a file).  Needs the chip, as a traced run does.
"""

import json
import pathlib
import sys
import time

T_START = time.perf_counter()


def main() -> int:
    import argparse

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    import harness
    import trace_reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args, rest = ap.parse_known_args()
    kept = {}
    load = trace_reduce.load_xplane

    def keep(trace_dir):
        kept["profile"] = load(trace_dir)
        return kept["profile"]

    trace_reduce.load_xplane = keep
    rc = harness.main(rest, t_start=T_START)
    if rc == 0 and "profile" in kept:
        pathlib.Path(args.out).write_text(json.dumps(trace_reduce.trim(kept["profile"])))
    return rc if "profile" in kept else rc or 1


if __name__ == "__main__":
    sys.exit(main())
