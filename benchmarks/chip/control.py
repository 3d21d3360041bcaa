"""The control of the logit comparison, on the chip at a cell's own size.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 5,6,7 --seconds 10

Runs the cell as ``run.py`` does, once per seed, with the program's float32
products one precision step lower than the configuration states
(``jax.default_matmul_precision("high")``: three bfloat16 passes instead
of float32), and prints the readings of the check for each seed.  The
logit limit has to fail every one of them; the benchmark's own runs never
run this.
"""

import json
import pathlib
import sys


def main() -> int:
    import argparse

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    sys.path.insert(0, str(harness.ROOT / "src"))
    cell = harness.resolve_cell(args.workload)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        harness.log(f"control: first device is {dev.platform}, not a TPU")
        return 3
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    peaks = harness.work.peaks(dev.device_kind)
    counter = harness.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.measure(cell, seed, args.seconds, False, peaks=peaks,
                              matmul_precision="high", counter=counter)
        print(json.dumps({"seed": seed, "precision": "high", "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
