#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, on the chip.

  python benchmarks/onchip/control.py --workload <cell> \
      --seeds 11,12,13 [--seconds 10]

For each seed, one whole run of the cell (set-up, a window at the cell's
own load, the comparison), all in this one process, with every control of
the guarantees the cell's mix exercises put in the program's place for the
sampled lookups.  Prints one JSON line per seed: the program's own numbers
compared (the lower readings) and each control's count of sampled lookups
that depart from the reference (the upper readings).  Needs a TPU; the
benchmark's own runs never run the controls.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0] = os.path.dirname(HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from onchip import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result = harness.run(args.workload, seed, args.seconds, False,
                             t_start=t0, controls=True, out=sys.stderr)
        if result is None:
            return harness.NO_CHIP
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": result["correct"],
            "program": {k: v["value"] for k, v in result["checks"].items()},
            "controls": result["controls"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
