#!/usr/bin/env python3
"""The on-chip benchmark of the served CAM search path.

  python benchmarks/onchip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object: whether
the answers were correct, the operations attempted and failed, the cell's
end-to-end metrics (``--trace 0``) or per-layer metrics (``--trace 1``, read
from a profiler trace of the window), and the device.  The numbers compared
for ``correct`` are the last lines of its standard error, each beside its
limit.  Exits non-zero, with no result, when JAX finds no TPU or fewer
chips than the cell needs.

JAX's persistent compilation cache is kept in ``.jax_cache`` at the root
of the checkout, so only a checkout's first run compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0] = os.path.dirname(HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1: a directory to keep the window's "
                         "trace in (for recording test traces)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from onchip import harness
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START,
                         keep_trace=args.keep_trace)
    return harness.NO_CHIP if result is None else 0


if __name__ == "__main__":
    sys.exit(main())
