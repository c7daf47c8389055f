"""Run one cell of the benchmark once; the result is the last stdout line.

    python3 bench/run.py --workload sift128_p2.steady --seed 7 \
        --seconds 30 --trace 0

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` turns
the serving stack's spans and profiler hooks on, profiles a few seconds
of the window and reports the per-layer metrics.  A machine without a
TPU, or with fewer chips than the cell asks for, exits non-zero and
prints no result.  JAX's compilation cache lives in ``.jax_cache`` at the
root of the checkout, so only a checkout's first run compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    from bench import spec

    spec.use_compilation_cache()

    from bench import harness

    cell = spec.load_cell(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}; this benchmark runs only on a TPU",
              file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
