"""Readings of the program and of the control, for setting the limits.

    python3 bench/control.py --workload sift128_p2.steady \
        --seeds 101,102,103 --seconds 10 [--out control.jsonl]

For each seed, one run of the cell as ``bench/run.py`` makes it (set-up,
window at the cell's own load, reference check), in one process; the
sampled answers are then also answered by the control, the reference
computed one precision step below the served path's
(``reference.control_distances``), and the control's answers go through
the same checks in the program's place.  Each seed prints one JSON line
with the program's compared numbers, the control's, and whether each
comes out correct (``correct``, ``control_correct``).  The benchmark's
own runs never run the control.  Runs only on a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: also the per-layer metrics of a traced run")
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)

    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    from bench import spec

    spec.use_compilation_cache()
    from bench import harness

    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = harness.run_cell(cell, seed, args.seconds,
                                   bool(args.trace),
                                   t_start=time.perf_counter(),
                                   with_control=True)
        except harness.NoAccelerator as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        print(json.dumps(res), flush=True)
        ctrl = res["control"]
        for name, c in ctrl["checks"].items():
            print(f"control check {name}={c['value']} limit={c['limit']}",
                  file=sys.stderr)
        line = json.dumps({
            "workload": args.workload, "seed": seed,
            "program": {k: c["value"] for k, c in res["checks"].items()},
            "control": {k: c["value"] for k, c in ctrl["checks"].items()},
            "correct": res["correct"], "control_correct": ctrl["correct"]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
