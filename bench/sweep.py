"""Sweep the offered rate on one deployment to find its knee.

    python3 bench/sweep.py --config sift128_p2 --seed 11 --seconds 15 \
        --rates 20,30,40,50,60 [--out sweep_sift.json]

One process and one set-up; then, for each rate, one open-loop window of
the traffic generator that the cells use (Zipf 0.99 over weight ids,
corpus rows plus N(0, 3) noise), drained before the next.  Per rate it
records the requests offered and answered inside the window, the time
the last answer trailed the window's close, the latency tails of the
first and the last quarter of the window, how late the generator ran,
and the launches' occupancy.  Completions keep up with arrivals while
the last answer trails the close by about two launches and the last
quarter's tail is no longer than the first quarter's; the knee is the
highest rate at which they do.  Runs only on a TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quarter_p95(rec, first: bool):
    import numpy as np

    from bench.harness import percentile

    rel = rec.due - rec.t_open
    sel = rel < rec.seconds / 4 if first else rel >= 3 * rec.seconds / 4
    return percentile(rec.latency_ms[sel], 95) if np.any(sel) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, q/s")
    ap.add_argument("--zipf", type=float, default=0.99)
    ap.add_argument("--q-noise", type=float, default=3.0)
    ap.add_argument("--out", default=None, help="also write rows here")
    args = ap.parse_args(argv)

    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    import jax
    import numpy as np

    from bench import spec

    spec.use_compilation_cache()
    from bench import gen, harness, serve

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; the sweep runs only on a TPU", file=sys.stderr)
        return 3
    with open(spec.BENCH_DIR / "configs" / f"{args.config}.json") as fh:
        cfg = json.load(fh)
    dep = serve.build(cfg, args.seed, obs=False)
    print(f"setup_s={time.perf_counter() - T_START} plan_s={dep.plan_s} "
          f"build_s={dep.build_s} groups={dep.group_shapes}", flush=True)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = {"rate_qps": rate, "zipf_s": args.zipf,
                   "q_noise": args.q_noise}
        sched = gen.make_schedule(traffic, args.seconds, dep.data,
                                  cfg["n_weights"],
                                  gen.seeds(args.seed + 1 + i)[2])
        dep.svc.reset_stats()
        rec = serve.run_window(dep.svc, sched, args.seconds)
        c = harness._counters(dep.svc)
        close = rec.t_open + args.seconds
        row = {
            "rate_qps": rate, "offered": len(sched),
            "answered_in_window": int(np.sum(rec.t_resolved <= close)),
            "trail_s": float(np.nanmax(rec.t_resolved) - close),
            "p50_ms": harness.percentile(rec.latency_ms, 50),
            "p95_ms": harness.percentile(rec.latency_ms, 95),
            "p95_first_quarter_ms": _quarter_p95(rec, True),
            "p95_last_quarter_ms": _quarter_p95(rec, False),
            "gen_late_p95_ms": harness.percentile(rec.gen_late_ms, 95),
            "launches": c["n_batches"],
            "occupancy": c["n_queries"] / max(1, c["n_batches"])
            / cfg["q_batch"],
            "unanswered": rec.n_unanswered, "compiles": rec.n_compiles,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump({"config": args.config, "seed": args.seed,
                       "seconds": args.seconds, "rows": rows}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
