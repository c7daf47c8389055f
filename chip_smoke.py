"""Chip smoke test: the served WLSH retrieval path, end to end, on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # row-sharded group states, 4 chips

One process drives the chip only through the normal entry point,
``repro.launch.retrieval.main``: plan -> build -> warmup -> open-loop
replay through the async frontend and the ``ServiceDriver`` -> ``--check``
of every answer against the host oracle ``WLSHIndex.search_dense`` (ids
and stop levels exactly equal).  Data, weights and traffic come from
``--seed`` through ``core/datagen.py``; nothing is downloaded.

One chip runs these phases, and any failure exits non-zero:

  device     a TPU is required; there is no CPU fallback
  main       SIFT-1M's shape as listed by ann-benchmarks (arXiv:1807.05614):
             d = 128, p = 2, k = 10, |S| = 8 weights in 2 subsets; the
             row count is cut to what the host planner's memory and the
             run's time limit allow (``N_MAIN``)
  path       the backend's scan mode is compiled ``pallas`` and the
             compiled query step holds a ``tpu_custom_call``
  p=1, p=0.5 the same traffic shape under the other exponents, each with
             its own plan
  streaming  inserts, seals and compaction under load; inserted rows must
             be recalled before and after compaction

``--chips 4`` runs only the sharded comparison: the same traffic at
``--shards 4`` and ``--shards 1`` must give identical ids, stop levels
and ``n_checked``, both must match the oracle, and every device must hold
its own quarter of each group's rows.

The last line of standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import time

import numpy as np

# |S| = 8 weights in 2 subsets, k = 10, d = 128 (SIFT's width); tau per p
# as the parity suite plans each exponent
_SHAPE = ["--d", "128", "--k", "10", "--n-weights", "8", "--n-subset", "2",
          "--async", "--driver", "--check"]
_TAU = {2.0: "500", 1.0: "1000", 0.5: "2000"}
# rows of the main phase, cut from SIFT-1M's 1,000,000: the host planner
# keeps 4 bytes per row per table (the n x beta codes) and hashes one
# group at a time in float64; ``--check``'s first search of a group adds
# its sorted copies, 8 bytes per row per table, and about 12 more of
# argsort temporaries while it sorts, beside the TPU runtime's own host
# memory. On a one-chip v5e host (40 GiB) the run peaked at 25.25 GiB
# for 500,000 rows and 32.21 GiB for 750,000 while every group's tables
# were still built up front; the full size extrapolated to about 39 GiB,
# too close to the host's limit
N_MAIN = 750_000
# rows of the p = 1 / p = 0.5 phases, the streaming phase and the
# four-chip comparison: the host oracle's time per query, not the chip,
# bounds them inside the run's time limit
N_OTHER, N_STREAM, N_SHARDED = 50_000, 20_000, 100_000
N_QUERIES = 256


def _peak_rss_gib() -> float:
    """Peak resident set of this process so far (Linux reports KiB)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def _serve(retrieval, name: str, *, n: int, p: float, seed: int,
           extra: tuple[str, ...] = ()) -> dict:
    """One launcher run through its CLI entry point; prints its summary."""
    argv = _SHAPE + ["--n", str(n), "--p", str(p), "--tau", _TAU[p],
                     "--n-queries", str(N_QUERIES), "--seed", str(seed),
                     *extra]
    print(f"== phase {name}: {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    out = retrieval.main(argv)
    if out["n_check_failures"]:
        raise RuntimeError(f"phase {name}: {out['n_check_failures']} "
                           f"answers disagree with the host oracle")
    cache = out["cache"]
    print(f"phase {name}: n={n} p={p} groups={out['n_groups']} "
          f"betas={[g.beta_group for g in out['service'].plan.groups]} "
          f"resident_MiB={cache['resident_bytes'] / 2**20} "
          f"compiled_steps={out['n_compiled_steps']} "
          f"plan_s={out['t_plan']} build_s={out['t_build']} "
          f"serve_s={out['t_serve']} "
          f"phase_s={time.perf_counter() - t0} "
          f"peak_rss_GiB={_peak_rss_gib()}", flush=True)
    return out


def _check_path(svc) -> None:
    """The served step is the compiled Pallas kernel, not a fallback."""
    import jax

    from repro.index.engine import query_input_specs
    from repro.kernels import platform

    mode = platform.scan_mode()
    if mode != "pallas":
        raise RuntimeError(f"scan mode is {mode!r}, expected 'pallas'")
    cfg = svc.group_config(0)
    step = svc.step_cache.get(svc.mesh, cfg)
    hlo = step.lower(*query_input_specs(cfg).values()).compile().as_text()
    n_calls = hlo.count("tpu_custom_call")
    if not n_calls:
        raise RuntimeError("compiled query step holds no tpu_custom_call")
    print(f"path: scan mode {mode}, compiled query step for "
          f"{jax.devices()[0].device_kind} holds {n_calls} "
          f"tpu_custom_call(s)", flush=True)


def _release(out: dict) -> None:
    """Drop a finished phase's service so its device states are freed."""
    out.clear()
    gc.collect()


def _one_chip(retrieval, args) -> None:
    main = _serve(retrieval, "main", n=N_MAIN, p=2.0, seed=args.seed)
    resident = main["cache"]["resident_bytes"]
    if resident < 2**30:
        raise RuntimeError(f"main phase holds {resident} bytes of group "
                           f"state on the chip, under 1 GiB")
    _check_path(main["service"])
    _release(main)
    for p in (1.0, 0.5):
        _release(_serve(retrieval, f"p={p}", n=N_OTHER, p=p,
                        seed=args.seed))
    _release(_serve(retrieval, "streaming", n=N_STREAM, p=2.0,
                    seed=args.seed,
                    extra=("--insert-rate", "0.25",
                           "--delta-seal-rows", "16")))


def _rows_per_device(svc) -> list[dict[int, int]]:
    """Per group, the rows each device holds in its own shard.

    Read from every row-carrying array's addressable shards, so a state
    placed wholly on one device (or replicated) fails the check.
    """
    want = svc.batcher.row_capacity() // svc.mesh.size
    per_group = []
    for gi in range(svc.plan.n_groups):
        with svc.state_cache.lease(gi) as state:
            for field in (state.codes, state.points):  # (w, n): point axis
                rows = {s.device.id: s.data.shape[1]
                        for s in field.addressable_shards}
                if len(rows) != svc.mesh.size or set(rows.values()) != {want}:
                    raise RuntimeError(
                        f"group {gi}: rows per device {rows}, expected "
                        f"{want} on each of {svc.mesh.size} devices")
        per_group.append(rows)
    return per_group


def _four_chips(retrieval, args) -> None:
    runs = {}
    for shards in (4, 1):
        out = _serve(retrieval, f"shards={shards}", n=N_SHARDED, p=2.0,
                     seed=args.seed,
                     extra=("--shards", str(shards)))
        if shards > 1:
            svc = out["service"]
            print(f"sharding: rows per device, per group "
                  f"{_rows_per_device(svc)} (row capacity "
                  f"{svc.batcher.row_capacity()})", flush=True)
        res = out["result"]
        runs[shards] = (res.ids.copy(), res.stop_levels.copy(),
                        res.n_checked.copy())
        _release(out)
    for name, a, b in zip(("ids", "stop levels", "n_checked"),
                          runs[4], runs[1]):
        if not np.array_equal(a, b):
            raise RuntimeError(f"--shards 4 and --shards 1 differ in {name}")
    print(f"sharding: --shards 4 == --shards 1 in ids, stop levels and "
          f"n_checked for all {len(runs[1][0])} queries", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every one-chip phase; 4: only the sharded "
                         "comparison across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this smoke test runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}",
          flush=True)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch import retrieval

    print(f"compilation cache: {retrieval.use_compilation_cache()}",
          flush=True)
    t0 = time.perf_counter()
    if args.chips == 1:
        _one_chip(retrieval, args)
    else:
        _four_chips(retrieval, args)
    print(f"total_s={time.perf_counter() - t0} "
          f"peak_rss_GiB={_peak_rss_gib()}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
