import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks the device count on first
# init).  512 placeholder host devices back both production meshes:
# (16,16) single-pod and (2,16,16) multi-pod.

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
cell on the production meshes, prove memory fits, and extract the roofline
terms (launch/roofline.py) from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out experiments/dryrun

Results are cached to JSON (one file per cell); --force re-runs.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs.base import ARCHS, SHAPES, ModelConfig, ShapeConfig, get_config
from ..distributed.sharding import named_sharding, with_rules
from ..models import build_model, default_flags, input_specs
from ..models.params import ParamDef, abstract_params, param_specs
from ..training.optimizer import AdamWConfig
from ..training.train_loop import (batch_shardings, make_train_step,
                                   train_state_defs)
from .estimate import model_flops
from .mesh import make_production_mesh
from .roofline import HW, analyze

HBM_PER_CHIP = 16 * 1024**3  # v5e

# per-arch optimizer memory policy (see EXPERIMENTS.md Sec Dry-run):
# llama3-405b only fits a single 256-chip pod with bf16-SR master + int8
# moments; everything else keeps full-precision state.
_OPT_POLICY: dict[str, AdamWConfig] = {
    "llama3_405b": AdamWConfig(master_dtype="bfloat16", moment_dtype="int8",
                               acc_dtype="bfloat16", update_chunk=2),
    "chameleon_34b": AdamWConfig(moment_dtype="int8", update_chunk=4),
}

# per-arch microbatch policy for train_4k: gradient accumulation bounds the
# live-activation footprint (the standard fix once remat boundaries alone
# exceed HBM — see EXPERIMENTS.md Sec Perf iterations).
_MICRO_POLICY: dict[str, int] = {
    "llama3_405b": 8,
    "chameleon_34b": 4,
    "moonshot_v1_16b_a3b": 2,
    "minicpm_2b": 2,  # 122k-vocab head: 17.7 GB/chip at micro=1
}


def _opt_cfg(arch: str) -> AdamWConfig:
    return _OPT_POLICY.get(arch, AdamWConfig())


def _microbatches(arch: str) -> int:
    return _MICRO_POLICY.get(arch, 1)


def skip_reason(arch: str, cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    if cfg.family == "index":
        if shape.kind == "decode":
            return "index has no decode semantics (build/query only)"
        return None
    if shape.name == "long_500k" and cfg.full_attention:
        return ("pure full-attention arch: 500k-token decode needs a "
                "sub-quadratic cache (DESIGN.md Sec 5)")
    return None


def _bf16_defs(defs):
    """Serving params: all f32 leaves in bf16."""
    is_def = lambda x: isinstance(x, ParamDef)  # noqa: E731
    return jax.tree.map(
        lambda d: dataclasses.replace(d, dtype="bfloat16")
        if d.dtype == "float32" else d,
        defs,
        is_leaf=is_def,
    )


def _cache_specs(model, mesh, cache_shapes):
    names_by_key = {
        "k": ("layers", "batch", "kv_seq", "kv_heads", None),
        "v": ("layers", "batch", "kv_seq", "kv_heads", None),
        "ssm": ("layers", "batch", "heads", None, None),
        "conv": ("layers", "batch", None, "model"),
    }
    return {
        k: named_sharding(mesh, names_by_key[k], tuple(s.shape))
        for k, s in cache_shapes.items()
    }


def lower_cell(arch: str, shape_name: str, mesh_name: str,
               cfg_override: ModelConfig | None = None,
               flags=None, index_overrides: dict | None = None):
    """Returns (lowered, compiled, chips, extras) for one cell.

    ``cfg_override``/``flags``/``index_overrides`` serve the shallow
    unrolled analysis lowerings (see analysis_terms)."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.size

    if cfg.family == "index":
        return _lower_wlsh(cfg, shape, mesh, mesh_name,
                           overrides=index_overrides)

    model = build_model(cfg, mesh=mesh, flags=flags or default_flags(cfg))
    defs = model.defs()
    analysis = flags is not None and flags.analysis_unroll

    if shape.kind == "train":
        ocfg = _opt_cfg(arch)
        micro = _microbatches(arch)
        sdefs = train_state_defs(defs, ocfg)
        state_abs = abstract_params(sdefs)
        state_sh = jax.tree.map(
            lambda sp: jax.sharding.NamedSharding(mesh, sp),
            param_specs(sdefs, mesh),
        )
        batch_abs = input_specs(cfg, shape)
        batch_sh = batch_shardings(mesh, batch_abs)
        step = make_train_step(model, ocfg, microbatches=micro,
                               unroll=analysis)
        jitted = jax.jit(
            step, in_shardings=(state_sh, batch_sh), donate_argnums=(0,)
        )
        lowered = jitted.lower(state_abs, batch_abs)
    elif shape.kind == "prefill":
        pdefs = _bf16_defs(defs)
        params_abs = abstract_params(pdefs)
        params_sh = jax.tree.map(
            lambda sp: jax.sharding.NamedSharding(mesh, sp),
            param_specs(pdefs, mesh),
        )
        batch_abs = input_specs(cfg, shape)
        batch_sh = batch_shardings(mesh, batch_abs)
        jitted = jax.jit(model.prefill, in_shardings=(params_sh, batch_sh))
        lowered = jitted.lower(params_abs, batch_abs)
    else:  # decode
        data_size = chips // mesh.shape["model"]
        rules = {}
        kv_axes = []
        if shape.global_batch % data_size != 0:
            # batch can't take the data axes -> cache sequence does
            kv_axes += ["pod", "data"] if multi else ["data"]
        eff_kv = cfg.n_kv_heads * model.kv_rep if cfg.n_kv_heads else 0
        if eff_kv and eff_kv % mesh.shape["model"] != 0:
            # MHA (G == 1, no kv replication possible): the head dim can't
            # shard over "model" -> the cache sequence does instead
            kv_axes.append("model")
        if kv_axes:
            rules["kv_seq"] = tuple(kv_axes)
        ctx = with_rules(**rules) if rules else None
        if ctx:
            ctx.__enter__()
        try:
            pdefs = _bf16_defs(defs)
            params_abs = abstract_params(pdefs)
            params_sh = jax.tree.map(
                lambda sp: jax.sharding.NamedSharding(mesh, sp),
                param_specs(pdefs, mesh),
            )
            cache_shapes = model.cache_shapes(shape.global_batch,
                                              shape.seq_len)
            cache_sh = _cache_specs(model, mesh, cache_shapes)
            tok_abs = input_specs(cfg, shape)
            tok_sh = {
                "tokens": named_sharding(
                    mesh, ("batch",), (shape.global_batch,)
                ),
                "position": jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()
                ),
            }

            def serve_step(params, cache, tokens, position):
                return model.decode_step(params, cache, tokens, position)

            jitted = jax.jit(
                serve_step,
                in_shardings=(params_sh, cache_sh, tok_sh["tokens"],
                              tok_sh["position"]),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(
                params_abs, cache_shapes, tok_abs["tokens"],
                tok_abs["position"]
            )
        finally:
            if ctx:
                ctx.__exit__(None, None, None)

    compiled = lowered.compile()
    return lowered, compiled, chips, {}


def _lower_wlsh(cfg, shape, mesh, mesh_name, overrides: dict | None = None):
    from ..index import IndexConfig, make_query_step, query_input_specs
    from ..index.builder import build_input_specs, make_build_step
    from ..index.engine import shardings as index_shardings

    kw = dict(n=cfg.vocab, d=cfg.d_model, beta=cfg.d_ff)
    kw.update(overrides or {})
    icfg = IndexConfig(**kw)
    chips = mesh.size
    if shape.kind == "train":  # build step
        step = make_build_step(mesh, icfg)
        specs = build_input_specs(icfg)
        lowered = step.lower(
            specs["points"], specs["proj"], specs["b_int"], specs["b_frac"]
        )
    else:  # query step
        step = make_query_step(mesh, icfg)
        specs = query_input_specs(icfg)
        lowered = step.lower(
            specs["state"], specs["queries"], specs["q_codes"],
            specs["q_weight"], specs["mu"], specs["r_min"],
            specs["beta_q"], specs["levels_q"], specs["n_live"],
        )
    compiled = lowered.compile()
    return lowered, compiled, chips, {"index_cfg": dataclasses.asdict(icfg)}


def _extract_terms(lowered, compiled) -> dict:
    """Per-chip (flops, bytes, coll_bytes) from one compiled module."""
    from .roofline import collective_bytes

    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll["total"]),
        "coll_detail": coll,
    }


def _analysis_depths(cfg: ModelConfig) -> tuple[int, int]:
    if cfg.family == "hybrid":
        e = max(cfg.shared_block_every, 1)
        return e, 2 * e
    return 2, 4


def analysis_terms(arch: str, shape_name: str, mesh_name: str) -> dict:
    """Corrected per-chip roofline inputs.

    XLA's cost_analysis counts while-loop bodies ONCE regardless of trip
    count (verified: scan of k matmuls reports k-independent FLOPs), so the
    full scanned lowering undercounts every per-layer term by ~n_layers.
    Correction: lower the model FULLY UNROLLED (python-loop layers, unrolled
    kv-block/CE-chunk/microbatch scans — RunFlags.analysis_unroll) at two
    shallow depths L1 < L2, fit terms linear in depth, extrapolate to the
    real depth.  Nested-remat grouping is disabled in the analysis lowering
    (its extra recompute is a ~1x-per-group-boundary forward, noted in
    EXPERIMENTS.md).  Memory analysis still comes from the full scanned
    lowering in run_cell — loop buffers are reused, so that number is the
    true peak.
    """
    from ..models.transformer import RunFlags

    cfg = get_config(arch)
    if cfg.family == "index":
        return _analysis_terms_wlsh(cfg, shape_name, mesh_name)
    L1, L2 = _analysis_depths(cfg)
    full_scan = cfg.n_layers - cfg.first_dense_layers
    flags = RunFlags(remat="full", layer_groups=1, analysis_unroll=True)
    pts = []
    for Lk in (L1, L2):
        cfg_k = dataclasses.replace(
            cfg, n_layers=Lk + cfg.first_dense_layers
        )
        lowered, compiled, _, _ = lower_cell(
            arch, shape_name, mesh_name, cfg_override=cfg_k, flags=flags
        )
        pts.append(_extract_terms(lowered, compiled))
    out = {}
    for key in ("flops", "bytes", "coll"):
        slope = (pts[1][key] - pts[0][key]) / (L2 - L1)
        out[key] = pts[0][key] + slope * (full_scan - L1)
    out["coll_detail"] = {
        "per_layer_bytes": (pts[1]["coll"] - pts[0]["coll"]) / (L2 - L1),
        "base_bytes": pts[0]["coll_detail"]["bytes"],
        "counts_at_L1": pts[0]["coll_detail"]["counts"],
    }
    out["method"] = (
        f"unrolled two-point extrapolation L1={L1}, L2={L2} -> {full_scan}"
    )
    return out


def _analysis_terms_wlsh(cfg, shape_name: str, mesh_name: str) -> dict:
    """Index cells: extrapolate over scan *blocks* instead of layers."""
    from ..index import IndexConfig

    shape = SHAPES[shape_name]
    if shape.kind == "train":
        # build step: one sharded matmul, no loops — direct counting
        lowered, compiled, _, _ = lower_cell(cfg.name.replace("-", "_"),
                                             shape_name, mesh_name)
        out = _extract_terms(lowered, compiled)
        out["method"] = "direct (loop-free build step)"
        return out
    base = IndexConfig(n=cfg.vocab, d=cfg.d_model, beta=cfg.d_ff)
    chips = 512 if mesh_name == "multi" else 256
    blocks_full = base.n // chips // base.block_n
    pts = []
    for nb in (1, 2):
        n_k = chips * base.block_n * nb
        lowered, compiled, _, _ = lower_cell(
            cfg.name.replace("-", "_"), shape_name, mesh_name,
            index_overrides={"n": n_k, "analysis_unroll": True},
        )
        pts.append(_extract_terms(lowered, compiled))
    out = {}
    for key in ("flops", "bytes", "coll"):
        slope = pts[1][key] - pts[0][key]
        out[key] = pts[0][key] + slope * (blocks_full - 1)
    out["coll_detail"] = {"per_block_bytes": pts[1]["coll"] - pts[0]["coll"],
                          "base": pts[0]["coll_detail"]["bytes"]}
    out["method"] = (
        f"unrolled two-point extrapolation blocks 1,2 -> {blocks_full}"
    )
    return out


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str,
             force: bool = False) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(arch, cfg, shape)
    t0 = time.time()
    if reason:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "status": "skipped", "reason": reason}
    else:
        try:
            lowered, compiled, chips, extras = lower_cell(
                arch, shape_name, mesh_name
            )
            terms = analysis_terms(arch, shape_name, mesh_name)
            rr = analyze(
                arch, shape_name, mesh_name, chips, compiled,
                model_flops(cfg, shape), terms=terms,
            )
            mem_total = rr.memory.get("total_bytes", 0)
            result = {
                "status": "ok",
                "compile_s": round(time.time() - t0, 1),
                "fits_hbm": bool(mem_total <= HBM_PER_CHIP),
                "hbm_gb": round(mem_total / 1024**3, 2),
                "analysis_method": terms.get("method", "direct"),
                **rr.to_dict(),
                **extras,
            }
        except Exception as e:  # noqa: BLE001 — per-cell isolation
            result = {
                "arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
                "compile_s": round(time.time() - t0, 1),
            }
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=str)
    return result


def _fmt(result: dict) -> str:
    if result["status"] == "skipped":
        return (f"{result['arch']:22s} {result['shape']:12s} "
                f"{result['mesh']:6s} SKIP   {result['reason'][:60]}")
    if result["status"] == "error":
        return (f"{result['arch']:22s} {result['shape']:12s} "
                f"{result['mesh']:6s} ERROR  {result['error'][:80]}")
    return (
        f"{result['arch']:22s} {result['shape']:12s} {result['mesh']:6s} "
        f"ok {result['hbm_gb']:7.2f}GB/chip "
        f"c={result['compute_s']:.2e}s m={result['memory_s']:.2e}s "
        f"x={result['collective_s']:.2e}s -> {result['bottleneck']:10s} "
        f"useful={result['useful_fraction']:.2f} "
        f"[{result['compile_s']:.0f}s compile]"
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ARCHS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]

    failures = 0
    for arch in archs:
        arch = arch.replace("-", "_").replace("1.2b", "1p2b")
        for shape_name in shapes:
            for mesh_name in meshes:
                result = run_cell(arch, shape_name, mesh_name, args.out,
                                  force=args.force)
                print(_fmt(result), flush=True)
                if result["status"] == "error":
                    failures += 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
