"""Training launcher: config -> mesh -> sharded train loop with the full
fault-tolerance stack (checkpoint/restart, preemption handling, straggler
monitoring, bounded auto-restart supervision).

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --steps 200 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ckpt

On a real pod the same entry point runs under one process per host with
jax.distributed.initialize(); on CPU it drives the reduced configs for the
examples and tests.  The mesh is (data, model) from --mesh; sharded state
via the logical-axis rules (FSDP x TP x EP); the data pipeline is
deterministic and shardable, so restart-resume is exactly-once.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import get_config, reduced as reduce_cfg
from ..distributed.fault import (PreemptionHandler, RestartSupervisor,
                                 StragglerMonitor)
from ..distributed.sharding import make_mesh
from ..models import build_model, init_params
from ..training.checkpoint import CheckpointManager
from ..training.data import DataConfig, SyntheticStream
from ..training.optimizer import AdamWConfig
from ..training.train_loop import (batch_shardings, init_train_state,
                                   make_train_step, train_state_shardings)

__all__ = ["train", "main"]


def _mesh_or_none(spec: str):
    if not spec or spec == "1":
        return None
    shape = tuple(int(x) for x in spec.split(","))
    names = ("data", "model")[: len(shape)]
    return make_mesh(shape, names)


def train(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    mesh = _mesh_or_none(args.mesh)
    model = build_model(cfg, mesh=mesh)
    ocfg = AdamWConfig(
        lr=args.lr,
        warmup_steps=max(10, args.steps // 20),
        total_steps=args.steps,
        schedule="wsd" if cfg.name.startswith("minicpm") else "cosine",
    )
    stream = SyntheticStream(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq_len, global_batch=args.global_batch,
        seed=args.seed, mode="markov",
    ))

    step_fn = make_train_step(model, ocfg, microbatches=args.microbatches)
    if mesh is not None:
        sh = train_state_shardings(model.defs(), ocfg, mesh)
        bsh = batch_shardings(mesh, stream.global_batch(0))
        step_fn = jax.jit(step_fn, in_shardings=(sh, bsh),
                          donate_argnums=(0,))
    else:
        sh = None
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every,
                            keep=3) if args.ckpt_dir else None
    preempt = PreemptionHandler()
    straggler = StragglerMonitor(window=50, threshold=args.straggler_ratio)
    supervisor = RestartSupervisor(max_restarts=args.max_restarts)
    history: list[float] = []

    def resume_step() -> int:
        if mgr is None:
            return 0
        got = mgr.restore_or_none(_template())
        return got[2].get("data_step", 0) if got else 0

    def _template():
        params = init_params(model.defs(), jax.random.PRNGKey(args.seed))
        return init_train_state(model.defs(), params, ocfg)

    def body(start_step: int):
        state = _template()
        if mgr is not None and start_step > 0:
            _, state, _ = mgr.restore_or_none(state) or (0, state, {})
        if mesh is not None:
            state = jax.tree.map(
                lambda x, s: jax.device_put(x, s), state, sh,
                is_leaf=lambda x: hasattr(x, "shape"),
            )
        loss = float("nan")
        for s in range(start_step, args.steps):
            straggler.start()
            batch = {k: jnp.asarray(v)
                     for k, v in stream.global_batch(s).items()}
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            history.append(loss)
            rep = straggler.stop()
            if rep is not None:
                print(f"[straggler] step {s}: {rep.duration:.2f}s = "
                      f"{rep.ratio:.1f}x median", flush=True)
            if args.fail_at is not None and s == args.fail_at:
                args.fail_at = None  # fail exactly once
                raise RuntimeError("injected failure (--fail-at)")
            if s % args.log_every == 0:
                print(f"step {s:5d}  loss {loss:.4f}  "
                      f"lr {float(metrics['lr']):.2e}  "
                      f"gnorm {float(metrics['grad_norm']):.2f}", flush=True)
            if mgr is not None:
                mgr.maybe_save(s + 1, state, extra={"data_step": s + 1})
            if preempt.should_stop:
                print("[preempt] SIGTERM received: checkpoint + exit",
                      flush=True)
                if mgr is not None:
                    mgr.maybe_save(s + 1, state,
                                   extra={"data_step": s + 1}, force=True)
                    mgr.wait()
                break
        if mgr is not None:
            mgr.maybe_save(args.steps, state,
                           extra={"data_step": args.steps}, force=True)
            mgr.wait()
        return {"final_loss": loss, "steps_run": len(history),
                "restarts": supervisor.restarts,
                "stragglers": len(straggler.flagged)}

    t0 = time.time()
    out = supervisor.run(body, resume_step)
    out["wall_s"] = round(time.time() - t0, 1)
    out["loss_first"] = history[0] if history else float("nan")
    out["loss_last_avg"] = float(np.mean(history[-10:])) if history else None
    print(f"done: {out}", flush=True)
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the arch (smoke scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="",
                    help="mesh shape, e.g. '4,2' (needs >= 8 devices)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--straggler-ratio", type=float, default=3.0)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject one failure at this step (restart demo)")
    return ap.parse_args(argv)


def main(argv=None):
    return train(parse_args(argv))


if __name__ == "__main__":
    main()
