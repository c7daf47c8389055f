"""Multi-device SPMD semantics via subprocesses (8 forced host devices).

The main test process must keep the single real CPU device (smoke tests),
so anything needing a populated mesh runs in a child process with
XLA_FLAGS=--xla_force_host_platform_device_count=8.  These are the CI-scale
versions of the production dry-run meshes.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_engine_matches_host_oracle_on_8_devices():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.datagen import make_dataset, make_weight_set
        from repro.core.params import PlanConfig
        from repro.core.wlsh import WLSHIndex
        from repro.index import IndexConfig, build_state, encode_queries, \
            make_query_step

        assert jax.device_count() == 8
        data = make_dataset(n=1024, d=16, seed=41)
        weights = make_weight_set(size=6, d=16, n_subset=2, n_subrange=10,
                                  seed=42)
        cfg = PlanConfig(p=2.0, c=3, n=len(data), gamma_n=100.0)
        host = WLSHIndex(data, weights, cfg, tau=500.0, v=4, v_prime=4,
                         seed=9)
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(4, 2)
        gi = int(host.part.group_of[0])
        built = host._group(gi)
        icfg = IndexConfig(
            n=len(data), d=16, beta=built.fam.beta, q_batch=4, k=3,
            c=3, n_levels=int(np.max(built.plan.n_levels)), p=2.0,
            block_n=128, gamma_n=cfg.gamma_n,
            vec_dtype="float32",
        )
        state = build_state(mesh, icfg, data, built.fam)
        step = make_query_step(mesh, icfg)
        wid = int(built.plan.member_ids[0])
        _, slot, beta_i, mu_i = host._member_params(wid)
        pids = [3, 400, 777, 1000]
        qpts = jnp.asarray(data[pids], jnp.float32)
        dists, ids, stop, _ = step(
            state,
            qpts,
            encode_queries(state, qpts),
            jnp.asarray(np.stack([host.weights[wid]] * 4), jnp.float32),
            jnp.asarray([mu_i] * 4, jnp.int32),
            jnp.asarray([built.plan.r_min_members[slot]] * 4, jnp.float32),
            jnp.asarray([beta_i] * 4, jnp.int32),
            jnp.asarray([int(built.plan.n_levels[slot])] * 4, jnp.int32),
            jnp.int32(4),
        )
        ids = np.asarray(ids)
        assert list(ids[:, 0]) == pids, ids[:, 0]
        assert np.all(np.asarray(dists)[:, 0] < 1e-3)
        # per-query oracle agreement on stop level
        for qi, pid in enumerate(pids):
            want = host.search_dense(data[pid], weight_id=wid, k=3)
            assert int(np.asarray(stop)[qi]) == want.stats.stop_level
        print("OK")
    """)
    assert "OK" in out


def test_retrieval_service_on_8_devices_matches_host_oracle():
    """Multi-group serving on a real (4,2) mesh: routed, coalesced queries
    match search_dense per query, with compiled-step sharing intact."""
    out = _run("""
        import numpy as np, jax
        from repro.core.datagen import make_dataset, make_weight_set
        from repro.core.params import PlanConfig
        from repro.core.wlsh import WLSHIndex
        from repro.serving import RetrievalService, ServiceConfig

        assert jax.device_count() == 8
        data = make_dataset(n=1024, d=16, seed=41)
        weights = make_weight_set(size=8, d=16, n_subset=4, n_subrange=10,
                                  seed=42)
        cfg = PlanConfig(p=2.0, c=3, n=len(data), gamma_n=100.0)
        host = WLSHIndex(data, weights, cfg, tau=500.0, v=4, v_prime=4,
                         seed=9)
        plan = host.export_serving_plan()
        assert plan.n_groups >= 3
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(4, 2)
        svc = RetrievalService(plan, data, mesh=mesh,
                               cfg=ServiceConfig(k=3, q_batch=4))
        rng = np.random.default_rng(43)
        wids = rng.integers(0, len(weights), 10)
        qpts = data[rng.choice(len(data), 10, replace=False)].astype(
            np.float32)
        qpts += rng.normal(0, 3.0, qpts.shape).astype(np.float32)
        res = svc.query(qpts, wids)
        assert len(np.unique(res.group_ids)) >= 3
        for qi in range(10):
            want = host.search_dense(qpts[qi], weight_id=int(wids[qi]), k=3)
            np.testing.assert_array_equal(res.ids[qi],
                                          want.ids.astype(np.int32))
            assert int(res.stop_levels[qi]) == want.stats.stop_level
        assert svc.step_cache.n_compiled < plan.n_groups
        print("OK")
    """)
    assert "OK" in out


def test_train_step_spmd_matches_single_device():
    """Same tiny model, same batch: (4,2)-mesh loss == 1-device loss."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ShapeConfig, get_config, reduced
        from repro.models import build_model, init_params, make_batch
        from repro.models.params import param_specs
        from repro.training.optimizer import AdamWConfig
        from repro.training.train_loop import (batch_shardings,
            init_train_state, make_train_step, train_state_shardings)

        cfg = reduced(get_config("olmo_1b"))
        shape = ShapeConfig("s", 16, 8, "train")
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=0, schedule="constant")
        batch = make_batch(cfg, shape, seed=3)

        # single device
        m0 = build_model(cfg, mesh=None)
        p0 = init_params(m0.defs(), jax.random.PRNGKey(0))
        s0 = init_train_state(m0.defs(), p0, ocfg)
        _, met0 = jax.jit(make_train_step(m0, ocfg))(s0, batch)

        # 4x2 mesh
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(4, 2)
        m1 = build_model(cfg, mesh=mesh)
        p1 = init_params(m1.defs(), jax.random.PRNGKey(0))
        s1 = init_train_state(m1.defs(), p1, ocfg)
        sh = train_state_shardings(m1.defs(), ocfg, mesh)
        s1 = jax.tree.map(
            lambda x, s: jax.device_put(x, s), s1, sh,
            is_leaf=lambda x: hasattr(x, "shape"),
        )
        bsh = batch_shardings(mesh, batch)
        batch1 = jax.tree.map(jax.device_put, batch, bsh)
        step = jax.jit(make_train_step(m1, ocfg),
                       in_shardings=(sh, bsh), donate_argnums=(0,))
        _, met1 = step(s1, batch1)
        l0, l1 = float(met0["loss"]), float(met1["loss"])
        assert abs(l0 - l1) / abs(l0) < 0.05, (l0, l1)
        print("OK", l0, l1)
    """)
    assert "OK" in out


def test_dryrun_cell_on_8_device_mesh():
    """A miniature dry-run: lower+compile a reduced arch on a real 8-device
    mesh through the launcher path (sharding rules, input specs)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.base import ShapeConfig, get_config, reduced
        from repro.models import build_model, input_specs
        from repro.models.params import abstract_params, param_specs
        from repro.training.optimizer import AdamWConfig
        from repro.training.train_loop import (batch_shardings,
            make_train_step, train_state_defs)

        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(4, 2)
        cfg = reduced(get_config("olmoe_1b_7b"))
        shape = ShapeConfig("s", 64, 8, "train")
        model = build_model(cfg, mesh=mesh)
        ocfg = AdamWConfig()
        sdefs = train_state_defs(model.defs(), ocfg)
        state_abs = abstract_params(sdefs)
        state_sh = jax.tree.map(
            lambda sp: jax.sharding.NamedSharding(mesh, sp),
            param_specs(sdefs, mesh))
        batch_abs = input_specs(cfg, shape)
        step = make_train_step(model, ocfg)
        lowered = jax.jit(step, in_shardings=(state_sh,
            batch_shardings(mesh, batch_abs)), donate_argnums=(0,)
        ).lower(state_abs, batch_abs)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list): ca = ca[0]
        assert ca.get("flops", 0) > 0
        print("OK flops=", ca.get("flops"))
    """)
    assert "OK" in out


def test_elastic_checkpoint_across_meshes():
    """Save under a (2,4) mesh, restore under (4,2) — elastic restart."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training.checkpoint import load_checkpoint, save_checkpoint

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        mesh_a = jax.make_mesh((2, 4), ("data", "model"))
        sh_a = {"w": NamedSharding(mesh_a, P("data", "model"))}
        tree_a = jax.tree.map(jax.device_put, tree, sh_a)
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, tree_a)
            mesh_b = jax.make_mesh((4, 2), ("data", "model"))
            sh_b = {"w": NamedSharding(mesh_b, P("model", "data"))}
            _, restored, _ = load_checkpoint(d, tree, shardings=sh_b)
            np.testing.assert_array_equal(
                np.asarray(restored["w"]), np.asarray(tree["w"]))
        print("OK")
    """)
    assert "OK" in out
