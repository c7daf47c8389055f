"""End-to-end driver: embed a corpus with an assigned-arch backbone, plan
WLSH table groups over every user's preference weight vector, and serve a
mixed stream of weight-personalized k-NN queries through the multi-group
retrieval service.

    PYTHONPATH=src python examples/serve_retrieval.py

This is the paper's recommender-system scenario (Sec. 1) on the framework's
own stack: the LM substrate produces the vectors, the WLSH core partitions
the users' weight vectors into table groups and exports a ServingPlan, and
``RetrievalService`` routes each (query, user) to its group, coalesces
same-group traffic into batches, and shares compiled query steps across
groups with equal padded shapes (single-device mesh here; the same code
lowers to the production meshes in launch/dryrun.py).

The same traffic is then replayed open-loop — one request at a time, at
Poisson arrival times — through the deadline-aware async frontend
(``AsyncRetrievalService``, launch on batch fill or ``max_delay_ms``
expiry), which must answer bit-exactly while recovering most of the batch
occupancy that single-request submission throws away.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, reduced
from repro.core.datagen import make_weight_set
from repro.core.distances import weighted_lp_np
from repro.core.params import PlanConfig
from repro.core.wlsh import WLSHIndex
from repro.models import build_model, init_params
from repro.serving import (
    AsyncRetrievalService,
    ManualClock,
    RetrievalService,
    ServiceConfig,
    replay_open_loop,
)


def embed_corpus(n_docs: int, seq_len: int = 32, arch: str = "olmo-1b"):
    """Mean-pooled final hidden states of a reduced backbone = doc vectors."""
    cfg = reduced(get_config(arch))
    model = build_model(cfg, mesh=None)
    params = init_params(model.defs(), jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    vecs = []
    fwd = jax.jit(lambda p, b: model.hidden_states(p, b).mean(axis=1))
    bs = 64
    for i in range(0, n_docs, bs):
        key, k = jax.random.split(key)
        toks = jax.random.randint(k, (min(bs, n_docs - i), seq_len), 0,
                                  cfg.vocab, dtype=jnp.int32)
        vecs.append(np.asarray(fwd(params, {"tokens": toks}), np.float32))
    out = np.concatenate(vecs)
    # shift embeddings to the positive orthant (weighted l_p is used on
    # magnitudes; any affine shift preserves neighbor structure under D_W)
    out = out - out.min(axis=0, keepdims=True)
    return out, cfg


def main():
    n_docs, n_users, n_queries, k = 4_096, 12, 24, 5
    t0 = time.time()
    corpus, cfg_lm = embed_corpus(n_docs)
    d = corpus.shape[1]
    print(f"embedded {n_docs} docs -> ({n_docs}, {d}) "
          f"with {cfg_lm.name} in {time.time() - t0:.1f}s")

    # user preference weight vectors (the paper's S), one group plan for all
    value_range = float(corpus.max())
    users = make_weight_set(size=n_users, d=d, n_subset=3, n_subrange=10,
                            seed=7)
    cfg = PlanConfig(p=2.0, c=3, n=n_docs, gamma_n=100.0)
    host = WLSHIndex(corpus, users, cfg, tau=500.0, v=d // 4, v_prime=d // 4,
                     value_range=value_range, seed=8)
    plan = host.export_serving_plan()
    print(f"WLSH plan: {plan.n_groups} groups, {plan.beta_total} tables, "
          f"group betas {[g.beta_group for g in plan.groups]}")

    # the retrieval service serves *every* group behind one front end
    t0 = time.time()
    svc = RetrievalService(
        plan, corpus, cfg=ServiceConfig(k=k, q_batch=8)
    )
    svc.warmup()
    print(f"service: {plan.n_groups} device group states, "
          f"{svc.step_cache.n_compiled} compiled steps in "
          f"{time.time() - t0:.1f}s")

    # mixed batched requests: every user queries from docs they liked
    rng = np.random.default_rng(9)
    wids = rng.integers(0, n_users, size=n_queries)
    doc_ids = rng.choice(n_docs, n_queries, replace=False)
    queries = corpus[doc_ids] + rng.normal(
        0, 0.01, (n_queries, d)
    ).astype(np.float32)

    t0 = time.time()
    res = svc.query(queries, wids)
    dt = time.time() - t0
    print(f"served {n_queries} personalized queries spanning "
          f"{len(np.unique(res.group_ids))} groups in {dt:.2f}s "
          f"({n_queries / dt:.1f} q/s)")
    for gi, s in sorted(svc.stats_summary().items()):
        print(f"  group {gi}: {s['n_queries']} queries / {s['n_batches']} "
              f"batches, occupancy {s['occupancy']:.2f}, "
              f"mean stop level {s['mean_stop_level']:.1f}")

    # the same requests, one at a time at Poisson arrivals, through the
    # deadline-aware async frontend (shared states / stats / step cache)
    rate_qps, max_delay_ms = 2_000.0, 2.0
    arrivals = np.cumsum(rng.exponential(1.0 / rate_qps, n_queries))
    svc.reset_stats()
    asvc = AsyncRetrievalService(svc, max_delay_ms=max_delay_ms,
                                 clock=ManualClock())
    ares, waits = replay_open_loop(asvc, queries, wids, arrivals)
    assert (
        np.array_equal(ares.ids, res.ids)
        and np.array_equal(ares.stop_levels, res.stop_levels)
        and np.array_equal(ares.n_checked, res.n_checked)
    ), "async frontend must answer bit-exactly like the sync service"
    occ = svc.mean_occupancy()
    print(f"async replay at {rate_qps:.0f} q/s, deadline {max_delay_ms} ms: "
          f"bit-exact with sync; {asvc.n_launched_full} full / "
          f"{asvc.n_launched_deadline} deadline launches, occupancy "
          f"{occ:.2f} (single-submission baseline "
          f"{1 / svc.cfg.q_batch:.2f}), wait mean "
          f"{1e3 * waits.mean():.2f} ms")

    ok = 0
    for qi, (wid, did) in enumerate(zip(wids, doc_ids)):
        w = users[wid]
        exact = np.argsort(weighted_lp_np(corpus, queries[qi], w, 2.0))[:k]
        got = res.ids[qi][res.ids[qi] >= 0]
        hit = did in got
        ok += hit
        overlap = len(set(got.tolist()) & set(exact.tolist()))
        print(f"  user w{wid} (group {res.group_ids[qi]}): source doc {did} "
              f"{'FOUND' if hit else 'missed'}; top-{k} overlap with exact: "
              f"{overlap}/{k}")
    assert ok >= int(0.75 * n_queries), (
        "service must find the perturbed source doc for most users"
    )
    print("ok")


if __name__ == "__main__":
    main()
