"""The fused scan kernels vs their pure-jnp ref.py oracles, and the oracles
themselves vs brute force and the host planner.

Per the kernel contract, fused_query_block's Pallas body against the
fused XLA composite, both on blocks stored point axis minor (codes
(beta, B), vectors (d, B)): histograms exact-int; scores carry an
identical +inf stop-mask and are bit-exact for p != 2, where both sum
over d in order, and ulp-tight allclose at p = 2, where the in-body MXU
expansion may differ from the XLA gemm in the last ulp.  (Serving bit-exactness does not rest on
this: off-TPU the served scan is the XLA composite itself.)  The hash
oracle matches the planner's float64 codes except at floor boundaries,
where f32 and f64 summation orders may differ by one bucket.

All Pallas calls run with interpret=True on CPU (the kernel body itself is
executed), matching how the kernels are validated off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

def _mk(n, d, beta, Q, seed=0, int_vals=False):
    rng = np.random.default_rng(seed)
    if int_vals:
        pts = rng.integers(0, 1000, (n, d)).astype(np.float32)
        qs = rng.integers(0, 1000, (Q, d)).astype(np.float32)
    else:
        pts = rng.uniform(0, 1000, (n, d)).astype(np.float32)
        qs = rng.uniform(0, 1000, (Q, d)).astype(np.float32)
    w = rng.uniform(1, 10, d).astype(np.float32)
    proj = rng.normal(0, 1, (d, beta)).astype(np.float32)
    b = rng.uniform(0, 729.0, beta)
    b_int = np.floor(b).astype(np.int32)
    b_frac = (b - b_int).astype(np.float32)
    return pts, qs, w, proj, b_int, b_frac


def _boundary_ok(diff, u):
    """Mismatches must be |1| and only where u is ~at an integer boundary."""
    if not diff.any():
        return True
    if np.abs(diff[diff != 0]).max() > 1:
        return False
    frac = np.abs(u - np.round(u))
    return bool(np.all(frac[diff != 0] < 1e-2))


def test_freq_level_semantics_bruteforce():
    """ref.freq_level == brute-force per-level collision counting."""
    rng = np.random.default_rng(3)
    n, beta, Q, c, L = 80, 12, 5, 3, 6
    cp = rng.integers(-(c**L), c**L, (n, beta)).astype(np.int32)
    cq = rng.integers(-(c**L), c**L, (Q, beta)).astype(np.int32)
    mu = rng.integers(1, 6, Q).astype(np.int32)
    got = np.array(ref.freq_level_ref(cp, cq, mu, c=c, n_levels=L))
    for qi in range(Q):
        for pi in range(n):
            first = L + 1
            for j in range(L + 1):
                cnt = np.sum(
                    (cp[pi] // (c**j)) == (cq[qi] // (c**j))
                )
                if cnt >= mu[qi]:
                    first = j
                    break
            assert got[qi, pi] == first


def test_freq_level_monotone_in_mu():
    """Larger mu can only delay the first frequent level."""
    rng = np.random.default_rng(4)
    cp = rng.integers(0, 729, (64, 16)).astype(np.int32)
    cq = rng.integers(0, 729, (4, 16)).astype(np.int32)
    prev = None
    for mu in (1, 3, 6, 12):
        cur = np.array(ref.freq_level_ref(cp, cq, mu, c=3, n_levels=6))
        if prev is not None:
            assert np.all(cur >= prev)
        prev = cur


def test_count_level_matches_numpy():
    rng = np.random.default_rng(5)
    cp = rng.integers(0, 500, (100, 20)).astype(np.int32)
    cq = rng.integers(0, 500, (6, 20)).astype(np.int32)
    for lvl in (0, 1, 3):
        got = np.array(ref.count_level_ref(cp, cq, c=3, level=lvl))
        want = (
            (cq[:, None, :] // 3**lvl) == (cp[None, :, :] // 3**lvl)
        ).sum(-1)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("p", [1.0, 2.0])
def test_weighted_lp_vs_host_oracle(p):
    from repro.core.distances import weighted_lp_np

    pts, qs, w, *_ = _mk(150, 32, 8, 7, seed=7)
    got = np.array(ref.weighted_lp_ref(qs, pts, w, p))
    want = np.stack([weighted_lp_np(pts, q, w.astype(np.float64), p)
                     for q in qs])
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_lp_dtypes(dtype):
    pts, qs, w, *_ = _mk(64, 16, 4, 3, seed=8)
    got = np.array(
        ref.weighted_lp_ref(
            jnp.asarray(qs, dtype), jnp.asarray(pts, dtype),
            jnp.asarray(w, jnp.float32), 2.0,
        )
    )
    ref32 = np.array(ref.weighted_lp_ref(qs, pts, w, 2.0))
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(got, ref32, rtol=tol, atol=tol * 1e3)


# ------------------------------------------------- fused query block step

# Interpret mode runs the grid in Python -> keep shapes moderate.
# (257, 33, ...) keeps a partial last tile (rows not a multiple of
# bn=128) and d not a multiple of 8 in the sweep; (300, 40, 70, 9) has
# Q > 8; (512, 128, 64, 8) spans several row tiles.
_FUSED_SHAPES = [
    (64, 16, 24, 4),  # (n, d, beta, Q)
    (257, 33, 70, 3),
    (96, 128, 24, 3),  # d a lane multiple
    (300, 40, 70, 9),
    (512, 128, 64, 8),
]
_PS = [2.0, 1.0, 0.5, 1.5]


def _mk_fused(n, d, beta, Q, seed=0):
    """Point codes (n, beta) and vectors (n, d) row-major, as the oracles
    take them; the block step gets their transposes."""
    rng = np.random.default_rng(seed)
    cp = rng.integers(-(2**16), 2**16, (n, beta)).astype(np.int32)
    cq = rng.integers(-(2**16), 2**16, (Q, beta)).astype(np.int32)
    pts = rng.uniform(0, 1000, (n, d)).astype(np.float32)
    qs = rng.uniform(0, 1000, (Q, d)).astype(np.float32)
    qw = rng.uniform(1, 10, (Q, d)).astype(np.float32)
    mu = rng.integers(1, max(2, beta // 3), Q).astype(np.int32)
    beta_q = rng.integers(max(1, beta // 2), beta + 1, Q).astype(np.int32)
    r_min = rng.uniform(10.0, 200.0, Q).astype(np.float32)
    stop = rng.integers(0, 9, Q).astype(np.int32)
    return cp, cq, pts, qs, qw, mu, beta_q, r_min, stop


def _fused_both(shape, p, *, boff, n_valid, stop=None, seed=0, bn=128):
    """(ref-route result, pallas-interpret result) for one config."""
    n, d, beta, Q = shape
    cp, cq, pts, qs, qw, mu, beta_q, r_min, st_ = _mk_fused(
        n, d, beta, Q, seed=seed)
    if stop is not None:
        stop = st_
    kw = dict(boff=boff, n_valid=n_valid, c=2, n_levels=8, p=p, stop=stop)
    got_ref = ops.fused_query_block(cp.T, pts.T, cq, qs, qw, mu, r_min,
                                    beta_q, use_pallas=False, **kw)
    got_pal = ops.fused_query_block(cp.T, pts.T, cq, qs, qw, mu, r_min,
                                    beta_q, use_pallas=True, interpret=True,
                                    bn=bn, **kw)
    return got_ref, got_pal


@pytest.mark.parametrize("shape", _FUSED_SHAPES, ids=str)
@pytest.mark.parametrize("p", _PS)
def test_fused_hist_pallas_equals_ref(shape, p):
    n = shape[0]
    (hf0, hg0), (hf1, hg1) = _fused_both(shape, p, boff=0, n_valid=n)
    np.testing.assert_array_equal(np.array(hf0), np.array(hf1))
    np.testing.assert_array_equal(np.array(hg0), np.array(hg1))
    # every live row lands in exactly one frequent bin; good rows are a
    # prefix-dominated subset (good = max(lf, jg) >= lf; rows whose good
    # level overflows the kept bins drop out of hist_g entirely)
    assert np.all(np.array(hf0).sum(axis=1) == n)
    assert np.all(np.array(hg0).sum(axis=1) <= n)
    assert np.all(np.cumsum(hg0, axis=1) <= np.cumsum(hf0, axis=1))


@pytest.mark.parametrize("shape", _FUSED_SHAPES, ids=str)
@pytest.mark.parametrize("p", _PS)
def test_fused_scores_pallas_equals_ref(shape, p):
    n = shape[0]
    s0, s1 = _fused_both(shape, p, boff=0, n_valid=n, stop=True)
    s0, s1 = np.array(s0), np.array(s1)
    fin = np.isfinite(s0)
    np.testing.assert_array_equal(fin, np.isfinite(s1))  # same stop mask
    if abs(p - 2.0) < 1e-9:
        np.testing.assert_allclose(s0[fin], s1[fin], rtol=2e-4, atol=2e-2)
    else:  # both sum over d in order, at every d
        np.testing.assert_array_equal(s0[fin], s1[fin])


@pytest.mark.parametrize("p", _PS)
def test_fused_streaming_watermark(p):
    """Rows at/after n_valid vanish from hists and score +inf, both paths.

    boff puts the block mid-stream so the watermark cuts it at row 21 of
    64: a streaming state serving with n_valid below capacity.
    """
    shape = (64, 16, 24, 4)
    boff, n_valid = 1000, 1021  # rows 21.. of this block are dead
    live = n_valid - boff
    (hf0, hg0), (hf1, hg1) = _fused_both(shape, p, boff=boff,
                                         n_valid=n_valid)
    np.testing.assert_array_equal(np.array(hf0), np.array(hf1))
    np.testing.assert_array_equal(np.array(hg0), np.array(hg1))
    assert np.all(np.array(hf0).sum(axis=1) == live)
    assert np.all(np.array(hg0).sum(axis=1) <= live)
    s0, s1 = _fused_both(shape, p, boff=boff, n_valid=n_valid, stop=True)
    s0, s1 = np.array(s0), np.array(s1)
    assert np.all(np.isinf(s0[:, live:])) and np.all(np.isinf(s1[:, live:]))
    np.testing.assert_array_equal(np.isfinite(s0), np.isfinite(s1))


@pytest.mark.parametrize("p", [2.0, 1.0])
@pytest.mark.parametrize("kind", ["hist", "scores"])
@pytest.mark.parametrize("n_live", [1, 3, 8])
def test_fused_n_live_skips_padding_rows(n_live, kind, p):
    """With ``n_live`` < Q the kernels answer the live rows exactly as the
    full grid does, and rows at or past it read zero histograms / +inf
    scores.  Live rows also match the XLA reference: histograms and p = 1
    scores bit for bit, p = 2 scores as tightly as
    ``test_fused_scores_pallas_equals_ref`` holds them."""
    n, d, beta, Q = 200, 128, 40, 8
    cp, cq, pts, qs, qw, mu, beta_q, r_min, stop = _mk_fused(
        n, d, beta, Q, seed=21)
    kw = dict(boff=0, n_valid=n - 9, c=2, n_levels=8, p=p,
              stop=stop if kind == "scores" else None)
    args = (cp.T, pts.T, cq, qs, qw, mu, r_min, beta_q)
    want = ops.fused_query_block(*args, use_pallas=False, **kw)
    full = ops.fused_query_block(*args, use_pallas=True, interpret=True,
                                 bn=128, **kw)
    got = ops.fused_query_block(*args, use_pallas=True, interpret=True,
                                bn=128, n_live=jnp.int32(n_live), **kw)
    if kind == "hist":
        for w, f, g in zip(want, full, got):
            w, f, g = np.array(w), np.array(f), np.array(g)
            np.testing.assert_array_equal(g[:n_live], f[:n_live])
            np.testing.assert_array_equal(g[:n_live], w[:n_live])
            assert np.all(g[n_live:] == 0)
        return
    w, f, g = np.array(want), np.array(full), np.array(got)
    np.testing.assert_array_equal(g[:n_live], f[:n_live])
    assert np.all(np.isposinf(g[n_live:]))
    fin = np.isfinite(w[:n_live])
    np.testing.assert_array_equal(fin, np.isfinite(g[:n_live]))
    if p == 2.0:
        np.testing.assert_allclose(w[:n_live][fin], g[:n_live][fin],
                                   rtol=2e-4, atol=2e-2)
    else:
        np.testing.assert_array_equal(w[:n_live][fin], g[:n_live][fin])


def test_fused_ref_matches_unfused_stages():
    """The fused XLA composite vs its stages run one by one.

    Pins the composite, block by block, to the ref.py stage oracles it is
    built from (``freq_level_ref``, ``per_query_dist``, the good-level
    ceil and the stop mask): identical bins 0..L and identical
    stop-masked scores, with rows past ``n_valid`` excluded.
    """
    n, d, beta, Q = 300, 40, 70, 9
    c, L = 2, 8
    cp, cq, pts, qs, qw, mu, beta_q, r_min, stop = _mk_fused(
        n, d, beta, Q, seed=11)
    n_valid = n - 17
    row_ok = np.arange(n) < n_valid
    for p in _PS:
        hf, hg = ops.fused_query_block(
            cp.T, pts.T, cq, qs, qw, mu, r_min, beta_q, boff=0,
            n_valid=n_valid, c=c, n_levels=L, p=p, use_pallas=False)
        lf = np.array(ref.freq_level_ref(cp, cq, mu, c=c, n_levels=L,
                                         beta_q=beta_q))
        # the composite runs the distance under jit; eager op-by-op
        # execution rounds the p = 2 expansion differently in the last ulp
        dist = np.array(jax.jit(ref.per_query_dist, static_argnums=3)(
            jnp.asarray(qs), jnp.asarray(qw), jnp.asarray(pts.T), p))
        jg = np.ceil(np.maximum(
            np.log(np.maximum(dist, 1e-30)) / np.log(c)
            - np.log(c * r_min)[:, None] / np.log(c), 0.0)).astype(np.int64)
        good = np.maximum(lf, jg)
        for bins, fused in ((lf, np.array(hf)), (good, np.array(hg))):
            for j in range(L + 1):  # bins the stop logic reads
                want = ((bins == j) & row_ok[None, :]).sum(axis=1)
                np.testing.assert_array_equal(fused[:, j], want)
        scores = np.array(ops.fused_query_block(
            cp.T, pts.T, cq, qs, qw, mu, r_min, beta_q, boff=0,
            n_valid=n_valid, c=c, n_levels=L, p=p, stop=stop,
            use_pallas=False))
        want = np.where((lf <= stop[:, None]) & row_ok[None, :], dist, np.inf)
        np.testing.assert_array_equal(scores, want)  # bit-exact, shared HLO


def test_fused_scalar_broadcast_and_default_beta():
    """Scalar mu/r_min/stop and beta_q=None broadcast like arrays."""
    n, d, beta, Q = 64, 16, 24, 4
    cp, cq, pts, qs, qw, *_ = _mk_fused(n, d, beta, Q, seed=12)
    kw = dict(boff=0, n_valid=n, c=2, n_levels=8, p=1.0)
    a = ops.fused_query_block(cp.T, pts.T, cq, qs, qw, 3, 50.0, None,
                              use_pallas=False, **kw)
    b = ops.fused_query_block(cp.T, pts.T, cq, qs, qw,
                              np.full(Q, 3, np.int32),
                              np.full(Q, 50.0, np.float32),
                              np.full(Q, beta, np.int32),
                              use_pallas=False, **kw)
    np.testing.assert_array_equal(np.array(a[0]), np.array(b[0]))
    np.testing.assert_array_equal(np.array(a[1]), np.array(b[1]))


_I32 = np.iinfo(np.int32)


def _mk_edge_codes(c, L, n, beta, Q, rng):
    """Query and point codes that collide at every level 0..L+2, with codes
    at the int32 extremes and on both sides of negative bucket edges.

    Row r is built from query r % Q: each lane lies within c**t of that
    query's code for a random t, so first-frequent levels spread out.
    """
    cq = rng.integers(_I32.min, _I32.max, (Q, beta), endpoint=True,
                      dtype=np.int64)
    extremes = [_I32.min, _I32.min + 1, -(2**31 - 1) + 5, _I32.max,
                _I32.max - 1, -1, 0]
    cq[:, : len(extremes)] = extremes
    edge_lanes = np.arange(len(extremes), len(extremes) + beta // 4)
    j = rng.integers(1, L + 1, (Q, edge_lanes.size))
    cj = np.minimum(float(c) ** j, 2.0**31).astype(np.int64)
    cq[:, edge_lanes] = -rng.integers(1, 50, (Q, edge_lanes.size)) * cj
    cq = np.clip(cq, _I32.min, _I32.max)

    ref_q = cq[np.arange(n) % Q]  # (n, beta)
    t = rng.integers(0, L + 3, (n, beta))
    span = np.minimum(float(c) ** t, 2.0**33).astype(np.int64)
    cp = ref_q + rng.integers(-span, span, endpoint=True)
    # just inside and just outside the query's negative level-j bucket
    cj_r = cj[np.arange(n) % Q]
    step = rng.choice([-1, 0, 1, 2], (n, edge_lanes.size))
    cp[:, edge_lanes] = np.where(
        step == -1, ref_q[:, edge_lanes] - 1,
        ref_q[:, edge_lanes] + np.where(step == 2, cj_r, step * (cj_r - 1)))
    cp[n // 3:: 7] = _I32.min  # rows at the extremes
    cp[n // 3 + 1:: 7] = _I32.max
    cp = np.clip(cp, _I32.min, _I32.max)
    return cp.astype(np.int32), cq.astype(np.int32)


@pytest.mark.parametrize("mode", ["all_tables", "narrow_member_streaming"])
@pytest.mark.parametrize("c,n_levels", [(2, 14), (2, 18), (2, 24), (2, 33),
                                        (3, 14), (3, 18), (3, 24)])
def test_fused_levels_exact_at_int32_edges(c, n_levels, mode):
    """Both fused passes equal the ref.py oracle bit for bit where bucket
    bounds meet the int32 range: c**j past 2**31, codes at +-(2**31 - 1)
    and -2**31, negative codes one step either side of a bucket edge,
    members narrower than the state (beta_q < beta) and a streaming
    watermark below the block's rows."""
    n, d, beta, Q = 192, 128, 160, 4
    rng = np.random.default_rng(1000 * c + n_levels)
    cp, cq = _mk_edge_codes(c, n_levels, n, beta, Q, rng)
    pts = rng.uniform(0, 1000, (n, d)).astype(np.float32)
    qs = rng.uniform(0, 1000, (Q, d)).astype(np.float32)
    qw = rng.uniform(1, 10, (Q, d)).astype(np.float32)
    mu = rng.integers(beta // 8, beta // 2, Q).astype(np.int32)
    r_min = rng.uniform(10.0, 200.0, Q).astype(np.float32)
    stop = rng.integers(0, n_levels + 1, Q).astype(np.int32)
    if mode == "all_tables":
        beta_q, boff, n_valid = np.full(Q, beta, np.int32), 0, n
    else:
        beta_q = rng.integers(beta // 2, beta, Q).astype(np.int32)
        boff, n_valid = 500, 500 + n - 37
    # p = 1: the distances are bit-exact too
    kw = dict(boff=boff, n_valid=n_valid, c=c, n_levels=n_levels, p=1.0)
    args = (cp.T, pts.T, cq, qs, qw, mu, r_min, beta_q)
    hf0, hg0 = ops.fused_query_block(*args, use_pallas=False, **kw)
    hf1, hg1 = ops.fused_query_block(*args, use_pallas=True, interpret=True,
                                     bn=128, **kw)
    np.testing.assert_array_equal(np.array(hf0), np.array(hf1))
    np.testing.assert_array_equal(np.array(hg0), np.array(hg1))
    # the data reach several levels, not only "never frequent"
    assert (np.array(hf0)[:, : n_levels + 1].sum(axis=0) > 0).sum() >= 3
    s0 = ops.fused_query_block(*args, stop=stop, use_pallas=False, **kw)
    s1 = ops.fused_query_block(*args, stop=stop, use_pallas=True,
                               interpret=True, bn=128, **kw)
    np.testing.assert_array_equal(np.array(s0), np.array(s1))


def _eqns(jaxpr):
    """Every eqn of a jaxpr and of the jaxprs nested in its eqns."""
    from jax.extend import core as jcore

    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _eqns(sub)


def _pass_jaxpr(kind, rows, bn, beta, d, Q, L, **kw):
    """The jaxpr of one fused pass on a point-axis-minor block."""
    from repro.kernels import fused_query as fq

    fn = {"hist": fq.fused_query_hist_pallas,
          "scores": fq.fused_query_scores_pallas}[kind]
    per_q = jnp.zeros((Q,), jnp.int32)
    last = jnp.zeros((Q,), jnp.float32 if kind == "hist" else jnp.int32)
    return jax.make_jaxpr(functools.partial(
        fn, c=3, n_levels=L, p=2.0, bn=bn, **kw))(
        jnp.zeros((beta, rows), jnp.int32), jnp.zeros((d, rows)),
        jnp.zeros((Q, beta), jnp.int32), jnp.zeros((Q, d)),
        jnp.zeros((Q, d)), per_q, per_q, last, jnp.int32(0), jnp.int32(0))


@pytest.mark.parametrize("kind", ["hist", "scores"])
def test_fused_kernel_divides_no_point_tile(kind):
    """No integer division or remainder inside the fused kernel body, so
    none acts on the (beta, bn) point-code tile: the level bounds come from
    dividing the (Q, beta) query codes once per launch, outside it."""
    rows, bn, beta, d, Q, L = 512, 256, 480, 128, 8, 14
    jaxpr = _pass_jaxpr(kind, rows, bn, beta, d, Q, L)
    calls = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1

    def int_divs(eqns):
        return [e for e in eqns if e.primitive.name in ("div", "rem")
                and jnp.issubdtype(e.outvars[0].aval.dtype, jnp.integer)]

    assert not int_divs(_eqns(calls[0].params["jaxpr"]))
    outside = int_divs(_eqns(jaxpr.jaxpr))
    shapes = {tuple(v.aval.shape) for e in outside for v in e.outvars}
    assert shapes == {(Q, beta)}  # the query rows' bucket chain


@pytest.mark.parametrize("kind", ["hist", "scores"])
def test_fused_grid_query_bound_is_run_time(kind):
    """The grid is (n_live, n_tiles): the query bound is read at run time
    (one dynamic bound, so ``n_live`` adds no compiled shape) and the
    tile axis is the block's, so a full batch runs Q x n_tiles steps."""
    rows, bn, beta, d, Q, L = 512, 256, 480, 128, 8, 14
    jaxpr = _pass_jaxpr(kind, rows, bn, beta, d, Q, L, n_live=jnp.int32(Q))
    (eqn,) = [e for e in _eqns(jaxpr.jaxpr)
              if e.primitive.name == "pallas_call"]
    grid_mapping = eqn.params["grid_mapping"]
    assert grid_mapping.num_dynamic_grid_bounds == 1
    assert not isinstance(grid_mapping.grid[0], int)
    assert grid_mapping.grid[1] == rows // bn


def test_hash_encode_matches_host_family():
    """Kernel path must agree with core.families.hash_codes_np (the planner's
    oracle) — the int split of b* is exactness-critical."""
    from repro.core.families import hash_codes_np, sample_lp_family

    rng = np.random.default_rng(9)
    pts = rng.integers(0, 10_000, (128, 24)).astype(np.float32)
    wc = rng.uniform(1, 10, 24)
    fam = sample_lp_family(d=24, beta=16, p=2.0, width=50.0,
                           center_weight=wc, ratio_cap=1e5, c=3, seed=2)
    want = hash_codes_np(pts, fam)
    got = np.array(
        ref.hash_encode_ref(
            pts, fam.proj, fam.b_int, fam.b_frac, fam.center_weight,
            fam.width,
        )
    )
    diff = got - want
    u = (pts * fam.center_weight) @ fam.proj / fam.width + fam.b_frac
    assert _boundary_ok(diff, u)
    assert np.mean(diff != 0) < 1e-3
