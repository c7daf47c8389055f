"""Pallas TPU kernel: fused multi-level collision counting ("freq_level").

The TPU-native form of the C2LSH virtual-rehashing search (DESIGN.md Sec 2):
for a tile of points and one query it computes, in a single pass over the
(point, table) code matrix, the FIRST level j at which the point's collision
count reaches the query's threshold mu:

    out[q, o] = min { j : #{ i : floor(h_i(o)/c^j) == floor(h_i(q)/c^j) } >= mu }

(n_levels + 1 if never frequent).  The level loop runs entirely in VMEM on
int32 code tiles — each iteration is one integer floor-divide + compare +
lane reduction; the codes shrink monotonically so no reloads are needed.
This replaces the paper's sequential radius-doubling probes with one fused
sweep (all radii at once), which is the main beyond-paper optimization.

Grid: (Q, n/BN).  Query block (1, beta), point block (BN, beta), output
block (1, BN).  All tiles 2-D to stay Mosaic-friendly.  beta is kept whole
in VMEM: BN=256, beta<=1024 -> ~1.3 MB of int32 codes per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["freq_level_pallas"]


def _floor_div(x, c: int):
    # lax integer div truncates toward zero; emulate floor for negatives.
    q = jax.lax.div(x, jnp.int32(c))
    r = jax.lax.rem(x, jnp.int32(c))
    return q - jnp.where((r != 0) & ((r < 0) != (c < 0)), 1, 0).astype(jnp.int32)


def _kernel(q_ref, p_ref, mu_ref, bq_ref, o_ref, *, c: int, n_levels: int):
    never = jnp.int32(n_levels + 1)
    a = p_ref[...].astype(jnp.int32)  # (BN, beta)
    b = q_ref[...].astype(jnp.int32)  # (1, beta)
    mu = mu_ref[0, 0]
    beta_q = bq_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)  # (BN, beta)
    lane_ok = (lane < beta_q).astype(jnp.int32)
    out = jnp.full((1, a.shape[0]), never, jnp.int32)

    def body(j, carry):
        a, b, out = carry
        cnt = jnp.sum((a == b).astype(jnp.int32) * lane_ok, axis=1)[None, :]
        out = jnp.where((cnt >= mu) & (out == never), jnp.int32(j), out)
        return (_floor_div(a, c), _floor_div(b, c), out)

    _, _, out = jax.lax.fori_loop(
        0, n_levels + 1, body, (a, b, out), unroll=True
    )
    o_ref[...] = out


@functools.partial(
    jax.jit, static_argnames=("c", "n_levels", "bn", "interpret")
)
def freq_level_pallas(
    codes_p,  # (n, beta) int32
    codes_q,  # (Q, beta) int32
    mu,  # (Q,) int32 per-query collision threshold
    beta_q,  # (Q,) int32 per-query table count (WLSH beta_{W_i})
    c: int,
    n_levels: int,
    bn: int = 256,
    interpret: bool = False,
):
    n, beta = codes_p.shape
    q = codes_q.shape[0]
    bn = min(bn, n)
    assert n % bn == 0, "caller (ops.py) must pad points to block multiples"
    grid = (q, n // bn)
    kernel = functools.partial(_kernel, c=int(c), n_levels=int(n_levels))
    smem_spec = pl.BlockSpec(
        (1, 1), lambda iq, ip: (iq, 0), memory_space=pltpu.SMEM
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, beta), lambda iq, ip: (iq, 0)),
            pl.BlockSpec((bn, beta), lambda iq, ip: (ip, 0)),
            smem_spec,
            smem_spec,
        ],
        out_specs=pl.BlockSpec((1, bn), lambda iq, ip: (iq, ip)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
    )(
        codes_q.astype(jnp.int32),
        codes_p.astype(jnp.int32),
        jnp.asarray(mu, jnp.int32).reshape(-1, 1),
        jnp.asarray(beta_q, jnp.int32).reshape(-1, 1),
    )
