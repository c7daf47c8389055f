"""Pallas TPU kernel: weighted l_p candidate scoring.

Computes the (Q, n) distance matrix D[q, o] = (sum_i |w_i (q_i - o_i)|^p)^(1/p)
for the candidate-verification stage of the WLSH search.

Two regimes:
  * p == 2 is NOT handled here — ops.py routes it to the norms+matmul
    expansion (MXU) which is strictly better than any elementwise kernel.
  * p != 2 (the paper's fractional/l_1 case) is a VPU reduction; this kernel
    tiles it as grid (Q, n/BN, d/BD) with an f32 VMEM accumulator, fusing
    the weighting, |.|^p, and the final ^(1/p) epilogue.

Blocks are 2-D: query row (1, BD) against point tile (BN, BD).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["weighted_lp_pallas"]


def _kernel(q_ref, x_ref, w_ref, o_ref, acc_ref, *, p: float, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    diff = jnp.abs((x_ref[...] - q_ref[...]) * w_ref[...])  # (BN, BD)
    if abs(p - 1.0) < 1e-9:
        contrib = diff
    else:
        contrib = diff**p
    acc_ref[...] += jnp.sum(contrib, axis=1)[None, :]  # (1, BN)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        if abs(p - 1.0) < 1e-9:
            o_ref[...] = acc
        else:
            o_ref[...] = acc ** (1.0 / p)


@functools.partial(
    jax.jit, static_argnames=("p", "bn", "bd", "interpret")
)
def weighted_lp_pallas(
    queries,  # (Q, d) f32
    points,  # (n, d) f32
    weight,  # (d,) f32
    p: float,
    bn: int = 256,
    bd: int = 256,
    interpret: bool = False,
):
    qn, d = queries.shape
    n = points.shape[0]
    bn = min(bn, n)
    bd = min(bd, d)
    assert n % bn == 0 and d % bd == 0, (
        "caller (ops.py) must pad to block multiples"
    )
    k_steps = d // bd
    grid = (qn, n // bn, k_steps)
    kernel = functools.partial(_kernel, p=float(p), k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bd), lambda iq, ip, k: (iq, k)),
            pl.BlockSpec((bn, bd), lambda iq, ip, k: (ip, k)),
            pl.BlockSpec((1, bd), lambda iq, ip, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda iq, ip, k: (iq, ip)),
        out_shape=jax.ShapeDtypeStruct((qn, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(
        queries.astype(jnp.float32),
        points.astype(jnp.float32),
        weight.astype(jnp.float32)[None, :],
    )
