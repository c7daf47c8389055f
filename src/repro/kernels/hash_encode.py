"""Pallas TPU kernel: fused weighted LSH hash encode.

Computes level-1 bucket codes for a tile of points against all beta hash
functions in one pass:

    codes = floor( ((X o W) @ A) / w + b_frac ) + b_int        (int32)

i.e. a blocked (n, d) x (d, beta) matmul (MXU) whose epilogue fuses the
weight elementwise scaling (on the X tile as it is loaded), the bucket-width
division, the fractional-offset floor, and the exact integer offset b_int —
so codes never round-trip through HBM as floats.

Tiling: grid (n/BN, beta/BB, d/BD); the d axis is the contraction
("arbitrary" semantics), with an f32 VMEM accumulator scratch.  MXU-aligned
defaults BN=256, BB=128, BD=256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["hash_encode_pallas"]


def _kernel(x_ref, w_ref, a_ref, bint_ref, bfrac_ref, o_ref, acc_ref, *,
            inv_width: float, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...] * w_ref[...]  # (BN, BD) * (1, BD): fused weighting
    acc_ref[...] += jnp.dot(
        x, a_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        u = acc_ref[...] * inv_width + bfrac_ref[...]  # (BN, BB) + (1, BB)
        o_ref[...] = jnp.floor(u).astype(jnp.int32) + bint_ref[...]


@functools.partial(
    jax.jit, static_argnames=("width", "bn", "bb", "bd", "interpret")
)
def hash_encode_pallas(
    points,  # (n, d) f32
    weight,  # (d,) f32
    proj,  # (d, beta) f32
    b_int,  # (beta,) int32
    b_frac,  # (beta,) f32
    width: float,
    bn: int = 256,
    bb: int = 128,
    bd: int = 256,
    interpret: bool = False,
):
    n, d = points.shape
    beta = proj.shape[1]
    bn = min(bn, n)
    bb = min(bb, beta)
    bd = min(bd, d)
    assert n % bn == 0 and beta % bb == 0 and d % bd == 0, (
        "caller (ops.py) must pad to block multiples"
    )
    k_steps = d // bd
    grid = (n // bn, beta // bb, k_steps)
    kernel = functools.partial(
        _kernel, inv_width=float(1.0 / width), k_steps=k_steps
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),  # X
            pl.BlockSpec((1, bd), lambda i, j, k: (0, k)),  # weight row
            pl.BlockSpec((bd, bb), lambda i, j, k: (k, j)),  # A
            pl.BlockSpec((1, bb), lambda i, j, k: (0, j)),  # b_int row
            pl.BlockSpec((1, bb), lambda i, j, k: (0, j)),  # b_frac row
        ],
        out_specs=pl.BlockSpec((bn, bb), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, beta), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bn, bb), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(
        points.astype(jnp.float32),
        weight.astype(jnp.float32)[None, :],
        proj.astype(jnp.float32),
        b_int.astype(jnp.int32)[None, :],
        b_frac.astype(jnp.float32)[None, :],
    )
