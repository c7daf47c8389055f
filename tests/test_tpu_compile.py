"""The served kernels and query step compile for a described TPU v5e.

Nothing runs here: the TPU compiler installed with JAX compiles for a
v5e that is described, not attached.  That catches what interpret mode
cannot — block layouts Mosaic refuses, fast memory a kernel may not use
— at the service's compiled batch (``ServiceConfig.q_batch = 8``) and
the kernel's row tile (``bn = 256``).  The topology is described inside
a fixture, so collecting this file never loads the TPU library.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.index.config import IndexConfig
from repro.index.engine import make_query_step, query_input_specs
from repro.kernels import ops
from repro.kernels import platform as kplatform

Q, BN, ROWS = 8, 256, 4096


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off.

    A cache entry written for a described chip cannot be read back
    without one, so the cache is off while these compiles run.
    """
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_fused(one_chip, kind, beta, d, p, n_levels, rows=ROWS):
    """Compiled HLO text of one fused pass through the ops wrapper, with
    the launch's live-row count ``n_live`` a run-time scalar (the grid's
    dynamic query bound), as the query step passes it."""

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(codes_p, points, codes_q, queries, q_weight, mu, r_min, beta_q,
             boff, n_valid, stop, n_live):
        return ops.fused_query_block(
            codes_p, points, codes_q, queries, q_weight, mu, r_min, beta_q,
            boff=boff, n_valid=n_valid, c=3, n_levels=n_levels, p=p,
            stop=stop if kind == "scores" else None, n_live=n_live,
            use_pallas=True, interpret=False, bn=BN)

    return jax.jit(step).lower(
        spec((rows, beta), jnp.int32), spec((rows, d), jnp.float32),
        spec((Q, beta), jnp.int32), spec((Q, d), jnp.float32),
        spec((Q, d), jnp.float32), spec((Q,), jnp.int32),
        spec((Q,), jnp.float32), spec((Q,), jnp.int32),
        spec((), jnp.int32), spec((), jnp.int32), spec((Q,), jnp.int32),
        spec((), jnp.int32),
    ).compile().as_text()


@pytest.mark.parametrize("kind", ["hist", "scores"])
@pytest.mark.parametrize("d,p", [(128, 2.0), (960, 0.5)])
def test_fused_kernel_compiles_at_service_batch(one_chip, kind, d, p):
    """Both fused passes, through the ops wrapper, at Q = 8 and beta = 512
    (per-query (1, X) blocks of a (Q, X) array were refused for Q > 1)."""
    assert "tpu_custom_call" in _compile_fused(one_chip, kind, 512, d, p, 16)


@pytest.mark.parametrize("kind", ["hist", "scores"])
@pytest.mark.parametrize("beta,d,p,n_levels", [
    (480, 128, 2.0, 14),  # sift128_p2: group 0's padded state
    (416, 960, 1.0, 18),  # gist960_p1
], ids=["sift128_p2", "gist960_p1"])
def test_fused_kernel_compiles_at_cell_shapes(one_chip, kind, beta, d, p,
                                              n_levels):
    """Both fused passes at the benchmark cells' state widths, distance
    and level count, at Q = 8 and bn = 256, with the run-time ``n_live``
    grid bound."""
    hlo = _compile_fused(one_chip, kind, beta, d, p, n_levels)
    assert "tpu_custom_call" in hlo


def test_query_step_compiles_with_the_pallas_kernels(topo, monkeypatch):
    """The whole jitted query step on one described chip, with the scan
    mode read as it is on a TPU backend; its inputs end with the launch's
    live-row count ``n_live``."""
    monkeypatch.setattr(kplatform, "_backend_cache", "tpu")
    assert kplatform.scan_mode() == "pallas"
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    n = 16 * ROWS
    cfg = IndexConfig(n=n, d=128, beta=512, q_batch=Q, k=10, c=3,
                      n_levels=16, p=2.0, block_n=n, gamma_n=100.0,
                      vec_dtype="float32")
    step = make_query_step(mesh, cfg)
    specs = query_input_specs(cfg)
    assert list(specs)[-1] == "n_live"
    assert specs["n_live"].shape == () and specs["n_live"].dtype == jnp.int32
    hlo = step.lower(*specs.values()).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 2  # pass 1 and pass 2


@pytest.mark.parametrize("kind", ["hist", "scores"])
def test_fused_kernel_custom_call_carries_its_name(one_chip, kind):
    """Each pass's Mosaic call is named for the kernel (``pallas_call``'s
    ``name=``), which is the op name a device trace lists."""
    hlo = _compile_fused(one_chip, kind, 128, 128, 2.0, 16, rows=2 * BN)
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert calls and all(
        ln.lstrip().startswith(f"%fused_query_{kind}.") for ln in calls)
