"""The served kernels and query step compile for a described TPU v5e.

Nothing runs here: the TPU compiler installed with JAX compiles for a
v5e that is described, not attached.  That catches what interpret mode
cannot — block layouts Mosaic refuses, fast memory a kernel may not use
— at the service's compiled batch (``ServiceConfig.q_batch = 8``) and
the kernel's row tile (``bn = 256``).  The topology is described inside
a fixture, so collecting this file never loads the TPU library.
"""

from __future__ import annotations

import re

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
from repro.kernels.fused_query import ROW_TILE

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
    """Compiled HLO text of one fused pass through the ops wrapper on a
    point-axis-minor block, codes (beta, rows) and vectors (d, rows), with
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
        spec((beta, rows), jnp.int32), spec((d, rows), jnp.float32),
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


def _own_relayouts(hlo: str, extent: int) -> list[str]:
    """Copies, pads and transposes that run as device ops of their own
    (not inside a fusion) and have a dimension of at least ``extent``."""
    fused = set(re.findall(r"\bfusion\(.*?calls=(%[\w.\-]+)", hlo))
    found, own = [], True
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if head:
            own = head.group(1) not in fused
        m = re.search(r"= (\S+) (copy|copy-start|copy-done|pad|transpose)\(",
                      line)
        if own and m and any(int(x) >= extent for x in
                             re.findall(r"\d+", m.group(1).split("{")[0])):
            found.append(line.strip()[:160])
    return found


def _transposed_state(hlo: str, cap: int, beta: int, d: int) -> list[str]:
    """Instructions anywhere in the program, fusion bodies included, that
    hold the whole state with the point axis major: shaped s32[cap,beta]
    or f32[cap,d], or laid out {0,1} as s32[beta,cap] or f32[d,cap].  That
    is a relayout of the state into row tiles, wherever the compiler put
    it."""
    shape = re.compile(
        rf"\b(?:s32\[{cap},{beta}\]|f32\[{cap},{d}\]"
        rf"|s32\[{beta},{cap}\]\{{0,1\b|f32\[{d},{cap}\]\{{0,1\b)")
    return [ln.strip()[:160] for ln in hlo.splitlines() if shape.search(ln)]


@pytest.mark.parametrize("n,beta,d,p,n_levels", [
    (200_000, 480, 128, 2.0, 16),  # sift128_p2, groups 0 and 1
    (200_000, 256, 128, 2.0, 16),  # sift128_p2, groups 2 and 3
    (100_000, 416, 960, 1.0, 20),  # gist960_p1
], ids=["sift128_p2-beta480", "sift128_p2-beta256", "gist960_p1"])
def test_query_step_reads_the_state_in_place(topo, monkeypatch, n, beta, d,
                                             p, n_levels):
    """At the benchmark cells' state shapes the whole query step takes the
    point-axis-minor state in row-major layout, as the chip keeps it, and
    no copy, pad or transpose of the state's row extent runs per launch,
    nor any op, fused or not, on the state transposed to points major:
    the kernels read the state where it lies."""
    monkeypatch.setattr(kplatform, "_backend_cache", "tpu")
    cap = n + (-n) % ROW_TILE  # Batcher.row_capacity on one chip
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = IndexConfig(n=cap, d=d, beta=beta, q_batch=Q, k=10, c=3,
                      n_levels=n_levels, p=p, block_n=cap, gamma_n=100.0,
                      vec_dtype="float32")
    compiled = make_query_step(mesh, cfg).lower(
        *query_input_specs(cfg).values()).compile()
    state = compiled.input_formats[0][0]
    assert state.codes.layout.major_to_minor == (0, 1)
    assert state.points.layout.major_to_minor == (0, 1)
    hlo = compiled.as_text()
    assert _own_relayouts(hlo, cap) == []
    assert _transposed_state(hlo, cap, beta, d) == []
