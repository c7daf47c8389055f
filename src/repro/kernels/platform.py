"""Platform detection and per-backend kernel dispatch.

One process serves one backend, so the backend query is answered once and
cached (``backend()``) instead of re-asking ``jax.default_backend()`` on
every op call — the seed-era ``ops.on_tpu()`` did exactly that re-query in
the middle of every kernel dispatch.  The platform itself is never pinned
in code: JAX picks it (``JAX_PLATFORMS`` selects one explicitly).

``resolve`` maps the single user-facing knob — ``use_pallas`` on
``IndexConfig`` / ``ServiceConfig`` / the launcher's ``--use-pallas`` —
onto the concrete query-pipeline path.  The dispatch table for the
``None`` ("auto") default:

  ============  ==========================  ===========================
  backend       query pipeline              kernel bodies
  ============  ==========================  ===========================
  tpu           fused (single block-scan    Pallas, compiled (Mosaic)
                launch per pass)
  gpu           fused                       XLA composite (Pallas once
                                            ``gpu_pallas_supported()``;
                                            the bodies are Mosaic/TPU
                                            today, so not yet)
  cpu           fused                       XLA composite (one jit, no
                                            per-stage HBM round trips)
  ============  ==========================  ===========================

Explicit values: ``False`` keeps the seed-era unfused stage-by-stage path
(the parity oracle), ``True`` forces fused Pallas (compiled on TPU,
interpret elsewhere), ``"interpret"`` forces fused Pallas with the kernel
body executed in interpret mode — the same body, testable on every
backend.
"""

from __future__ import annotations

import dataclasses

import jax

__all__ = [
    "KernelPath",
    "backend",
    "default_use_pallas",
    "describe",
    "gpu_pallas_supported",
    "on_tpu",
    "resolve",
]

_backend_cache: str | None = None


def backend() -> str:
    """The JAX default backend name ("cpu" / "gpu" / "tpu"), cached.

    The answer cannot change after the first JAX computation, so every op
    dispatch reads this cache instead of re-querying the JAX client
    registry (``ops.on_tpu()`` used to call ``jax.default_backend()`` per
    op call).
    """
    global _backend_cache
    if _backend_cache is None:
        _backend_cache = jax.default_backend()
    return _backend_cache


def on_tpu() -> bool:
    """True when the cached backend is TPU."""
    return backend() == "tpu"


def gpu_pallas_supported() -> bool:
    """Whether the Pallas kernel bodies can compile for the GPU backend.

    The kernels in this package target Mosaic (TPU): they use
    ``pltpu.VMEM``/``pltpu.SMEM`` memory spaces and TPU compiler params,
    so the compiled path is TPU-only today.  This probe is the single
    place a Triton port would flip to widen the auto dispatch.
    """
    return False


def default_use_pallas() -> bool:
    """Whether ``use_pallas=None`` resolves to compiled Pallas kernels."""
    b = backend()
    return b == "tpu" or (b == "gpu" and gpu_pallas_supported())


@dataclasses.dataclass(frozen=True)
class KernelPath:
    """Resolved query-pipeline dispatch for one ``use_pallas`` value.

    ``fused``     — dispatch both block-scan passes through
                    ``ops.fused_query_block`` (histogram / masked-score
                    intermediates never round-trip through HBM between
                    stages); ``False`` is the seed-era unfused oracle.
    ``pallas``    — run the fused step as the Pallas kernel body
                    (``False``: the bit-exact fused XLA composite).
    ``interpret`` — execute the Pallas body in interpret mode (same
                    kernel code, runs on every backend).
    """

    fused: bool
    pallas: bool
    interpret: bool

    @property
    def label(self) -> str:
        """Short human name of the path ("fused-pallas", "unfused", ...)."""
        if not self.fused:
            return "unfused"
        if not self.pallas:
            return "fused-xla"
        return "fused-pallas-interpret" if self.interpret else "fused-pallas"


def resolve(use_pallas: bool | str | None) -> KernelPath:
    """Map a ``use_pallas`` config value onto a concrete ``KernelPath``.

    ``None`` ("auto") picks per backend from the module dispatch table;
    ``True``/``False``/``"interpret"`` force the path (``True`` degrades
    compiled -> interpret off-TPU so the same config runs everywhere).
    """
    if use_pallas is False:
        return KernelPath(fused=False, pallas=False, interpret=False)
    if use_pallas is None:
        return KernelPath(True, default_use_pallas(), False)
    if use_pallas is True:
        return KernelPath(True, True, not on_tpu())
    if use_pallas == "interpret":
        return KernelPath(True, True, True)
    raise ValueError(
        f"use_pallas must be None, True, False or 'interpret', "
        f"got {use_pallas!r}"
    )


def describe(use_pallas: bool | str | None) -> str:
    """One-line report of the resolved kernel path for the CLI."""
    path = resolve(use_pallas)
    if not path.fused:
        return f"unfused reference stages (XLA) on {backend()}"
    if not path.pallas:
        return f"fused query step, XLA composite, on {backend()}"
    mode = "interpret" if path.interpret else "compiled"
    return f"fused query step, Pallas {mode}, on {backend()}"
