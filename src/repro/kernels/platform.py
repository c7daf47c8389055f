"""The backend, and the fused scan's kernel mode on it.

One process serves one backend, so the backend query is answered once and
cached (``backend()``).  The platform itself is never pinned in code: JAX
picks it (``JAX_PLATFORMS`` selects one explicitly).

The backend alone decides which kernel runs the fused scan
(``scan_mode()``):

  =========  ==========================================================
  backend    fused scan
  =========  ==========================================================
  tpu        ``"pallas"``: the Pallas kernels in fused_query.py, compiled
  otherwise  ``"xla"``: the fused XLA composite in ref.py
  =========  ==========================================================

A third mode, ``"interpret"``, runs the Pallas kernel body in interpret
mode on any backend; no backend selects it, and tests monkeypatch
``scan_mode`` to run it inside the engine.
"""

from __future__ import annotations

import jax

__all__ = ["backend", "on_tpu", "scan_mode"]

_backend_cache: str | None = None


def backend() -> str:
    """The JAX default backend name ("cpu" / "gpu" / "tpu"), cached.

    The answer cannot change after the first JAX computation, so every op
    dispatch reads this cache instead of re-querying the JAX client
    registry.
    """
    global _backend_cache
    if _backend_cache is None:
        _backend_cache = jax.default_backend()
    return _backend_cache


def on_tpu() -> bool:
    """True when the cached backend is TPU."""
    return backend() == "tpu"


def scan_mode() -> str:
    """The fused scan's kernel mode on this backend: "pallas" or "xla".

    Read at trace time by ``ops.fused_query_block`` when its caller names
    no kernel, which is how the query engine calls it.
    """
    return "pallas" if on_tpu() else "xla"
