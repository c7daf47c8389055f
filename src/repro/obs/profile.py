"""Profiling hooks: ``jax.profiler`` wrappers + per-shape attribution.

The serving stack's compiled work is keyed by
``IndexConfig.shape_signature()`` — one executable per signature, one
signature per (shape bucket, rung, shard count, kernel path).  The
:class:`Profiler` attributes the two costs that matter to that key:

* **compile count** — how many distinct executables the step cache
  built (step-cache churn and rung switches become directly visible);
* **dispatch time** — wall seconds spent inside the compiled-step
  launch, per signature.

Both are host-side bookkeeping and never touch device values, so
enabling them is bit-exact.  :meth:`Profiler.span` opens a
``jax.profiler.TraceAnnotation`` region ``name[detail]`` on the
profiler's clock, which is the device trace's; the dispatch scope is
its one timed use.  The serving stack's spans, all ``wlsh_*``:

* ``wlsh_wait_idle`` / ``wlsh_wait_deadline`` — the driver thread
  asleep with nothing pending / until a pending request's deadline;
* ``wlsh_lease`` — the launch's ``StateCache`` acquire (hit, restore
  or build of the group's state);
* ``wlsh_encode`` — the host query codes;
* ``wlsh_query_step[sig]`` — the timed dispatch: input copies, the
  compiled step, the readback;
* ``wlsh_readback`` — inside it, the wait for the device and the
  outputs' copy back;
* ``wlsh_resolve`` — finishing a launch: rung padding, the delta
  merge, counters, span stamps, resolving the futures.

Arguments (group, rows, signature) go only in the ``[...]`` suffix, so
a trace has one label per span kind.  ``start_trace`` / ``stop_trace``
bracket an on-demand ``jax.profiler`` capture.  A capture that fails to
start or stop raises: a requested trace is never silently missing.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

__all__ = ["Profiler"]


class Profiler:
    """Per-``shape_signature`` compile/dispatch attribution + jax hooks."""

    def __init__(self, profile_dir: str | None = None,
                 timer=time.perf_counter):
        """Attribute compiles/dispatches; ``profile_dir`` enables capture.

        ``timer`` is injectable for deterministic tests; dispatch times
        are wall-clock by nature (they measure real device work).
        """
        self.profile_dir = profile_dir
        self._timer = timer
        self._lock = threading.Lock()
        self._compiles: dict[str, int] = {}
        self._dispatch_s: dict[str, float] = {}
        self._dispatch_n: dict[str, int] = {}
        self._tracing = False

    def record_compile(self, sig: str) -> None:
        """Count one step compilation under signature ``sig``."""
        with self._lock:
            self._compiles[sig] = self._compiles.get(sig, 0) + 1

    @staticmethod
    def span(name: str, detail=""):
        """An untimed region ``name[detail]`` in captured device traces."""
        return jax.profiler.TraceAnnotation(
            name if detail == "" else f"{name}[{detail}]")

    @contextlib.contextmanager
    def dispatch(self, sig: str):
        """Time one compiled-step launch, annotated in device traces."""
        t0 = self._timer()
        try:
            with self.span("wlsh_query_step", sig):
                yield
        finally:
            dt = self._timer() - t0
            with self._lock:
                self._dispatch_s[sig] = self._dispatch_s.get(sig, 0.0) + dt
                self._dispatch_n[sig] = self._dispatch_n.get(sig, 0) + 1

    def start_trace(self) -> bool:
        """Start a ``jax.profiler`` trace into ``profile_dir``.

        Returns False (and starts nothing) without a ``profile_dir`` or
        while a trace is already running; a failed start raises.
        """
        if self.profile_dir is None or self._tracing:
            return False
        jax.profiler.start_trace(self.profile_dir)
        self._tracing = True
        return True

    def stop_trace(self) -> bool:
        """Stop the in-flight trace; False when none is running.

        A failed stop (the trace not written) raises.
        """
        if not self._tracing:
            return False
        self._tracing = False
        jax.profiler.stop_trace()
        return True

    def summary(self) -> dict:
        """Compile counts and dispatch-time attribution per signature."""
        with self._lock:
            return {
                "n_compiles": sum(self._compiles.values()),
                "compiles": dict(self._compiles),
                "dispatch": {
                    sig: {
                        "count": self._dispatch_n[sig],
                        "total_s": self._dispatch_s[sig],
                        "mean_s": (self._dispatch_s[sig]
                                   / self._dispatch_n[sig]),
                    }
                    for sig in sorted(self._dispatch_n)
                },
            }
