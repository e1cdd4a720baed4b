"""Device-path named scopes: line host spans up with XLA profiles.

The oracle bridge's batched phases (encode → device → apply → finalize)
get ``jax.profiler.TraceAnnotation`` scopes so a JAX profiler capture
(Engine.profiled / KUEUE_TPU_PROFILE) shows the same phase names the
host span tree and the flight recorder report — one vocabulary across
all three artifacts.

The bridge times its phases with sequential perf_counter marks rather
than nested ``with`` blocks, so the annotator mirrors that shape: a
``phase(name)`` call closes the previous scope and opens the next, and
``close()`` ends the last one. Annotation is active only while a cycle
tracer has tracing on (hooks.CURRENT set) — when off, every call is a
single None-check.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation as _TraceAnnotation

from kueue_tpu.obs import hooks


class PhaseAnnotator:
    """Sequential phase scopes for the oracle bridge's cycle."""

    __slots__ = ("_cur", "_enabled")

    def __init__(self) -> None:
        # Latched at cycle start: a tracer that detaches mid-cycle must
        # not leave a dangling open scope.
        self._enabled = hooks.CURRENT is not None
        self._cur = None

    def phase(self, name: str) -> None:
        """End the previous scope (if any) and begin ``name``."""
        if not self._enabled:
            return
        self._exit()
        self._cur = _TraceAnnotation(f"kueue_tpu.oracle.{name}")
        self._cur.__enter__()

    def close(self) -> None:
        if self._enabled:
            self._exit()

    def _exit(self) -> None:
        if self._cur is not None:
            self._cur.__exit__(None, None, None)
            self._cur = None
