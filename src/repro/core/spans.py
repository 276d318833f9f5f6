"""Host spans on the profiler's clock, timed for the program's counters.

A :class:`Span` opens a ``jax.profiler.TraceAnnotation``, so while a
profiler trace is active it lands on the calling thread's line of the
host plane, on the same clock as the device's ``XLA Modules`` /
``XLA Ops`` events.  It also times itself with ``time.perf_counter_ns``,
so the caller can add the duration to a ``StreamIO`` / ``RunStats``
counter.  It is always on: with no profiler active the annotation records
nothing, and nothing is kept once the span closes.

Use it as a context manager, or open it and ``close()`` it later where a
span outlives a block (a stretch dispatched in one loop trip and settled
in the next).
"""

from __future__ import annotations

import time

import jax


class Span:
    """One named host span with ``args`` as its trace metadata."""

    __slots__ = ("_ann", "_t0", "ns")

    def __init__(self, name: str, **args):
        self._ann = jax.profiler.TraceAnnotation(name, **args)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        self.ns = 0

    def close(self) -> int:
        """End the span; return (and keep in ``ns``) its host duration."""
        self.ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(None, None, None)
        return self.ns

    @property
    def us(self) -> int:
        return self.ns // 1000

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
