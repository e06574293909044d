"""Nestable wall-time spans recording into a MetricsRegistry.

``span("serve/pack", reg)`` times its body on the registry clock and
records the elapsed seconds into ``reg.histogram(path)``, where *path* is
the "/"-joined chain of enclosing spans on this thread — so a
``span("dispatch")`` inside ``span("serve")`` lands in the
``serve/dispatch`` histogram. A span name may itself be a multi-segment
fragment (``span("serve/pack")`` at top level records ``serve/pack``
directly — the instrumented components use this flat namespacing). The
stack is thread-local: the pack-ahead serving worker and the async
checkpoint writer nest independently of the main thread.

**Spans live OUTSIDE jitted graphs.** A span must wrap the *call* to a
jitted function (where host wall-time is meaningful), never run inside
one: a Python context manager under trace would execute once at trace
time, measure tracing instead of execution, and — worse — any attempt to
feed its measurement back into the graph would change the traced program
and invalidate the compile-cache == bucket-cache invariant. Instrumented
components therefore keep spans at the host boundary, and
tests/test_obs.py pins that ``SpiraSession.compile_count`` and the zdelta
search-call counters are unchanged by instrumentation, with engine
results bitwise identical to an uninstrumented run. Inside a jitted graph
the stages are named with ``jax.named_scope`` instead (``plan/*``,
``<layer>/conv``, ``<layer>/norm``, ``head``, ``outputs``): op metadata
that costs nothing at run time and that a device trace charges time by.

Spans measure host wall-time, which under jax's async dispatch is
dispatch time unless the body blocks on results. Where ``jax`` is already
imported, a span also opens a ``jax.profiler.TraceAnnotation`` named by
its path, so the span lands on the profiler's clock beside the device's
operations; keyword arguments of ``span`` ride on that annotation as its
metadata (the serving engine passes the batch's sequence number, so every
span of one batch shares it). Without a running trace the annotation
costs about a microsecond. ``obs`` never imports jax itself, so it stays
importable without it.
"""
from __future__ import annotations

import sys
import threading
from typing import Optional

from .metrics import MetricsRegistry, default_registry

_local = threading.local()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current_path() -> str:
    """The "/"-joined path of spans currently open on this thread
    (empty string at top level)."""
    return "/".join(_stack())


class span:
    """Context manager timing its body into ``registry.histogram(path)``.

    The elapsed time is recorded even when the body raises (the exception
    still propagates) — a failed dispatch is exactly the latency you want
    on the histogram. Re-entrant per thread via the thread-local stack;
    a span object itself is single-use.
    """

    def __init__(self, name: str, registry: Optional[MetricsRegistry] = None,
                 **annotation):
        if not name or name.startswith("/") or name.endswith("/"):
            raise ValueError(
                f"span name must be a non-empty path fragment, got {name!r}")
        self.name = name
        self.registry = registry if registry is not None else default_registry()
        self.annotation = annotation
        self.path = ""
        self._t0 = 0.0
        self._ann = None

    def __enter__(self) -> "span":
        st = _stack()
        st.append(self.name)
        self.path = "/".join(st)
        jax = sys.modules.get("jax")
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(self.path,
                                                     **self.annotation)
            self._ann.__enter__()
        self._t0 = self.registry.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        elapsed = self.registry.clock() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        _stack().pop()
        self.registry.histogram(self.path).record(elapsed)
        return False
