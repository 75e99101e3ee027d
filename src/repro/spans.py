"""Named host spans on the profiler's clock.

`span(name, **meta)` is a context manager that marks a stage of the
service or the trainer as a `jax.profiler.TraceAnnotation`: a profile
taken with `jax.profiler.trace` holds it on the thread that closed it, on
the same clock as the device's operations, with `meta` (and anything
passed to `set_metadata` before it closes) as its stats. Outside a
profile a span costs about a microsecond. Before JAX is imported it is a
no-op, so modules that promise a stdlib-only import (the socket server
and client) can mark their stages too. Every span of the package is named
`repro.<area>.<stage>`.
"""
from __future__ import annotations

import sys


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set_metadata(self, **meta) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(name: str, **meta):
    """A `TraceAnnotation` named `name` once JAX is loaded, else a no-op."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _NO_SPAN
    return profiler.TraceAnnotation(name, **meta)
