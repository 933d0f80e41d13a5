"""Host spans of the served path, written into the JAX profiler's trace.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation``: while a
trace records (``jax.profiler.start_trace`` or a ``start_server`` capture)
each span is a host event on the same clock as the device's operations and
carries its stats, values already on the host (ids, lengths, step counts);
while none records it records nothing and costs about a microsecond.
``install_gc_spans`` adds a ``py.gc`` span around each collection of the
Python garbage collector. PERF.md lists every span and what reads it.
"""

from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation


def span(name: str, **stats) -> TraceAnnotation:
    """A context manager that marks ``name`` on the profiler's host line."""
    return TraceAnnotation(name, **stats)


#: the ``py.gc`` span of the collection under way (``gc.callbacks`` is
#: process-wide, so its state is too)
_open_gc: list = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        if TraceAnnotation.is_enabled():
            s = TraceAnnotation("py.gc", gen=info["generation"])
            s.__enter__()
            _open_gc.append(s)
    elif _open_gc:
        _open_gc.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Mark every garbage collection with a ``py.gc`` span (``gen``: the
    generation collected). Installing twice installs once."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
