"""The program's own spans in the profiler's trace.

:func:`span` names a stretch of host work ``repro.<name>``: a
``jax.profiler.TraceAnnotation``, which lands on the trace's host plane,
on the same clock as the device's operations.  There is no switch: when
no profiler is running an annotation records nothing and costs about a
microsecond.  The profiler keeps the spans and writes them out with the
trace; nothing here stores them.

Importing this module also hooks Python's cyclic collector
(``gc.callbacks``): every collection becomes a ``repro.gc`` span with its
``generation`` as a stat, so a pause of the host can be told from the
work around it.
"""

from __future__ import annotations

import gc

import jax

PREFIX = "repro."


def span(name: str, **stats):
    """A context manager that records ``repro.<name>`` with ``stats`` (ints
    or strings) while a profiler runs."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


_open_collection: list = []  # the span of the collection under way, if any


def _on_gc(phase: str, info: dict) -> None:
    # collections do not nest and run under the interpreter lock, so one
    # collection's start and stop arrive in pair on one thread
    if phase == "start":
        sp = span("gc", generation=info["generation"])
        sp.__enter__()
        _open_collection.append(sp)
    elif _open_collection:
        _open_collection.pop().__exit__(None, None, None)


gc.callbacks.append(_on_gc)
