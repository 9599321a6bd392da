"""Spans inside a combining round, written into the JAX profiler's trace.

Off by default.  After ``enable()``, ``span(name, **ids)`` returns a
``jax.profiler.TraceAnnotation``, which records only while a profiler
session runs, on the clock the device ops share, with the id ``round``
(the odd lock value of the PBComb round its thread serves); off, it
returns one shared no-op context.  Per-op code tests ``enabled`` itself,
so tracing that is off costs one global read per op.  The profiler's
buffer is the only store.  Importing this module does not import jax.
The spans and the metrics that read them are listed in PERF.md.
"""

from __future__ import annotations

import threading
import time

enabled = False
#: perf_counter_ns() at the last enable(): earlier stamps are not queued
since_ns = 0


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **ids):
        pass


_NO_SPAN = _NoSpan()
_annotation = None
_thread = threading.local()


def enable() -> None:
    global enabled, since_ns, _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    since_ns = time.perf_counter_ns()
    enabled = True


def disable() -> None:
    global enabled
    enabled = False


def set_round(round_id) -> None:
    """The round this thread's spans carry from now on (None: none)."""
    _thread.round = round_id


def span(name: str, **ids):
    if not enabled:
        return _NO_SPAN
    round_id = getattr(_thread, "round", None)
    if round_id is not None:
        ids["round"] = round_id
    return _annotation(name, **ids)
