"""Host spans of the manager's batch path, recorded in the profiler trace.

A span is a ``jax.profiler.TraceAnnotation`` (through ``repro.compat``):
while a profiler session records, it lands on the trace's host plane, on
the clock the device planes share; otherwise entering and leaving it costs
about a microsecond. Counters travel as the span's metadata. Those that
take work to compute are computed only when :func:`tracing` is true,
checked once per batch, and attached before the span exits with
``set_metadata``.

Spans sit at batch granularity, never per environment, reading or
publish. One ``run_windows_scan`` batch of a fused-decide mode records::

    percepta.run_windows        n
      percepta.batch            k, window (the batch's first window index)
        percepta.assemble       when tracing: records, envs, drain_ms,
                                ingest_ms (gather + count), close_ms,
                                queue_wait_ms (the oldest drained reading's
                                wait), staged_bytes (the (K, E, S, M)
                                triple); on the fleet pass sort_fallbacks
                                (1 when the close ordered by timestamp) and
                                carried (records left pending)
        percepta.dispatch
          percepta.train.apply      trainer.apply_pending
          percepta.fused_step       run_many_decide, with the triple's transfer
          percepta.train.dispatch   the train step's enqueue
        percepta.consume
          percepta.result_wait      the first fetch, which waits on the device
          percepta.forward          ForwarderHub.dispatch_window, every window
          percepta.log              LogDB.append_many, when a DB is attached

The plain scan modes record ``percepta.assemble``, ``percepta.dispatch``
and ``percepta.consume`` alone. The async modes have no
``percepta.batch``: ``percepta.assemble`` runs on the prefetcher's pump
thread, dispatch and consume on the manager's.
"""
from __future__ import annotations

import functools

from repro import compat


def span(name: str, **metadata):
    """A host span named ``name`` (``percepta.<step>``), with ``metadata``
    as its stats; use as a context manager."""
    return compat.trace_annotation(name, **metadata)


def tracing() -> bool:
    """Whether a profiler session records spans: the gate for counters
    that cost work to compute."""
    return compat.trace_enabled()


def spanned(name: str):
    """Decorator: the whole call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with compat.trace_annotation(name):
                return fn(*args, **kwargs)
        return call
    return wrap
