"""Host spans of the re-slice tick and the device solve, on the profiler's
own trace.

Every span is a ``jax.profiler.TraceAnnotation`` named
``repro.<layer>.<phase>``. The spans are always in the code: with no profiler session recording, one
costs well under a microsecond, and a session started with
``jax.profiler.start_trace`` records them on the same trace as the device's
programs and operations. Spans sit only at layer boundaries (a tick, a solve,
a collection), never per cell, event or request. Span tree of one tick::

    repro.tick.ingest                       MultiCellEngine.ingest
    repro.tick.dispatch                     MultiCellEngine.reslice_dispatch
      repro.tick.sync_slots                 every cell's sync_slots
      repro.sesm.build_session              SESM._build_session (when it runs)
      repro.sesm.sync_rows                  SESM._sync_rows
      repro.solve.launch                    inputs snapshot + jitted call
    repro.tick.commit                       MultiCellEngine.reslice_commit
      repro.solve.wait                      until the device result is ready
      repro.solve.fetch                     device-to-host copies
      repro.solve.unpack                    bit unpack, result dict
      repro.sesm.decisions                  SliceDecision objects
      repro.tick.apply                      every cell's apply
      repro.tick.freeze_heap                gc.collect + gc.freeze, a
                                            session's first commit only
    repro.gc.gen<N>                         one garbage collection

``repro.solve.launch`` carries the batch size ``B`` and the name of the
device program it launches (``program``, as the trace's ``XLA Modules`` line
names it), so a reduction can pair each launch with the program's execution.
It and ``repro.sesm.build_session`` also carry the allocation grid's size
``A`` and the number of distinct cell grids on it, ``grids`` (1 unless cells
of different levels share a union grid); ``repro.sesm.sync_rows`` carries
``masked``, the rows it recomputes under a cell mask that is not all true.
"""

from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation

__all__ = ["span", "install_gc_spans"]


def span(name: str, **meta) -> TraceAnnotation:
    """A host span ``name`` with ``meta`` recorded as its arguments."""
    return TraceAnnotation(name, **meta)


# the span of the collection in progress: collections are process-wide and
# never nest, so one slot holds it between "start" and "stop"
_gc_open: list[TraceAnnotation] = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        s = TraceAnnotation(f"repro.gc.gen{info['generation']}")
        s.__enter__()
        _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Record every garbage collection of this process as a span
    ``repro.gc.gen<N>`` (``N`` the generation collected). Idempotent."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
