"""Serving-plane public surface.

One ingestion API (:meth:`MultiCellEngine.ingest`), one event union
(re-exported from :mod:`repro.core.events`), one closed-loop driver, one
scorecard. ``EdgeServingEngine`` remains as a deprecated 1-cell view over
:class:`MultiCellEngine`.
"""

from repro.core.events import (Arrival, CellFault, Departure, Event,
                               Handover, LinkScale, SemanticShift, Tick)

from .request import SliceRequest
from .sdla import SDLA
from .admission import SESM, CellDecisions, PendingSolve, SliceDecision
from .engine import (CellRuntime, EdgeServingEngine, TaskRuntime,
                     pinned_accuracy_at)
from .multicell import MultiCellEngine, TierPolicy
from .driver import drive_closed_loop, sla_scorecard

__all__ = [
    "Arrival", "CellFault", "Departure", "Event", "Handover", "LinkScale",
    "SemanticShift", "Tick",
    "SliceRequest", "SDLA", "SESM", "CellDecisions", "PendingSolve",
    "SliceDecision", "CellRuntime", "EdgeServingEngine", "TaskRuntime",
    "pinned_accuracy_at",
    "MultiCellEngine", "TierPolicy",
    "drive_closed_loop", "sla_scorecard",
]
