"""The SEM-O-RAN edge serving engine.

Ties the paper's control plane (SDLA + SESM admission) to an execution data
plane: per admitted task, input streams are compressed by the slicer-chosen
factor z (Pallas bilinear-resize kernel for frame streams), batched, and run
against the task's model with the sliced accelerator share.

Resource mapping (DESIGN.md §4): the "gpu" resource type is a count of
accelerator slices; on the emulated runtime each slice contributes a fixed
service rate, and the engine enforces the radio share by throttling ingest
bitrate — so the end-to-end latency accounting mirrors core.latency. The
model forward itself runs for real (smoke-scale models on CPU; pod submeshes
in production).

The module is split control/data:

* :class:`CellRuntime` is the per-cell DATA plane — admitted task runtimes,
  the pending/retry queue (rejected requests re-offer up to ``max_retries``
  times before dropping, the ``closed_loop_trace`` semantics), handover
  warm-start pins, job execution, metrics. It never talks to a solver.
* :class:`EdgeServingEngine` is a deprecated thin 1-cell view over
  :class:`repro.serving.multicell.MultiCellEngine` (kept as a shim).
* The multi-cell control loop lives in
  :class:`repro.serving.multicell.MultiCellEngine`, which syncs N cell
  runtimes' solver-row slots into ONE coupled device program per re-slice.

STRUCT-OF-ARRAYS DATA PLANE. ``CellRuntime`` stores per-request state in
slot-indexed numpy tables that mirror the solver rows one-to-one: ``_rid``
(request id, -1 = free), ``_state`` (free/queued/running), ``_tier``,
``_retries_left``, ``_pin`` (handover warm-start accuracy bound, 0.0 =
unpinned), ``_gen`` (per-arrival generation), ``_deadline`` / ``_bits``
(SLA deadline and resolved stream size), ``_dirty`` (accumulated
changed-row bits), the ``_sig_gen`` / ``_sig_pin`` signatures of the
last consumed sync and ``_drow`` (a running task's row in the cell's newest
decisions). Each is the cell's row of a :class:`SlotTables` that all the
cells of an engine share, so :func:`apply_decisions` applies every cell's
decisions at once, as columns. A request is seated in the lowest free slot
at the first :meth:`CellRuntime.sync_slots` after it arrives (a min-heap of
freed slots keeps assignment identical to the old candidate-order walk),
keeps that slot for as long as it stays a candidate, and frees it on
departure/drop/handover — so slot sync is a vectorized signature compare
over the tables plus a ``flatnonzero`` of the dirty bits instead of a
Python loop over request objects, and event ingestion between ticks costs
O(1) numpy scalar writes per event. Three slot-indexed object tables ride
along for the parts that are inherently per-object: ``_req`` (the
original request), ``_row`` (the solver-row view with the pin applied —
what ``sync_slots`` returns without re-deriving), and ``_rt`` (the live or
parked :class:`TaskRuntime`).

The FIFO queue is a list of ``(rid, gen)`` entries with LAZY deletion: a
departure of a queued request only detaches its id from the tables (O(1));
the stale queue entry is skipped by generation mismatch wherever the queue
is read and physically purged by the per-tick rebuild in
:func:`apply_decisions` — so a churn-heavy event window never pays O(queue)
per departure.
"""

from __future__ import annotations

import collections
import dataclasses
import heapq
import itertools
import time
from typing import Sequence

import jax
import numpy as np

from repro.core import ResourcePool, semantics
from repro.core.latency import latency as latency_model
from repro.data.pipeline import FrameStream
from repro.kernels.resize import ops as resize_ops
from .admission import CellDecisions, SliceDecision
from .request import SliceRequest
from .sdla import SDLA

__all__ = ["CellRuntime", "EdgeServingEngine", "TaskRuntime",
           "pinned_accuracy_at"]

# slot states
_FREE, _QUEUED, _RUNNING = 0, 1, 2
# what a freed slot's table fields are reset to: a cleared row, dirty, and
# signatures reset so a future re-seating re-dirties it even across a
# consuming sync
_FREED = (("_rid", -1), ("_state", _FREE), ("_pin", 0.0), ("_dirty", True),
          ("_sig_gen", -1), ("_sig_pin", -1.0), ("_drow", -1))


class TaskRuntime:
    """An admitted task's runtime: its newest admitted decision and its
    job/latency history (which a rejected task keeps, parked, until it is
    admitted again or leaves).

    ``decision`` is built from the columns of the batch that admitted the
    task (:class:`~repro.serving.admission.CellDecisions`) the first time
    it is read. While the task runs, the runtime reads it through its cell:
    the cell's newest batch, at the row its slot records (``_drow``), so an
    apply that admits it again moves no Python object. Once the task stops
    running there (evicted, preempted, handed over, withdrawn, or carried
    past an apply that did not decide it) the runtime keeps that batch and
    row itself.
    """

    __slots__ = ("_decision", "_batch", "_row", "_cell", "_at", "jobs_done",
                 "jobs_dropped", "latencies")

    def __init__(self, decision: SliceDecision | None = None,
                 jobs_done: int = 0, jobs_dropped: int = 0,
                 latencies: list | None = None):
        self._decision = decision
        self._batch: CellDecisions | None = None     # an older batch, or
        self._row = 0
        self._cell: CellRuntime | None = None        # the cell it runs in,
        self._at = 0                                 # at this slot
        self.jobs_done = jobs_done
        self.jobs_dropped = jobs_dropped
        self.latencies = [] if latencies is None else latencies

    @property
    def decision(self) -> SliceDecision:
        cell = self._cell
        if cell is not None:
            return cell._batch[int(cell._drow[self._at])]
        if self._batch is not None:
            return self._batch[self._row]
        return self._decision

    @decision.setter
    def decision(self, d: SliceDecision):
        if self._cell is not None:
            self._cell._drow[self._at] = -1
        self._decision = d
        self._batch = self._cell = None


class SlotTables:
    """The numpy halves of cells' slot tables, one row per cell.

    Each :class:`CellRuntime`'s ``_rid``, ``_state``, ... are views of its
    row, so :func:`apply_decisions` indexes every cell's slots with one
    ``(row, slot)`` gather or scatter a field. The rows share one capacity:
    growing a cell grows them all.
    """

    _FIELDS = (("_rid", np.int64, -1), ("_state", np.int8, _FREE),
               ("_tier", np.int32, 0), ("_retries_left", np.int32, 0),
               ("_pin", np.float64, 0.0),       # 0.0 = unpinned
               ("_gen", np.int64, 0),
               ("_deadline", np.float64, 0.0),  # request.max_latency_s
               ("_bits", np.float64, 0.0),      # resolved stream Mbit/job
               ("_dirty", bool, False), ("_sig_gen", np.int64, -1),
               ("_sig_pin", np.float64, -1.0),
               # the running task's row in its cell's newest batch; -1 where
               # no task runs or its runtime keeps its own (TaskRuntime)
               ("_drow", np.int64, -1))

    def __init__(self, rows: int, cap: int = 8):
        self.cap = cap
        self.cells: list[CellRuntime] = []
        for name, dtype, fill in self._FIELDS:
            setattr(self, name, np.full((rows, cap), fill, dtype))

    def add(self, cell: "CellRuntime") -> int:
        """Give ``cell`` the next row; returns its index."""
        self.cells.append(cell)
        return len(self.cells) - 1

    def grow(self, need: int):
        new = max(self.cap * 2, need)
        for name, dtype, fill in self._FIELDS:
            old = getattr(self, name)
            arr = np.full((old.shape[0], new), fill, dtype)
            arr[:, :self.cap] = old
            setattr(self, name, arr)
        pad = [None] * (new - self.cap)
        self.cap = new
        for cell in self.cells:
            cell._req += pad
            cell._row += pad
            cell._rt += pad
            cell._views()


class CellRuntime:
    """Per-cell serving data plane: tasks, retry queue, execution, metrics.

    Decision application follows the closed-loop trace semantics
    (``core.scenarios.closed_loop_trace``): a rejected request — new OR
    previously running (an eviction, surfaced as ``decision.evicted``) — goes
    back onto the bounded retry queue and re-offers on the next re-slice,
    until its ``max_retries`` budget is exhausted and it drops. A handed-over
    task re-arrives with its accuracy bound pinned at the level achieved at
    its admitted ``z`` (the stream is already encoded — warm start); the pin
    clears on rejection, since an unserved task has no encoded stream to
    warm-start from.

    ``registry`` is an optional shared ``{request_id: cell}`` index (the
    engine-level O(1) ``locate``): every path a request enters or leaves the
    cell through keeps it consistent — submit, hand-in, departure, handover,
    drain, shed, retry-exhaustion drop. ``tables`` holds the cell's numpy
    slot tables as one row of a :class:`SlotTables` shared by the engine's
    cells.
    """

    def __init__(self, pool: ResourcePool, sdla: SDLA, *, tables: SlotTables,
                 max_batch: int = 8, max_retries: int = 2,
                 cell: int | None = None,
                 registry: dict[int, int] | None = None):
        self.pool = pool
        self.sdla = sdla
        self.cell = cell
        self.max_batch = max_batch
        self.max_retries = max_retries
        self.tasks: dict[int, TaskRuntime] = {}
        # drop accounting: `drops` is the monotone event count (what loops
        # should diff); `dropped` is a bounded log of recent drop EVENTS for
        # inspection — an id may reappear if resubmitted and dropped again
        self.drops = 0
        self.dropped: collections.deque[SliceRequest] = \
            collections.deque(maxlen=256)
        # SLA accounting: monotone event counts overall and per priority
        # tier (request.tier; the scorecard's per-class axis). `sheds` are
        # POLICY drops (TierPolicy pressure shedding) — a subset of `drops`.
        self.evictions = 0
        self.sheds = 0
        # `preemptions` are tier-policy force-evictions of RUNNING tasks
        # (MultiCellEngine's post-solve preemption pass) — a subset of
        # `evictions`, attributed to the EVICTED task's tier
        self.preemptions = 0
        # rows whose state an apply (or a preemption) changed: admitted from
        # the queue, evicted, or dropped
        self.moved = 0
        self.offered_by_tier: collections.Counter = collections.Counter()
        self.admitted_by_tier: collections.Counter = collections.Counter()
        self.evictions_by_tier: collections.Counter = collections.Counter()
        self.drops_by_tier: collections.Counter = collections.Counter()
        self.sheds_by_tier: collections.Counter = collections.Counter()
        self.preemptions_by_tier: collections.Counter = collections.Counter()
        # ------------------------------------------------ SoA slot tables
        # numpy halves (slot index == solver row; see the module docstring):
        # this cell's row of ``tables``, shared with the engine's cells
        self._tables = tables
        self._trow = self._tables.add(self)
        self._views()
        self._hi = 0                              # slot high-watermark
        # the decisions of the newest apply; running tasks' rows in _drow
        self._batch: CellDecisions | None = None
        cap = self._tables.cap
        # object halves (slot-indexed)
        self._req: list[SliceRequest | None] = [None] * cap
        self._row: list[SliceRequest | None] = [None] * cap
        self._rt: list[TaskRuntime | None] = [None] * cap
        # maps / queues
        self._slot_of: dict[int, int] = {}        # rid → seated slot
        self._free_slots: list[int] = []          # min-heap of freed slots
        # arrivals not yet seated: rid → (req, retries, pin, runtime, gen)
        self._pending_in: dict[int, tuple] = {}
        self._queue: list[tuple[int, int]] = []   # FIFO of (rid, gen)
        self._registry = registry
        self._arrivals = 0
        self.frames = FrameStream()
        self._models: dict[str, tuple] = {}
        self.step = 0

    # ------------------------------------------------------- SoA plumbing
    def _views(self):
        """Point the table fields at this cell's row of ``_tables``."""
        for name, _, _ in SlotTables._FIELDS:
            setattr(self, name, getattr(self._tables, name)[self._trow])

    def _unbind(self, slot: int):
        """The slot's task stops running here, or is carried past an apply
        that did not decide it: its runtime keeps its batch and row."""
        row = int(self._drow[slot])
        if row >= 0:
            rt = self._rt[slot]
            rt._batch, rt._row, rt._cell = self._batch, row, None
            self._drow[slot] = -1

    def _bind(self, slot: int, row: int):
        """The slot's task runs on ``row`` of the apply's batch."""
        rt = self._rt[slot]
        if rt is None:
            rt = self._rt[slot] = TaskRuntime()
        rt._cell, rt._at, rt._batch = self, slot, None
        self._drow[slot] = row

    def _free_slot(self, slot: int):
        """Detach a slot (see ``_FREED``)."""
        self._unbind(slot)
        for name, value in _FREED:
            getattr(self, name)[slot] = value
        self._req[slot] = self._row[slot] = self._rt[slot] = None
        heapq.heappush(self._free_slots, slot)

    def _drop_one(self, slot: int, rid: int):
        """:func:`_drop` of one slot."""
        _drop([self], np.zeros(1, np.int64), np.array([self._trow]),
              np.array([slot]), [rid])

    def _enter(self, request: SliceRequest, retries: int, pin: float,
               runtime: TaskRuntime | None):
        """Shared admission-to-the-cell path of submit/hand_in: park the
        request as a pending (unseated) arrival; the next sync seats it."""
        rid = request.request_id
        if rid in self._slot_of or rid in self._pending_in:
            raise ValueError(
                f"request {rid} is already live in cell {self.cell} "
                "(running or queued); clone it with a fresh request_id to "
                "submit a second instance")
        self._arrivals += 1
        gen = self._arrivals
        self._pending_in[rid] = (request, retries, pin, runtime, gen)
        self._queue.append((rid, gen))
        if self._registry is not None:
            self._registry[rid] = self.cell
        return gen

    def _leave(self, rid: int):
        if self._registry is not None:
            self._registry.pop(rid, None)

    def queued_ids(self) -> list[int]:
        """The LIVE queue in FIFO order (stale lazy-deleted entries skipped
        by generation mismatch; see the module docstring)."""
        out = []
        pend = self._pending_in
        slot_of = self._slot_of
        for rid, gen in self._queue:
            p = pend.get(rid)
            if p is not None:
                if p[4] == gen:
                    out.append(rid)
                continue
            slot = slot_of.get(rid)
            if slot is not None and self._gen[slot] == gen \
                    and self._state[slot] == _QUEUED:
                out.append(rid)
        return out

    # ---------------------------------------------------------- accessors
    def is_live(self, rid: int) -> bool:
        """True while the request is a candidate here (running or queued)."""
        return rid in self._slot_of or rid in self._pending_in

    def live_ids(self) -> list[int]:
        """All live request ids: running first (task order), then queue."""
        return list(self.tasks) + self.queued_ids()

    def request_of(self, rid: int) -> SliceRequest:
        """The ORIGINAL (unpinned) request of a live id."""
        p = self._pending_in.get(rid)
        if p is not None:
            return p[0]
        return self._req[self._slot_of[rid]]

    def tier_of(self, rid: int) -> int:
        p = self._pending_in.get(rid)
        if p is not None:
            return p[0].tier
        return int(self._tier[self._slot_of[rid]])

    def pin_of(self, rid: int) -> float | None:
        """The handover warm-start accuracy bound, ``None`` if unpinned."""
        p = self._pending_in.get(rid)
        pin = p[2] if p is not None else float(self._pin[self._slot_of[rid]])
        return pin if pin > 0.0 else None

    def gen_of(self, rid: int) -> int:
        """The arrival generation of a live id."""
        p = self._pending_in.get(rid)
        if p is not None:
            return p[4]
        return int(self._gen[self._slot_of[rid]])

    def retries_left(self, rid: int) -> int:
        p = self._pending_in.get(rid)
        if p is not None:
            return p[1]
        return int(self._retries_left[self._slot_of[rid]])

    def carried(self, rid: int) -> TaskRuntime | None:
        """The live (running) or parked (carry) runtime of a request."""
        p = self._pending_in.get(rid)
        if p is not None:
            return p[3]
        return self._rt[self._slot_of[rid]]

    # ------------------------------------------------------------- control
    @property
    def pending(self) -> tuple[SliceRequest, ...]:
        """Read-only view of the retry/pending queue (a tuple on purpose:
        appending to it would silently go nowhere — use :meth:`submit`)."""
        return tuple(self.request_of(rid) for rid in self.queued_ids())

    @property
    def queue_depth(self) -> int:
        """Current retry/pending queue length (the shedding pressure signal)."""
        return len(self.queued_ids())

    def register_model(self, name: str, cfg, params, infer_fn):
        """infer_fn(params, inputs) → outputs; used for LM-service tasks."""
        self._models[name] = (cfg, params, infer_fn)

    def submit(self, request: SliceRequest):
        self._enter(request, self.max_retries, 0.0, None)

    def remove(self, request_id: int) -> TaskRuntime | None:
        """Withdraw a task (departure): no retry, no drop accounting."""
        p = self._pending_in.pop(request_id, None)
        if p is not None:
            self._leave(request_id)
            return p[3]
        slot = self._slot_of.pop(request_id, None)
        if slot is None:
            return None
        rt = self._rt[slot]
        if self._state[slot] == _RUNNING:
            self.tasks.pop(request_id, None)
        self._free_slot(slot)
        self._leave(request_id)
        return rt

    def gather(self) -> list[SliceRequest]:
        """The cell's current candidate set: running tasks first, then the
        pending/retry queue, with handover pins applied (idempotent)."""
        out = []
        for rid in self.live_ids():
            req = self.request_of(rid)
            pin = self.pin_of(rid)
            out.append(req if pin is None
                       else dataclasses.replace(req, min_accuracy=pin))
        return out

    def _seat_one(self, rid: int, entry: tuple) -> int:
        """Seat one pending arrival in the lowest free slot; returns it."""
        req, retries, pin, rt, gen = entry
        free = self._free_slots
        slot = heapq.heappop(free) if free else self._hi
        if slot == self._hi:
            self._hi += 1
            if self._hi > self._tables.cap:
                self._tables.grow(self._hi)
        self._rid[slot] = rid
        self._state[slot] = _QUEUED
        self._tier[slot] = req.tier
        self._retries_left[slot] = retries
        self._pin[slot] = pin
        self._gen[slot] = gen
        self._deadline[slot] = req.max_latency_s
        self._bits[slot] = self.sdla.bits_per_job(req)
        self._req[slot] = req
        self._row[slot] = req if pin == 0.0 \
            else dataclasses.replace(req, min_accuracy=pin)
        self._rt[slot] = rt
        self._slot_of[rid] = slot
        return slot

    def _seat_pending(self):
        """Seat every pending arrival in the lowest free slot, in arrival
        order (the old candidate-order walk seated unseated candidates —
        which are exactly the arrivals since the last sync — the same way)."""
        if not self._pending_in:
            return
        for rid, entry in self._pending_in.items():
            self._seat_one(rid, entry)
        self._pending_in.clear()

    def sync_slots(self, consume: bool = False
                   ) -> tuple[list[SliceRequest | None], list[int]]:
        """Seat pending arrivals and report which solver-row slots changed
        since the last CONSUMING sync — as a vectorized signature compare
        over the slot tables.

        The delta re-slice fast path keeps the stacked solver tables
        device-resident across ticks, so a task's row only needs host
        recompute + device scatter when the task itself changed. Slots are
        sticky: a request keeps its row for as long as it stays a candidate
        (running OR queued), a departure clears its row, and new candidates
        fill the lowest free slots in arrival order. A slot is dirty when
        it was cleared, newly assigned, its handover pin changed, or its id
        was reused by a NEW submission (the per-arrival generation in the
        signature — row-id reuse must never alias the predecessor's row).

        Returns ``(rows, dirty)``: ``rows`` is the per-slot request list
        (pins applied, ``None`` = cleared row), ``dirty`` the sorted indices
        of changed slots. Dirty slots ACCUMULATE across non-consuming syncs
        (``gather``-style introspection must not eat deltas the next
        re-slice still needs) and clear only when ``consume=True`` — the
        re-slice that actually delivers them to the solver session.
        """
        self._seat_pending()
        hi = self._hi
        occ = self._state[:hi] != _FREE
        changed = occ & ((self._gen[:hi] != self._sig_gen[:hi])
                         | (self._pin[:hi] != self._sig_pin[:hi]))
        if changed.any():
            np.copyto(self._sig_gen[:hi], self._gen[:hi], where=changed)
            np.copyto(self._sig_pin[:hi], self._pin[:hi], where=changed)
            self._dirty[:hi] |= changed
        dirty_now = np.flatnonzero(self._dirty[:hi]).tolist()
        if consume:
            self._dirty[:hi] = False
        return self._row[:hi], dirty_now

    def apply(self, decisions: Sequence[SliceDecision]
              ) -> Sequence[SliceDecision]:
        """Apply one re-slice round's decisions (for this cell's gather set).

        Admitted tasks keep (or gain) a runtime; rejected requests are NOT
        discarded — they consume one retry and re-queue, dropping only once
        the budget is exhausted. A rejection of a task that was RUNNING in
        this cell right before the re-slice is an eviction and is flagged on
        the returned decision (exactly once — later rejections of the same
        task while it is merely queued are plain rejections). Requests
        submitted after the slot sync that produced ``decisions`` are
        untouched: they stay queued for the next round, and decisions for
        requests withdrawn (``remove()``) in the meantime are ignored.
        ``decisions`` is a :class:`CellDecisions` or a list of
        :class:`SliceDecision`; it is returned. See :func:`apply_decisions`,
        which applies many cells' decisions at once, for how.
        """
        return apply_decisions([self], [decisions])[0]

    def _apply_row(self, rid: int, admitted: bool, prev: dict,
                   row: int) -> tuple[int, int, bool, bool]:
        """Apply one decision, ``row`` of the apply's batch, by its request
        id: the path of a row not seated in the slot it was decided in.
        Returns the state it leaves the request in (-1: a stale decision),
        its slot, whether that state changed, and whether the row is an
        eviction."""
        slot = self._slot_of.get(rid)
        before = -1
        if slot is None:
            p = self._pending_in.pop(rid, None)
            if p is None:
                # departed (remove()d) between sync and apply: the decision
                # is stale — do not resurrect or re-queue
                return -1, -1, False, False
            # decided while still unseated (an apply without a prior slot
            # sync — the gather()-based solve paths): seat now
            slot = self._seat_one(rid, p)
        else:
            before = int(self._state[slot])
        tier = int(self._tier[slot])
        self.offered_by_tier[tier] += 1
        if admitted:
            self.admitted_by_tier[tier] += 1
            self._bind(slot, row)
            self._state[slot] = _RUNNING
            return _RUNNING, slot, before != _RUNNING, False
        self._unbind(slot)
        evicted = rid in prev
        if evicted:
            self.evictions += 1
            self.evictions_by_tier[tier] += 1
        if self._reject_one(slot):
            return _QUEUED, slot, before != _QUEUED, evicted
        self._drop_one(slot, rid)
        return _FREE, slot, True, evicted

    def _reject_one(self, slot: int) -> bool:
        """:func:`_reject` of one slot: True if it re-queued."""
        return bool(_reject([self], np.zeros(1, np.int64),
                            np.array([self._trow]), np.array([slot]))[0])

    def preempt(self, request_id: int) -> bool:
        """Force-evict a RUNNING task (the post-solve preemption pass).

        Tier policy lives OUTSIDE the solver (mirror of :meth:`shed`): when a
        higher-tier arrival is rejected for lack of capacity, the engine
        preempts a lower-tier running task and re-solves the freed rows —
        the solver itself stays SLA-blind. Bookkeeping is identical to a
        solver eviction surfaced by :meth:`apply` — one retry consumed, the
        warm-start pin cleared (an evicted task has no served stream), the
        task re-queued or dropped on an exhausted budget — plus separate
        ``preemptions``/``preemptions_by_tier`` attribution (the EVICTED
        task's tier). Returns ``True`` if the victim re-queued, ``False`` if
        it dropped. A re-queued victim keeps its slot (it is still a
        candidate); the caller excludes that row from its delta re-solve and
        re-dirties it so the next consuming sync rescatters the real row.
        """
        if request_id not in self.tasks:
            raise KeyError(
                f"request {request_id} is not running in cell {self.cell}")
        self.tasks.pop(request_id)
        slot = self._slot_of[request_id]
        self._unbind(slot)
        tier = int(self._tier[slot])
        self.evictions += 1
        self.evictions_by_tier[tier] += 1
        self.preemptions += 1
        self.preemptions_by_tier[tier] += 1
        if self._reject_one(slot):
            self._queue.append((request_id, int(self._gen[slot])))
            return True
        self._drop_one(slot, request_id)
        return False

    def shed(self, request_id: int) -> SliceRequest:
        """Policy-drop a QUEUED request immediately (tier-based shedding).

        Graceful-degradation path: under pressure the engine sheds
        low-priority queued requests BEFORE the solve, so the solver never
        arbitrates between SLA classes it cannot see. Counted as a drop
        (``drops``/``dropped``, so loops that diff drops see it) and
        separately as a shed (``sheds``/``sheds_by_tier``) for attribution.
        Running tasks cannot be shed — evicting them is the solver's call.
        """
        p = self._pending_in.pop(request_id, None)
        if p is not None:
            req = p[0]
        else:
            slot = self._slot_of.get(request_id)
            if slot is None or self._state[slot] != _QUEUED:
                raise KeyError(
                    f"request {request_id} is not queued in cell {self.cell} "
                    "(running tasks are evicted by the solver, not shed)")
            req = self._req[slot]
            self._slot_of.pop(request_id)
            self._free_slot(slot)
        self._leave(request_id)
        self.drops += 1
        self.drops_by_tier[req.tier] += 1
        self.sheds += 1
        self.sheds_by_tier[req.tier] += 1
        self.dropped.append(req)
        return req

    def drain(self) -> list[tuple[SliceRequest, TaskRuntime | None, int,
                                  float | None]]:
        """Release the cell's ENTIRE candidate set for re-homing (outage).

        Returns ``(request, runtime, retries_left, pinned_accuracy)`` tuples
        in deterministic order — running tasks first (task order), then the
        queue FIFO — with the same carry semantics as :meth:`hand_out`:
        running tasks pin their achieved-``z`` accuracy bound and carry
        their runtime; queued requests keep whatever pin/runtime they
        already carried. No drop accounting here — the FAILED cell did not
        drop anything; what cannot be re-homed is dropped by the caller.
        Every vacated slot is reported dirty exactly once by the next
        :meth:`sync_slots`, so the device session sees the dead cell as
        cleared rows instead of a rebuild.
        """
        items: list[tuple[SliceRequest, TaskRuntime | None, int,
                          float | None]] = []
        for rid in list(self.tasks):
            req, rt, retries = self.hand_out(rid)
            items.append((req, rt, retries,
                          pinned_accuracy_at(req, rt.decision.z,
                                             model=self.sdla.semantics)))
        for rid in self.queued_ids():
            p = self._pending_in.pop(rid, None)
            if p is not None:
                req, retries, pin, rt, _ = p
            else:
                slot = self._slot_of.pop(rid)
                req = self._req[slot]
                retries = int(self._retries_left[slot])
                pin = float(self._pin[slot])
                rt = self._rt[slot]
                self._free_slot(slot)
            self._leave(rid)
            items.append((req, rt, retries, pin if pin > 0.0 else None))
        self._queue.clear()
        return items

    # ------------------------------------------------------ handover hooks
    def hand_out(self, request_id: int) -> tuple[SliceRequest, TaskRuntime,
                                                 int]:
        """Release a RUNNING task for handover: (request, runtime, retries)."""
        if request_id not in self.tasks:
            raise KeyError(
                f"request {request_id} is not running in cell {self.cell}")
        rt = self.tasks.pop(request_id)
        slot = self._slot_of.pop(request_id)
        req = self._req[slot]
        retries = int(self._retries_left[slot])
        self._free_slot(slot)
        self._leave(request_id)
        return req, rt, retries

    def hand_in(self, request: SliceRequest, runtime: TaskRuntime | None,
                retries: int, pinned_accuracy: float | None):
        """Accept a handed-over (or outage-drained) task: queue it with its
        warm-start pin; the runtime (job/latency history) resumes if the next
        re-slice admits. ``runtime``/``pinned_accuracy`` are ``None`` for a
        request that was merely QUEUED in the source cell (a drained retry
        has no encoded stream or job history to carry)."""
        try:
            self._enter(request, retries,
                        0.0 if pinned_accuracy is None else pinned_accuracy,
                        runtime)
        except ValueError:
            raise ValueError(
                f"request {request.request_id} is already live in cell "
                f"{self.cell}; cannot hand in a duplicate") from None

    # --------------------------------------------------------------- data
    def _run_vision_job(self, rt: TaskRuntime, batch: int):
        """Frame ingest path: compress by z (resize kernel), then 'infer'."""
        frames = self.frames.frames(self.step, batch)
        z = max(rt.decision.z, 0.02)
        compressed = resize_ops.compress_frames(
            jax.numpy.asarray(frames), z, use_kernel=True)
        return np.asarray(compressed)

    def _run_lm_job(self, rt: TaskRuntime, batch: int):
        cfg, params, infer_fn = self._models[rt.decision.request.model]
        rng = np.random.default_rng(self.step)
        toks = rng.integers(0, cfg.vocab_size, size=(batch, 16), dtype=np.int32)
        return infer_fn(params, {"tokens": jax.numpy.asarray(toks)})

    def process(self, wall_dt: float = 1.0):
        """One engine tick: run the admitted tasks' arrived jobs."""
        self.step += 1
        for rt in self.tasks.values():
            req = rt.decision.request
            n_jobs = max(1, int(round(req.jobs_per_sec * req.n_ues * wall_dt)))
            done = 0
            while done < n_jobs:
                b = min(self.max_batch, n_jobs - done)
                t0 = time.time()
                if req.model in self._models:
                    self._run_lm_job(rt, b)
                else:
                    self._run_vision_job(rt, b)
                compute_s = (time.time() - t0) / b
                # end-to-end accounting: modeled network + sched latency with
                # the sliced radio share, plus the measured compute time. The
                # stream size resolves through the SAME SDLA resolver used at
                # admission time (an explicit bits_per_job=0.0 stays 0.0).
                alloc = np.array([rt.decision.alloc[n]
                                  for n in self.pool.names])
                modeled = latency_model(
                    self.sdla.lat_params, self.sdla.bits_per_job(req),
                    req.jobs_per_sec * req.n_ues, 0.0,  # compute term measured
                    rt.decision.z, alloc)
                rt.latencies.append(float(modeled) + compute_s)
                rt.jobs_done += b
                done += b

    # ------------------------------------------------------------ metrics
    def metrics(self) -> dict:
        out = {}
        for rid, rt in self.tasks.items():
            rec = {
                "app": rt.decision.request.app_class,
                "z": rt.decision.z,
                "alloc": rt.decision.alloc,
                "jobs_done": rt.jobs_done,
                "deadline_s": rt.decision.request.max_latency_s,
            }
            if rt.latencies:
                lat = np.array(rt.latencies)
                rec.update(
                    p50_latency_s=float(np.median(lat)),
                    p99_latency_s=float(np.quantile(lat, 0.99)),
                    meets_deadline=bool(
                        np.median(lat)
                        <= rt.decision.request.max_latency_s),
                    no_data=False,
                )
            else:
                # an idle/starved task has no latency evidence: report that,
                # never a vacuous 0.0-latency "meets deadline"
                rec.update(p50_latency_s=None, p99_latency_s=None,
                           meets_deadline=False, no_data=True)
            out[rid] = rec
        return out


def _reject(cells: Sequence[CellRuntime], k: np.ndarray, trow: np.ndarray,
            slots: np.ndarray) -> np.ndarray:
    """One retry consumed by each rejected entry (cell ``cells[k[i]]``, its
    table row ``trow[i]``, slot ``slots[i]``), its pin cleared, and the
    entries with budget left re-queued; returns which those are. Their queue
    entries, and the drops of the others, are the caller's."""
    if not slots.size:
        return np.zeros(0, bool)
    tab = cells[0]._tables
    # no served stream to warm-start from: a rejected task re-offers at its
    # class threshold, not the pinned one
    pinned = np.flatnonzero(tab._pin[trow, slots] != 0.0)
    if pinned.size:
        tab._pin[trow[pinned], slots[pinned]] = 0.0
        for c, a in zip(k[pinned].tolist(), slots[pinned].tolist()):
            cells[c]._row[a] = cells[c]._req[a]
    left = tab._retries_left[trow, slots] - 1
    tab._retries_left[trow, slots] = left
    keep = left >= 0
    # the task stays in the system: its job/latency history (kept in _rt as
    # the parked carry) resumes on re-admission
    tab._state[trow[keep], slots[keep]] = _QUEUED
    return keep


def _drop(cells: Sequence[CellRuntime], k: np.ndarray, trow: np.ndarray,
          slots: np.ndarray, rids: list[int]):
    """Retry budgets exhausted: count and log each entry's drop (cell
    ``cells[k[i]]``, table row ``trow[i]``, slot ``slots[i]``, request
    ``rids[i]``), free its slot, take it out of the registry."""
    if not slots.size:
        return
    tab = cells[0]._tables
    _tally(cells, "drops_by_tier", k, tab._tier[trow, slots])
    for name, value in _FREED:
        getattr(tab, name)[trow, slots] = value
    for c, a, rid in zip(k.tolist(), slots.tolist(), rids):
        cell = cells[c]
        cell.drops += 1
        cell.dropped.append(cell._req[a])
        del cell._slot_of[rid]
        if cell._registry is not None:
            cell._registry.pop(rid, None)
        cell._req[a] = cell._row[a] = cell._rt[a] = None
        heapq.heappush(cell._free_slots, a)


def _tally(cells: Sequence[CellRuntime], counter: str, k: np.ndarray,
           tiers: np.ndarray):
    """``cells[k[i]].<counter>[tiers[i]] += 1`` for every ``i``."""
    if not k.size:
        return
    low = int(tiers.min())
    span = int(tiers.max()) - low + 1
    counts = np.bincount(k * span + (tiers - low))
    keys = np.flatnonzero(counts)
    for key, n in zip(keys.tolist(), counts[keys].tolist()):
        c, t = divmod(key, span)
        getattr(cells[c], counter)[t + low] += n


def apply_decisions(cells: Sequence[CellRuntime],
                    decisions: Sequence[Sequence[SliceDecision]]) -> list:
    """Apply one re-slice round's decisions, ``decisions[k]`` to
    ``cells[k]`` (the semantics of :meth:`CellRuntime.apply`), as columns.

    ``decisions[k]`` is a :class:`CellDecisions` or a sequence of
    :class:`SliceDecision`, turned into one first. The cells share one
    :class:`SlotTables`, so each table read or write is one ``(row, slot)``
    gather or scatter over every cell's decided rows: the slot check (is
    the row's request still seated where it was decided?), the per-tier
    counters, the admitted and rejected rows' states, retries, pins and
    freed slots. Python work is left to the rows whose state changes (a
    runtime for a newly admitted task, an eviction flag, a drop), to the
    rows that fail the slot check (decided unseated, or departed or
    re-seated since: :meth:`CellRuntime._apply_row`), and to each cell's
    ``tasks`` and queue, rebuilt in the order a row-by-row apply gives them.
    A running task's runtime finds its decision through its slot's row of
    the cell's newest batch (``_drow``, one scatter), bound once when it
    starts running; the :class:`SliceDecision` is built only when read.
    Returns ``decisions`` as a list.
    """
    decisions = list(decisions)
    K = len(cells)
    batches = [ds if isinstance(ds, CellDecisions) else CellDecisions.of(ds)
               for ds in decisions]
    sizes = [len(b) for b in batches]
    ends = list(itertools.accumulate(sizes))
    starts = [e - n for e, n in zip(ends, sizes)]
    live = [b for b in batches if len(b)]
    tab = cells[0]._tables
    trows = np.fromiter((c._trow for c in cells), np.int64, K)
    # the decided rows of all cells, in cell order: one slice of the
    # solve's columns where the batches are its contiguous cuts
    cols = live[0].columns if live else None
    if cols is not None and cols.slot is not None and all(
            b.columns is cols and b.lo == a.hi
            for a, b in zip(live, live[1:])):
        lo, hi = live[0].lo, live[-1].hi
        rid, slot = cols.rid[lo:hi], cols.slot[lo:hi]
        adm = cols.admitted[lo:hi]
    elif live:
        rid = np.concatenate([b.rid for b in batches])
        adm = np.concatenate([b.admitted for b in batches])
        slot = np.concatenate([
            b.slot if b.slot is not None else np.fromiter(
                (c._slot_of.get(r, -1) for r in b.rid.tolist()), np.int64,
                len(b)) for c, b in zip(cells, batches)])
    else:
        rid = slot = np.zeros(0, np.int64)
        adm = np.zeros(0, bool)
    n = rid.size
    rid_l = rid.tolist()
    pos = np.repeat(np.arange(K), sizes)
    trow = trows[pos]
    fast = (slot >= 0) & (slot < tab.cap)
    fast[fast] = tab._rid[trow[fast], slot[fast]] == rid[fast]
    f = np.flatnonzero(fast)
    fk, fr, ft = pos[f], trow[f], slot[f]
    st = tab._state[fr, ft]                  # as the re-slice found them
    tier = tab._tier[fr, ft]
    fa = adm[f]
    # what the carry-forward and the queue rebuild need, before any write:
    # per cell, the decided rows that were running / seated and queued, and
    # every seated queued request
    prevs = [c.tasks for c in cells]
    running = np.bincount(fk[st == _RUNNING], minlength=K).tolist()
    queued = np.bincount(fk[st == _QUEUED], minlength=K).tolist()
    waiting = np.count_nonzero(tab._state[trows] == _QUEUED, axis=1).tolist()
    slow: dict[int, list[int]] = {}
    for i in np.flatnonzero(~fast).tolist():
        k = int(pos[i])
        slow.setdefault(k, []).append(i)
        running[k] += rid_l[i] in prevs[k]
        a = cells[k]._slot_of.get(rid_l[i])
        queued[k] += a is not None and cells[k]._state[a] == _QUEUED
    # the rows in their slots, as masked updates
    starts_a = np.asarray(starts, np.int64)
    _tally(cells, "offered_by_tier", fk, tier)
    _tally(cells, "admitted_by_tier", fk[fa], tier[fa])
    # an admitted task's runtime reads its decision through its slot's row
    # of the cell's batch: bound once, when it starts running there
    kept = f[fa]
    new = fa & (st != _RUNNING)
    unbound = kept[tab._drow[fr[fa], ft[fa]] < 0]
    tab._drow[fr[fa], ft[fa]] = kept - starts_a[pos[kept]]
    for k, a, j in zip(pos[unbound].tolist(), slot[unbound].tolist(),
                       (unbound - starts_a[pos[unbound]]).tolist()):
        cells[k]._bind(a, j)
    tab._state[fr[fa], ft[fa]] = _RUNNING
    lost = f[~fa]
    ev = st[~fa] == _RUNNING
    evicted = lost[ev].tolist()
    for k, a in zip(pos[lost[ev]].tolist(), slot[lost[ev]].tolist()):
        cells[k].evictions += 1
        cells[k]._unbind(a)
    _tally(cells, "evictions_by_tier", pos[lost[ev]], tier[~fa][ev])
    keep = _reject(cells, pos[lost], trow[lost], slot[lost])
    # the state each row leaves its request in (-1: a stale decision)
    state = np.full(n, -1, np.int8)
    state[f[fa]] = _RUNNING
    state[lost[keep]] = _QUEUED
    gone = lost[~keep]
    state[gone] = _FREE
    moved = (np.bincount(fk[new], minlength=K)
             + np.bincount(pos[lost[ev | ~keep]], minlength=K)).tolist()
    at = slot
    if slow:
        # a cell's rows out of their slots seat, and its spent rows free
        # theirs, in decision order: a seat takes the lowest slot free at
        # its turn
        at = slot.copy()
        ordered = np.isin(pos[gone], list(slow))
        for k, i in zip(pos[gone[ordered]].tolist(),
                        gone[ordered].tolist()):
            slow[k].append(i)
        gone = gone[~ordered]
        for k, rows in slow.items():
            cell = cells[k]
            for i in sorted(rows):
                if state[i] == _FREE:
                    cell._drop_one(int(at[i]), rid_l[i])
                    continue
                state[i], at[i], changed, was_running = cell._apply_row(
                    rid_l[i], bool(adm[i]), prevs[k], i - starts[k])
                moved[k] += changed
                if was_running:
                    evicted.append(i)
    _drop(cells, pos[gone], trow[gone], slot[gone],
          [rid_l[i] for i in gone.tolist()])
    for i in evicted:
        k = int(pos[i])
        b = batches[k]
        b.columns.flag_evicted(b.lo + i - starts[k])
    won = np.flatnonzero(state == _RUNNING)
    won_rid = rid[won].tolist()
    won_at = at[won].tolist()
    back = np.flatnonzero(state == _QUEUED)
    requeue = list(zip(rid[back].tolist(),
                       tab._gen[trow[back], at[back]].tolist()))
    wcut = np.searchsorted(won, starts_a).tolist() + [won.size]
    qcut = np.searchsorted(back, starts_a).tolist() + [back.size]
    for k, cell in enumerate(cells):
        # running tasks / queued requests the decisions do not cover (e.g.
        # submitted between sync and apply) are carried forward untouched;
        # this rebuild also purges the queue's lazy-deleted stale entries
        prev = prevs[k]
        ids = won_rid[wcut[k]:wcut[k + 1]]
        if running[k] < len(prev):
            skip = set(rid_l[starts[k]:ends[k]])
            tasks = {r: rt for r, rt in prev.items() if r not in skip}
            for r in tasks:
                cell._unbind(cell._slot_of[r])
        elif ids == list(prev):
            tasks = None                     # the same tasks, in order
        else:
            tasks = {}
        if tasks is None:
            cell.tasks = prev.copy()
        else:
            rt_of = cell._rt
            tasks.update(zip(ids, [rt_of[a]
                                   for a in won_at[wcut[k]:wcut[k + 1]]]))
            cell.tasks = tasks
        cell._batch = batches[k]
        queue = requeue[qcut[k]:qcut[k + 1]]
        if queued[k] < waiting[k]:
            skip = set(rid_l[starts[k]:ends[k]])
            queue[:0] = [(r, cell.gen_of(r)) for r in cell.queued_ids()
                         if r not in skip]
        elif cell._pending_in:
            # no seated request left undecided: what stays queued ahead of
            # the re-queued rows is the unseated arrivals, in arrival order
            skip = set(rid_l[starts[k]:ends[k]]) if k in slow else ()
            queue[:0] = [(r, p[4]) for r, p in cell._pending_in.items()
                         if r not in skip]
        cell._queue = queue
        cell.moved += moved[k]
    return decisions


class EdgeServingEngine:
    """DEPRECATED shim: a thin 1-cell view over
    :class:`repro.serving.multicell.MultiCellEngine`.

    Kept so single-cell callers continue to work, but there is ONE code
    path now: process/metrics/retry live in the shared :class:`CellRuntime`
    and ``reslice()`` routes through the multi-cell engine's device-resident
    fast path. New code should construct ``MultiCellEngine([pool])`` (or use
    the event-stream ``ingest`` API) directly.
    """

    def __init__(self, pool: ResourcePool, *, lat_params=None,
                 max_batch: int = 8, max_retries: int = 2,
                 solver_backend: str = "numpy"):
        from .multicell import MultiCellEngine   # avoid an import cycle
        self.pool = pool
        self._multi = MultiCellEngine(
            [pool], lat_params=lat_params, max_batch=max_batch,
            max_retries=max_retries, solver_backend=solver_backend)

    # thin delegation — the multi-cell engine owns all serving state
    @property
    def sdla(self) -> SDLA:
        return self._multi.sdla

    @property
    def sesm(self):
        return self._multi.sesm

    @property
    def runtime(self) -> CellRuntime:
        return self._multi.cells[0]

    @property
    def tasks(self) -> dict[int, TaskRuntime]:
        return self.runtime.tasks

    @property
    def pending(self) -> tuple[SliceRequest, ...]:
        return self.runtime.pending

    @property
    def dropped(self) -> tuple[SliceRequest, ...]:
        """Recent drop events (bounded log; diff ``runtime.drops`` counts)."""
        return tuple(self.runtime.dropped)

    def register_model(self, name: str, cfg, params, infer_fn):
        self.runtime.register_model(name, cfg, params, infer_fn)

    def submit(self, request: SliceRequest):
        self._multi.submit(request, 0)

    def reslice(self) -> list[SliceDecision]:
        """Run SESM over pending + running requests (full re-slice: running
        tasks may be evicted — paper Section III-C; rejected requests stay on
        the bounded retry queue instead of being discarded)."""
        return self._multi.reslice()[0]

    def process(self, wall_dt: float = 1.0):
        self._multi.process(wall_dt)

    def metrics(self) -> dict:
        return self.runtime.metrics()


def pinned_accuracy_at(request: SliceRequest, z: float,
                       model: semantics.SemanticModel | None = None) -> float:
    """The warm-start accuracy bound of a stream already encoded at ``z`` —
    Eq. (2) then re-derives (at most) that compression in the target cell.
    (Request-level wrapper over the single-source pin in core.semantics.)

    ``model`` selects whose curves price the pin — the engine passes its
    SDLA's live (possibly drifted) model, so a pin records the accuracy the
    stream achieves UNDER THE CURVES IT WAS ENCODED UNDER; once recorded it
    is a value, unaffected by later drift."""
    return semantics.resolve(model).warm_start_accuracy(
        semantics.APP_INDEX[request.app_class], z)
