"""Semantic Edge Slicing Module (SESM) — the Near-real-time RIC xApp.

Runs the SF-ESP greedy (core.greedy, optionally via the Pallas inner kernel)
over the current request set + edge status and emits the three-fold output of
paper Section III-B: (i) admitted tasks, (ii) per-task compression level,
(iii) per-task resource slices. Re-slicing is full (new and running tasks are
equally considered — already-running tasks may be evicted, Section III-C).
"""

from __future__ import annotations

import collections.abc
import dataclasses
from typing import Sequence

import numpy as np

from repro.core import (CouplingSpec, ResourcePool, check_solution,
                        default_z_grid, next_pow2, restack, semantics, solve,
                        solve_greedy_batch, solve_greedy_sharded,
                        stack_instances, union_allocation_grid)
from repro.core import latency as lat_mod
from repro.core.greedy import (dispatch_device_batch, dispatch_sharded_batch,
                               unpack_device_batch, unpack_sharded_batch)
from repro.core.sfesp import (DeviceStack, ShardedStack, empty_device_stack,
                              empty_sharded_stack, stack_grid,
                              task_feasibility_rows)
from repro.trace import span
from .request import SliceRequest
from .sdla import SDLA

__all__ = ["CellDecisions", "DecisionColumns", "PendingSolve",
           "SliceDecision", "SESM"]


class PendingSolve:
    """Handle to a dispatched, not-yet-awaited re-slice solve.

    Returned by ``SESM.solve_slots(..., wait=False)``: the device program is
    launched and the host mirrors it unpacks against are snapshotted (the
    back buffer), so the serving loop can keep mutating its slot tables —
    ingesting tick N+1's events — while tick N solves. :meth:`wait` blocks
    on the device result exactly once and returns the per-cell decisions;
    repeat calls return the same list.
    """

    def __init__(self, resolve):
        self._resolve = resolve
        self._result = None

    def wait(self):
        if self._resolve is not None:
            self._result = self._resolve()
            self._resolve = None
        return self._result

    @classmethod
    def ready(cls, decisions) -> "PendingSolve":
        """An already-resolved handle (empty ticks, host-blocking solves)."""
        p = cls(None)
        p._result = decisions
        return p


@dataclasses.dataclass
class SliceDecision:
    """One task's re-slice outcome: admission, compression z, slice."""

    request: SliceRequest
    admitted: bool
    z: float
    alloc: dict[str, float]
    expected_latency_s: float
    expected_accuracy: float
    # control-plane plumbing for cell-indexed decision sets: which cell of a
    # multi-cell re-slice this decision belongs to, and whether a rejection
    # evicted a previously-RUNNING task (vs turning away a pending request)
    cell: int | None = None
    evicted: bool = False


class DecisionColumns:
    """One solve's decisions as columns, each cell's rows contiguous.

    Columns, one row per decision: ``requests`` (the solved rows), ``rid``
    (their request ids), ``slot`` (the solver-row slot each was decided in;
    ``None`` for decisions that came as objects), ``admitted``, ``z``,
    ``alloc`` ((n, m)), ``latency`` and ``accuracy`` (the expected ones) and
    ``evicted``, the one column ``CellRuntime.apply`` writes. ``built``
    holds the rows' :class:`SliceDecision` objects once made; ``sesm``
    counts those it makes (``SESM.decisions_built``).
    """

    __slots__ = ("requests", "rid", "slot", "admitted", "z", "alloc",
                 "latency", "accuracy", "evicted", "built", "sesm")

    def __init__(self, requests, rid, slot, admitted, z, alloc, latency,
                 accuracy, sesm: "SESM | None" = None):
        self.requests = requests
        self.rid = rid
        self.slot = slot
        self.admitted = admitted
        self.z = z
        self.alloc = alloc
        self.latency = latency
        self.accuracy = accuracy
        self.evicted = np.zeros(len(requests), bool)
        self.built: list[SliceDecision | None] | None = None
        self.sesm = sesm

    def decision(self, i: int, names: tuple[str, ...],
                 cell: int | None) -> SliceDecision:
        """Row ``i``'s object, built on first use."""
        if self.built is None:
            self.built = [None] * len(self.requests)
        d = self.built[i]
        if d is None:
            d = self.built[i] = SliceDecision(
                request=self.requests[i],
                admitted=bool(self.admitted[i]),
                z=float(self.z[i]),
                alloc={n: float(self.alloc[i, k])
                       for k, n in enumerate(names)},
                expected_latency_s=float(self.latency[i]),
                expected_accuracy=float(self.accuracy[i]),
                cell=cell,
                evicted=bool(self.evicted[i]),
            )
            if self.sesm is not None:
                self.sesm.decisions_built += 1
        return d

    def flag_evicted(self, rows) -> None:
        """Mark ``rows`` as evictions, in the column and on built objects."""
        self.evicted[rows] = True
        if self.built is not None:
            for i in np.atleast_1d(rows).tolist():
                if self.built[i] is not None:
                    self.built[i].evicted = True


class CellDecisions(collections.abc.Sequence):
    """One cell's decisions of one re-slice: a view over rows
    ``[lo, hi)`` of the solve's :class:`DecisionColumns`.

    What :meth:`SESM.solve_slots` returns per cell. It reads exactly as the
    list of :class:`SliceDecision` it stands for (same values, same order;
    ``==`` against such a list holds), while ``CellRuntime.apply`` works on
    the columns alone. Row ``i``'s object is built the first time it is
    read and then kept, so a tick whose decisions nobody reads builds none
    (``SESM.decisions_built``). The ``evicted`` flags ``apply`` writes show
    in the column and on built objects alike.
    """

    __slots__ = ("columns", "lo", "hi", "cell", "names")

    def __init__(self, columns: DecisionColumns, lo: int, hi: int,
                 cell: int | None, names: tuple[str, ...]):
        self.columns = columns
        self.lo = lo
        self.hi = hi
        self.cell = cell
        self.names = names

    @classmethod
    def of(cls, decisions: Sequence[SliceDecision]) -> "CellDecisions":
        """The columns of decisions that exist as objects; the objects are
        the built rows, so flags written through the columns reach them."""
        ds = list(decisions)
        n = len(ds)
        names = tuple(ds[0].alloc) if ds else ()
        cols = DecisionColumns(
            [d.request for d in ds],
            np.fromiter((d.request.request_id for d in ds), np.int64, n),
            None,
            np.fromiter((d.admitted for d in ds), bool, n),
            np.fromiter((d.z for d in ds), float, n),
            np.array([[d.alloc[k] for k in names] for d in ds],
                     float).reshape(n, len(names)),
            np.fromiter((d.expected_latency_s for d in ds), float, n),
            np.fromiter((d.expected_accuracy for d in ds), float, n))
        cols.evicted[:] = [d.evicted for d in ds]
        cols.built = ds
        return cls(cols, 0, n, ds[0].cell if ds else None, names)

    def __len__(self) -> int:
        return self.hi - self.lo

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        n = self.hi - self.lo
        if not -n <= i < n:
            raise IndexError("decision index out of range")
        return self.columns.decision(self.lo + i % n, self.names, self.cell)

    def __iter__(self):
        decision = self.columns.decision
        for i in range(self.lo, self.hi):
            yield decision(i, self.names, self.cell)

    def __eq__(self, other):
        if isinstance(other, (CellDecisions, list, tuple)):
            return list(self) == list(other)
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return (f"CellDecisions(cell={self.cell}, rows={len(self)}, "
                f"admitted={int(self.admitted.sum())})")

    # the cell's rows of the columns apply and its tests read
    rid = property(lambda self: self.columns.rid[self.lo:self.hi])
    admitted = property(lambda self: self.columns.admitted[self.lo:self.hi])
    evicted = property(lambda self: self.columns.evicted[self.lo:self.hi])
    alloc = property(lambda self: self.columns.alloc[self.lo:self.hi])

    @property
    def slot(self) -> np.ndarray | None:
        s = self.columns.slot
        return None if s is None else s[self.lo:self.hi]


@dataclasses.dataclass
class _ServeSession:
    """Device-resident serving state persisted across re-slice ticks.

    One per (batch size, Tmax bucket, algorithm, latency-scale epoch): the
    :class:`~repro.core.sfesp.DeviceStack` holds the solver inputs on device,
    the host mirrors hold the per-slot scalars the decision unpack needs
    (compression, app class, stream rate), and ``pending`` accumulates dirty
    slots until a live solve consumes them — deltas reported on a tick whose
    solve is skipped (transiently all-empty batch) must survive to the next.

    With a metro ``mesh`` configured the device half is a MESH-RESIDENT
    :class:`~repro.core.sfesp.ShardedStack` instead: the coupling groups are
    shard-planned once at build, dirty slots scatter through the group-major
    perm (``ShardedStack.update_rows``), and the tick solves as one
    ``shard_map`` program. The session-level triggers are identical, plus
    shard-plan invalidation: a coupling-group membership change (a DIFFERENT
    coupling object) replans + rebuilds (``sesm.shard_replans``), while
    budget/semantic drift rides the same in-place scatters as the
    single-device session.
    """

    dev: DeviceStack | ShardedStack
    grid: np.ndarray                 # host copy, for alloc unpack
    cell_mask: np.ndarray | None     # (B, A) each cell's own allocations on
    # a union grid (ANDed into every recomputed lat_ok row); None when every
    # cell is on ``grid``
    z_grid: np.ndarray
    names: list[tuple[str, ...]]     # per-cell resource names
    pools_ref: object                # identity guards: the engine passes the
    coupling_ref: object             # same objects every tick
    pool_state: np.ndarray           # (B, 2m) price|capacity VALUE snapshot —
    # ResourcePool is frozen but its arrays are not; an in-place capacity
    # edit must invalidate the session, not silently solve stale pools
    link_cap_state: np.ndarray | None  # (L,) link-budget VALUE snapshot —
    # unlike a pool edit, an in-place budget edit (CouplingSpec.set_budgets:
    # link degradation) does NOT invalidate the session: the link set is
    # unchanged, so the delta is one (L,) device refresh
    # (DeviceStack.update_link_budgets), counted in ``sesm.link_updates``
    sem_ref: object                  # the SDLA's SemanticModel — identity
    # guard: swapping in a DIFFERENT model object rebuilds the session
    sem_version: int                 # model-version snapshot — an IN-PLACE
    # drift of the same model (version bump) does NOT invalidate: the changed
    # apps' live rows re-run the min-z pipeline and delta-scatter
    # (DeviceStack.update_semantics), counted in ``sesm.semantic_updates``
    scale: float
    semantic: bool
    flexible: bool
    # host mirrors, (B, Tmax) each
    rid: np.ndarray                  # request id, -1 on a cleared row
    z_star: np.ndarray
    has_z: np.ndarray
    app_idx: np.ndarray
    bits: np.ndarray
    rate: np.ndarray
    gpu_t: np.ndarray
    pending: set[tuple[int, int]]

    @property
    def batch_size(self) -> int:
        return self.z_star.shape[0]

    @property
    def max_tasks(self) -> int:
        return self.z_star.shape[1]


class SESM:
    """The SESM xApp: SF-ESP admission over live request sets.

    Front doors: :meth:`slice` (one cell, one solve), :meth:`solve_batch`
    (many request sets — what-if studies or the cells of one coupled
    deployment — in ONE device program, restack-cached across calls) and
    :meth:`solve_slots` (the device-resident delta fast path over sticky
    solver-row slots). A configured ``mesh`` routes ``solve_batch`` through
    the sharded metro solve (``core.greedy.solve_greedy_sharded``) and makes
    :meth:`solve_slots`'s serve session MESH-RESIDENT: a
    :class:`~repro.core.sfesp.ShardedStack` persisted across ticks, delta
    scatters addressed through the shard plan, one ``shard_map`` serve per
    tick (``core.greedy.dispatch_sharded_batch``).
    """

    def __init__(self, pool: ResourcePool, sdla: SDLA | None = None,
                 backend: str = "numpy", inner: str = "jnp", mesh=None):
        self.pool = pool
        self.sdla = sdla or SDLA()
        self.backend = backend
        self.inner = inner
        # metro mode: a 1-D "cells" device mesh routes solve_batch through
        # the sharded coupled solve (launch.mesh.make_cells_mesh)
        self.mesh = mesh
        self.algorithm = {"semantic": True, "flexible": True}
        # padded stacking buffers reused across solve_batch calls (the
        # closed-loop re-slice case: only tasks/capacities change per call)
        self._batch_cache = None
        # device-resident serving session reused across solve_slots ticks
        self._serve_session: _ServeSession | None = None
        # stacking-cache telemetry: fresh_stacks counts (re)allocations of the
        # padded buffers, restacks counts in-place refills — a healthy closed
        # loop shows fresh_stacks == 1 after the first tick (zero cache
        # misses). On the fast path a "refill" is a delta sync; delta_rows
        # counts the task rows actually recomputed + scattered (zero per
        # steady-state tick).
        self.fresh_stacks = 0
        self.restacks = 0
        self.delta_rows = 0
        # of delta_rows, the live rows recomputed under a cell mask that is
        # not all true (cells off the union grid's every allocation)
        self.masked_rows = 0
        # admission rounds the device ran, summed over the slot solves (the
        # loop's trip count, read back with the packed decisions)
        self.rounds = 0
        # fault-plane telemetry: session_rebuilds counts LIVE serve sessions
        # torn down by an invalidating change (batch/bucket/pools/coupling
        # identity/latency scale — first-ever builds are not rebuilds);
        # link_updates counts budget-only coupling refreshes that kept the
        # session alive (the degradation fast path)
        self.session_rebuilds = 0
        self.link_updates = 0
        # semantic-drift telemetry: ticks whose model-version bump was
        # absorbed as dirty-row delta scatters with the session kept alive
        # (the drift fast path; rows counted on dev.semantic_rows)
        self.semantic_updates = 0
        # metro telemetry: shard-plan computations (one per sharded-session
        # build — a coupling-group membership change is the only way to force
        # a replan once the session is warm; budget/semantic drift must not)
        self.shard_replans = 0
        # SliceDecision objects built from the slot solves' columns
        # (CellDecisions): 0 over ticks whose decisions nobody reads
        self.decisions_built = 0

    def slice(self, requests: list[SliceRequest]) -> list[SliceDecision]:
        if not requests:
            return []
        inst = self.sdla.build_instance(requests, self.pool)
        sol = solve(inst, backend=self.backend, inner=self.inner,
                    **self.algorithm)
        return self._decisions(requests, inst, sol)

    def solve_batch(self, request_sets: list[list[SliceRequest]],
                    coupling: CouplingSpec | None = None,
                    pools: Sequence[ResourcePool] | None = None
                    ) -> list[list[SliceDecision]]:
        """Evaluate many candidate re-slice decisions in ONE device program.

        Each element of ``request_sets`` is one hypothetical request mix —
        e.g. the projected task sets over a re-slicing horizon, or the
        alternatives of a what-if admission study. All sets share this SESM's
        pool, so they stack onto one allocation grid and solve via the
        batched sweep engine; decisions per set match calling :meth:`slice`
        on it (up to the float32 gradient-tie caveat of the JAX backends vs
        the numpy default — see ``solve_greedy_batch``).

        ``coupling`` treats the request sets as CELLS of one multi-cell
        deployment instead of independent what-ifs: ``coupling.incidence``
        must have one row per request set, and sets routed through a common
        shared link admit jointly under its budget (the coupled sweep
        engine; reference semantics in ``core.baselines.solve_coupled_ref``).
        Empty request sets keep their (vacuous) incidence row.

        ``pools`` gives each request set its own resource pool (a multi-cell
        deployment with heterogeneous capacities, and grids: sets on
        different ``levels`` stack onto their union grid, each masked to its
        own). ``None`` keeps this SESM's pool for every set.

        Stacking buffers are padded to a power-of-two ``Tmax`` bucket and
        reused (``restack``) across calls with the same number of request
        sets, so a closed-loop horizon evaluation neither reallocates the
        (B, Tmax, A) host tables nor recompiles the device program per step.
        """
        if coupling is not None and \
                coupling.num_cells != len(request_sets):
            raise ValueError(
                f"coupling.incidence has {coupling.num_cells} rows for "
                f"{len(request_sets)} request sets")
        if pools is not None and len(pools) != len(request_sets):
            raise ValueError(
                f"got {len(pools)} pools for {len(request_sets)} request sets")
        out: list[list[SliceDecision]] = [[] for _ in request_sets]
        if not any(request_sets):
            return out
        # EMPTY sets stay in the batch as zero-task rows (task_mask all
        # False, never-alive padding): a transiently-empty cell in a closed
        # loop must not shrink the batch, which would miss the restack cache
        # and recompile the device program for the new shape
        insts = [self.sdla.build_instance(
            rs, self.pool if pools is None else pools[i])
            for i, rs in enumerate(request_sets)]
        if coupling is not None:
            insts = [dataclasses.replace(inst, coupling=coupling.row(i))
                     for i, inst in enumerate(insts)]
        cache = self._batch_cache
        tneed = max(inst.num_tasks for inst in insts)
        if (cache is not None and cache.batch_size == len(insts)
                and cache.max_tasks >= tneed
                and np.array_equal(cache.grid, stack_grid(insts).grid)):
            stacked = restack(cache, insts)
            self.restacks += 1
        else:
            stacked = stack_instances(insts, tmax=next_pow2(tneed))
            self.fresh_stacks += 1
        self._batch_cache = stacked
        if self.mesh is not None:
            # metro mode: shard the coupled solve over the configured mesh
            # (decisions identical to the single-device engine; the sharded
            # front door re-derives the group-major permutation itself and
            # returns solutions in this batch's row order)
            sols = solve_greedy_sharded(stacked, mesh=self.mesh,
                                        inner=self.inner, **self.algorithm)
        else:
            sols = solve_greedy_batch(stacked, **self.algorithm)
        for i, (rs, inst, sol) in enumerate(zip(request_sets, insts, sols)):
            out[i] = self._decisions(rs, inst, sol, cell=i)
        return out

    # ------------------------------------------------- delta fast path
    def solve_slots(self, slot_rows: list[list[SliceRequest | None]],
                    dirty: list[list[int]],
                    coupling: CouplingSpec | None = None,
                    pools: Sequence[ResourcePool] | None = None,
                    wait: bool = True):
        """Device-resident re-slice: solve the slotted candidate sets,
        recomputing and re-uploading ONLY the dirty rows.

        The fast-path twin of :meth:`solve_batch` for a closed serving loop:
        ``slot_rows[b]`` is cell ``b``'s candidate set in stable slot order
        (``None`` = cleared row; see ``CellRuntime.sync_slots``) and
        ``dirty[b]`` the slots whose content changed since the previous call.
        Invariant tables (grid, lexicographic cost, prices, capacities,
        coupling topology) upload once per session; per-tick work is one
        bucketed scatter of the dirty rows plus ONE fused device program that
        returns the packed decisions (admitted bitmask, s*, residual
        capacities, link loads) in a single small buffer. Decisions per live
        slot are identical to :meth:`solve_batch` on the compacted request
        sets (cleared rows are never feasible and cannot shift tie-breaks);
        each cell's come as a :class:`CellDecisions` over the tick's
        columns, which builds a :class:`SliceDecision` only when read.

        The session rebuilds (a fresh stack) when the Tmax bucket overflows,
        the batch size / algorithm / coupling / pools change, or the SDLA
        latency scale moves (every cached row depends on it); ``pools`` and
        ``coupling`` are identity-compared — pass the same objects per tick,
        as :class:`~repro.serving.multicell.MultiCellEngine` does. TWO
        in-place mutations are sanctioned and keep the session alive:
        ``CouplingSpec.set_budgets`` (link degradation — same coupling
        object, new budget VALUES, detected by value snapshot, applied as a
        single (L,) device refresh, ``sesm.link_updates``) and a
        ``SemanticModel`` drift (same model object, bumped version —
        detected by version snapshot, applied as dirty-row scatters of just
        the live slots whose curves moved, ``sesm.semantic_updates`` /
        ``DeviceStack.update_semantics``). Swapping in a DIFFERENT coupling
        or model object is a rebuild.

        ``wait=False`` returns a :class:`PendingSolve` instead of decisions:
        the dirty rows are consumed, the device program launches, and the
        per-slot host mirrors the unpack needs are snapshotted into the
        handle (the double-buffered back buffer) — the caller blocks only at
        ``PendingSolve.wait()``, typically after ingesting the next tick's
        events. Decisions are identical either way.

        With a metro ``mesh`` configured the session is MESH-RESIDENT: the
        same triggers and in-place survivals apply, but the device half is a
        :class:`~repro.core.sfesp.ShardedStack` (coupling groups shard-planned
        at build, ``sesm.shard_replans``), the dirty rows scatter through the
        group-major perm, and the tick dispatches one ``shard_map`` serve
        (``core.greedy.dispatch_sharded_batch``) — decisions identical to the
        single-device session and to :meth:`solve_batch`.
        """
        B = len(slot_rows)
        if coupling is not None and coupling.num_cells != B:
            raise ValueError(
                f"coupling.incidence has {coupling.num_cells} rows for "
                f"{B} slot sets")
        if pools is not None and len(pools) != B:
            raise ValueError(
                f"got {len(pools)} pools for {B} slot sets")
        out: list[Sequence[SliceDecision]] = [[] for _ in range(B)]
        live = any(r is not None for rows in slot_rows for r in rows)
        tneed = max([len(rows) for rows in slot_rows] + [1])
        scale = self.sdla.latency_scale
        semantic = bool(self.algorithm["semantic"])
        flexible = bool(self.algorithm["flexible"])
        model = self.sdla.semantics
        sess = self._serve_session
        if sess is not None and (
                sess.batch_size != B or tneed > sess.max_tasks
                or sess.scale != scale or sess.semantic != semantic
                or sess.flexible != flexible
                or sess.coupling_ref is not coupling
                or sess.pools_ref is not pools
                or sess.sem_ref is not model
                or isinstance(sess.dev, ShardedStack)
                != (self.mesh is not None)
                or not np.array_equal(sess.pool_state,
                                      self._pool_state(B, pools))):
            sess = self._serve_session = None
            self.session_rebuilds += 1
        if sess is None:
            if not live:
                return out if wait else PendingSolve.ready(out)
            sess = self._build_session(slot_rows, coupling, pools, scale)
            self._serve_session = sess
            self.fresh_stacks += 1
        else:
            for b, d in enumerate(dirty):
                sess.pending.update((b, t) for t in d)
            if coupling is not None and not np.array_equal(
                    sess.link_cap_state, coupling.link_capacity):
                # budget-only degradation: the coupling OBJECT (and with it
                # the link set) is unchanged — only the budgets moved
                # (CouplingSpec.set_budgets). One (L,) device refresh keeps
                # the whole session alive.
                if sess.dev.coupled:
                    sess.dev.update_link_budgets(coupling.link_capacity)
                sess.link_cap_state = coupling.link_capacity.copy()
                self.link_updates += 1
            if model.version != sess.sem_version:
                # semantic drift: the SAME model object moved in place
                # (version bump). The delta is the live rows whose EFFECTIVE
                # curve changed — re-run the shared min-z pipeline on just
                # those and scatter (DeviceStack.update_semantics); the
                # session stays alive.
                self._refresh_semantics(sess, slot_rows, model)
            if not live:
                return out if wait else PendingSolve.ready(out)
            self.restacks += 1
        masked = self._masked_pending(sess, slot_rows)
        with span("repro.sesm.sync_rows", B=B, masked=masked):
            self._sync_rows(sess, slot_rows)
        self.masked_rows += masked
        if isinstance(sess.dev, ShardedStack):
            dispatched = dispatch_sharded_batch(sess.dev, flexible=flexible,
                                                inner=self.inner)
            block = unpack_sharded_batch
        else:
            dispatched = dispatch_device_batch(sess.dev, flexible=flexible,
                                               inner=self.inner)
            block = unpack_device_batch
        unpack = self._slot_unpacker(sess, slot_rows, out)

        def resolve():
            res = block(dispatched)
            self.rounds += res["rounds"]
            with span("repro.sesm.decisions", B=B):
                return unpack(res)

        return resolve() if wait else PendingSolve(resolve)

    def ready_solve(self, request_sets, coupling=None,
                    pools=None) -> PendingSolve:
        """:meth:`solve_batch` wrapped as an already-resolved
        :class:`PendingSolve` — the dispatch-shaped front door for paths
        that solve host-blocking (what-if studies, rebuild comparisons)."""
        return PendingSolve.ready(self.solve_batch(
            request_sets, coupling=coupling, pools=pools))

    def _pool_state(self, B: int, pools) -> np.ndarray:
        cell_pools = [self.pool] * B if pools is None else pools
        return np.concatenate(
            [np.stack([p.price for p in cell_pools]),
             np.stack([p.capacity for p in cell_pools])], axis=1)

    def _build_session(self, slot_rows, coupling, pools,
                       scale) -> _ServeSession:
        B = len(slot_rows)
        cell_pools = [self.pool] * B if pools is None else list(pools)
        # one grid for every cell: their shared grid, or the union of their
        # grids with a per-cell mask of each one's own allocations
        union = union_allocation_grid(cell_pools)
        grid = union.grid
        with span("repro.sesm.build_session", B=B, A=grid.shape[0],
                  grids=union.grids):
            tmax = next_pow2(max([len(rows) for rows in slot_rows] + [1]))
            price = np.stack([p.price for p in cell_pools])
            cap = np.stack([p.capacity for p in cell_pools])
            semantic = bool(self.algorithm["semantic"])
            if self.mesh is not None:
                # metro mode: the session lives ON the mesh — coupling
                # groups are shard-planned here, once; every later tick is
                # delta scatters through that plan plus one shard_map serve
                dev = empty_sharded_stack(
                    grid, price, cap, tmax, self.mesh, coupling=coupling,
                    semantic=semantic, grids=union.grids)
                self.shard_replans += 1
            else:
                dev = empty_device_stack(
                    grid, price, cap, tmax, coupling=coupling,
                    semantic=semantic, grids=union.grids)
            return _ServeSession(
                dev=dev, grid=grid, cell_mask=union.mask,
                z_grid=default_z_grid(),
                names=[p.names for p in cell_pools],
                pools_ref=pools, coupling_ref=coupling,
                pool_state=self._pool_state(B, pools),
                link_cap_state=None if coupling is None
                else coupling.link_capacity.copy(),
                sem_ref=self.sdla.semantics,
                sem_version=self.sdla.semantics.version, scale=scale,
                semantic=semantic,
                flexible=bool(self.algorithm["flexible"]),
                rid=np.full((B, tmax), -1, np.int64),
                z_star=np.ones((B, tmax)), has_z=np.zeros((B, tmax), bool),
                app_idx=np.zeros((B, tmax), np.int64),
                bits=np.zeros((B, tmax)), rate=np.zeros((B, tmax)),
                gpu_t=np.zeros((B, tmax)),
                pending={(b, t) for b, rows in enumerate(slot_rows)
                         for t, r in enumerate(rows) if r is not None},
            )

    @staticmethod
    def _masked_pending(sess: _ServeSession, slot_rows) -> int:
        """Live pending rows of cells whose mask is not all true: the rows
        the next :meth:`_sync_rows` recomputes under a mask."""
        if sess.cell_mask is None:
            return 0
        partial = ~sess.cell_mask.all(axis=1)
        return sum(1 for b, t in sess.pending
                   if partial[b] and t < len(slot_rows[b])
                   and slot_rows[b][t] is not None)

    @staticmethod
    def _own_rows(sess: _ServeSession, rows, cells: np.ndarray,
                  pick: np.ndarray | None = None):
        """(lat_ok, alive) of recomputed ``rows``, those of cells ``cells``
        (or ``cells[pick]``), restricted to each cell's own grid: ``rows``'
        own arrays, untouched, on a shared grid."""
        if sess.cell_mask is None:
            return rows.lat_ok, rows.alive
        lat_ok = rows.lat_ok & sess.cell_mask[
            cells if pick is None else cells[pick]]
        return lat_ok, (rows.z_idx >= 0) & lat_ok.any(axis=1)

    def _sync_rows(self, sess: _ServeSession, slot_rows):
        """Recompute + scatter the pending dirty rows (host AND device)."""
        if not sess.pending:
            return
        items = sorted(sess.pending)
        reqs, live_pos = [], []
        for i, (b, t) in enumerate(items):
            rows = slot_rows[b]
            r = rows[t] if t < len(rows) else None
            if r is not None:
                live_pos.append(i)
                reqs.append(r)
        d = len(items)
        A = sess.grid.shape[0]
        # cleared-row defaults: never feasible, never alive, padding scalars
        lat_ok = np.zeros((d, A), bool)
        alive = np.zeros(d, bool)
        rid = np.full(d, -1, np.int64)
        load = np.zeros(d)
        z = np.ones(d)
        has_z = np.zeros(d, bool)
        app = np.zeros(d, np.int64)
        bits = np.zeros(d)
        rate = np.zeros(d)
        gpu_t = np.zeros(d)
        bb = np.fromiter((b for b, _ in items), np.int64, d)
        tt = np.fromiter((t for _, t in items), np.int64, d)
        if reqs:
            # the ONE per-task min-z pipeline (sfesp.task_feasibility_rows),
            # shared with sdla.build_instance and restricted to the changed
            # rows (unchanged requests cost zero recompute)
            ts = self.sdla.task_set(reqs)
            rows = task_feasibility_rows(
                ts, sess.z_grid, sess.grid, self.sdla.lat_params,
                semantic=sess.semantic, model=self.sdla.semantics)
            li = np.asarray(live_pos, np.int64)
            lat_ok[li], alive[li] = self._own_rows(sess, rows, bb, li)
            rid[li] = [r.request_id for r in reqs]
            load[li] = rows.load
            z[li] = rows.z_star
            has_z[li] = rows.z_idx >= 0
            app[li] = ts.app_idx
            bits[li] = ts.bits_per_job
            rate[li] = ts.jobs_per_sec
            gpu_t[li] = ts.gpu_time_per_job
        sess.rid[bb, tt] = rid
        sess.z_star[bb, tt] = z
        sess.has_z[bb, tt] = has_z
        sess.app_idx[bb, tt] = app
        sess.bits[bb, tt] = bits
        sess.rate[bb, tt] = rate
        sess.gpu_t[bb, tt] = gpu_t
        sess.dev.update_rows(bb, tt, lat_ok, alive, load)
        self.delta_rows += d
        sess.pending.clear()

    def _refresh_semantics(self, sess: _ServeSession, slot_rows, model):
        """Absorb an in-place model drift as dirty-row delta scatters.

        The drifted apps come from the model's change log
        (``changed_since``); only LIVE slots whose effective curve — the
        task's own app, or its service-wide 'All' fallback in agnostic mode —
        actually moved are recomputed (through the same shared pipeline as
        :meth:`_sync_rows`) and scattered via
        :meth:`~repro.core.sfesp.DeviceStack.update_semantics`. Everything
        else (app/bits/rate mirrors, pins, the session itself) is untouched:
        ``session_rebuilds`` stays 0 across drifts.
        """
        changed = model.changed_since(sess.sem_version)
        sess.sem_version = model.version
        if not changed:
            return
        items: list[tuple[int, int]] = []
        reqs: list[SliceRequest] = []
        for b, rows in enumerate(slot_rows):
            for t, r in enumerate(rows):
                if r is None:
                    continue
                a = semantics.APP_INDEX[r.app_class]
                eff = a if sess.semantic else int(model.agnostic_app(a))
                if eff in changed:
                    items.append((b, t))
                    reqs.append(r)
        if not items:
            return
        ts = self.sdla.task_set(reqs)
        rows_ = task_feasibility_rows(
            ts, sess.z_grid, sess.grid, self.sdla.lat_params,
            semantic=sess.semantic, model=model)
        d = len(items)
        bb = np.fromiter((b for b, _ in items), np.int64, d)
        tt = np.fromiter((t for _, t in items), np.int64, d)
        # only the curve-derived mirrors move; the request-derived ones
        # (app_idx, bits, rate, gpu_t) are drift-invariant
        sess.z_star[bb, tt] = rows_.z_star
        sess.has_z[bb, tt] = rows_.z_idx >= 0
        lat_ok, alive = self._own_rows(sess, rows_, bb)
        sess.dev.update_semantics(bb, tt, lat_ok, alive, rows_.load)
        self.semantic_updates += 1

    def _slot_unpacker(self, sess: _ServeSession, slot_rows, out):
        """Build the decision unpacker for one dispatched slot solve.

        Snapshots everything the unpack needs from the session's host
        mirrors AT DISPATCH TIME — live positions, request ids, per-row
        z*/app/stream scalars, request objects, resource names, latency
        params — so the returned closure depends only on the device result.
        That snapshot is the host half of the double buffer: a
        ``wait=False`` caller keeps ingesting events (which may dirty rows
        and later overwrite the mirrors) while the solve is in flight, and
        the unpack still reports against the state that was actually solved.

        The unpack returns one :class:`CellDecisions` per cell with live
        rows (an empty list otherwise): each cell's rows are a contiguous
        range of the b-major positions, cut out of the tick's columns.
        """
        pos = [(b, t) for b, rows in enumerate(slot_rows)
               for t, r in enumerate(rows) if r is not None]
        if not pos:
            return lambda res: out
        bb = np.fromiter((b for b, _ in pos), np.int64, len(pos))
        tt = np.fromiter((t for _, t in pos), np.int64, len(pos))
        # fancy indexing copies: these are value snapshots, not views
        rid = sess.rid[bb, tt]
        has_z = sess.has_z[bb, tt]
        z_star = sess.z_star[bb, tt]
        app_idx = sess.app_idx[bb, tt]
        bits = sess.bits[bb, tt]
        rate = sess.rate[bb, tt]
        gpu_t = sess.gpu_t[bb, tt]
        reqs = [slot_rows[b][t] for b, t in pos]
        names = list(sess.names)
        grid = sess.grid
        lat_params = self.sdla.lat_params
        # curve snapshot at dispatch: a model drift landing while the solve
        # is in flight must not change what the unpack reports (the accuracy
        # half of the double buffer)
        model = self.sdla.semantics.snapshot()
        # each cell's rows: pos[cut[b]:cut[b + 1]]
        cut = np.searchsorted(bb, np.arange(len(slot_rows) + 1)).tolist()

        def unpack(res):
            adm = res["admitted"][bb, tt]
            safe = np.clip(res["alloc_idx"][bb, tt], 0, None)
            z = np.where(adm & has_z, z_star, 1.0)
            alloc = grid[safe] * adm[:, None]
            # the identical first-principles report as
            # _decisions/check_solution
            lat = lat_mod.latency(lat_params, bits, rate, gpu_t, z, alloc)
            acc = model.accuracy(app_idx, z)
            cols = DecisionColumns(reqs, rid, tt, adm, z, alloc, lat, acc,
                                   self)
            for b, (lo, hi) in enumerate(zip(cut, cut[1:])):
                if lo < hi:
                    out[b] = CellDecisions(cols, lo, hi, b, names[b])
            return out

        return unpack

    def _decisions(self, requests, inst, sol,
                   cell: int | None = None) -> list[SliceDecision]:
        report = check_solution(inst, sol, lat_params=self.sdla.lat_params)
        out = []
        for i, r in enumerate(requests):
            alloc = {n: float(sol.alloc[i, k])
                     for k, n in enumerate(inst.pool.names)}
            out.append(SliceDecision(
                request=r,
                admitted=bool(sol.admitted[i]),
                z=float(sol.z[i]),
                alloc=alloc,
                expected_latency_s=float(report["latency"][i]),
                expected_accuracy=float(report["accuracy"][i]),
                cell=cell,
            ))
        return out
