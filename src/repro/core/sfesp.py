"""SF-ESP instance construction + feasibility/objective checking.

Builds the fully discretized :class:`~repro.core.types.ProblemInstance` from a
resource pool and a task set, by (i) solving Eq. (2) for z*_τ on both the
semantic and the agnostic accuracy curve, and (ii) tabulating l_τ(z*, s) over
the enumerated allocation grid. Also hosts the shared solution validator used
by every solver, the property tests, and the serving admission controller.

The second half of this module is the STACKING CACHE the batched engines run
on — three layers, each reusing the one below (lifecycle diagram in
``docs/ARCHITECTURE.md``):

1. **Host stack** — :func:`stack_instances` pads a batch into shared
   ``(B, Tmax, A)`` buffers (optionally group-major for the sharded solve);
   :func:`restack` refills them in place when only tasks/capacities change.
2. **Device half** — :func:`device_stack` memoizes the uploaded solver
   inputs ON the stacked batch; :func:`empty_device_stack` +
   :meth:`DeviceStack.update_rows` give the serving loop a delta-scatter
   path that re-uploads only dirty task rows.
3. **Sharded half** — :func:`device_stack_sharded` lays a group-major batch
   out across a device mesh (one contiguous block of coupling groups per
   shard) for ``greedy.solve_greedy_sharded``.

Cache keys and invalidation triggers are documented on the "Device half"
section banner below.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import latency as lat_mod
from . import semantics
from .types import (CouplingSpec, ProblemInstance, ResourcePool, Solution,
                    StackedInstances, TaskSet, UnionGrid,
                    make_allocation_grid, union_allocation_grid)

__all__ = ["build_instance", "check_solution", "objective_value",
           "default_z_grid", "stack_instances", "restack", "next_pow2",
           "task_link_load", "merge_coupling", "lexicographic_cost",
           "group_major_order", "group_offsets_of",
           "TaskRows", "task_feasibility_rows",
           "DeviceStack", "device_stack", "empty_device_stack", "stack_grid",
           "split_f32",
           "ShardedStack", "shard_plan", "device_stack_sharded",
           "empty_sharded_stack"]


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (and >= 1) — the sweep engine's padding
    buckets: padding Tmax/B to buckets means fluctuating trace sizes hit a
    handful of cached device programs instead of recompiling per shape."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def default_z_grid(n: int = 64) -> np.ndarray:
    """Log-spaced compression factors in (0.02, 1] — covers the paper's range
    (Fig. 7 picks factors down to 0.04)."""
    return np.geomspace(0.02, 1.0, n)


def lexicographic_cost(grid, xp=np):
    """MinRes-* allocation preference: minimize the LAST resource type first
    (compute), then the previous, ... matching the paper's observed behaviour
    (Fig. 7(e): MinRes-SEM requests 8 RBG + 1 GPU where SEM-O-RAN picks
    6 RBG + 5 GPU — compute is treated as the precious resource and radio
    compensates). Encoded as Σ_k s_k · W^k with a large base W."""
    grid = xp.asarray(grid)
    m = grid.shape[-1]
    weights = xp.asarray([float(1000 ** k) for k in range(m)])
    return (grid * weights).sum(axis=-1)


@dataclasses.dataclass(frozen=True)
class TaskRows:
    """Output of :func:`task_feasibility_rows` — everything the per-task
    pipeline derives from the accuracy curves, for one solver mode."""

    z_idx: np.ndarray    # (T,) int — Eq. (2) z* index into z_grid, -1 pruned
    z_star: np.ndarray   # (T,) — z_grid[z_idx] (1.0 where pruned)
    lat: np.ndarray      # (T, A) — l_τ(z*, s_a) over the allocation grid
    lat_ok: np.ndarray   # (T, A) bool — meets L_c at that allocation
    alive: np.ndarray    # (T,) bool — Alg. 1 line-7 candidate filter
    load: np.ndarray     # (T,) — shared-link load b_τ·λ_τ·z*_τ


def _latency_rows(lat_params: lat_mod.LatencyParams, tasks: TaskSet,
                  z: np.ndarray, grid: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(T, A) latency table and its deadline test, each distinct task row
    evaluated once: a row depends only on (b, λ, P₁, z*, L), and requests of
    one application repeat them, so a tick re-admitting a thousand requests
    of ten applications evaluates ten rows. The function is elementwise, so
    a row is the same bits whichever rows are evaluated with it."""
    key = np.stack([tasks.bits_per_job, tasks.jobs_per_sec,
                    tasks.gpu_time_per_job, z, tasks.max_latency], axis=1)
    uniq, inv = np.unique(key, axis=0, return_inverse=True)
    if len(uniq) == len(key):
        lat = lat_mod.latency_table(lat_params, tasks, z, grid)
        return lat, lat <= tasks.max_latency[:, None]
    lat = lat_mod.latency(lat_params, uniq[:, 0, None], uniq[:, 1, None],
                          uniq[:, 2, None], uniq[:, 3, None], grid[None, :, :])
    inv = inv.reshape(-1)
    return lat[inv], (lat <= uniq[:, 4, None])[inv]


def task_feasibility_rows(tasks: TaskSet, z_grid: np.ndarray,
                          grid: np.ndarray,
                          lat_params: lat_mod.LatencyParams | None = None, *,
                          semantic: bool = True,
                          model=None) -> TaskRows:
    """Eq. (2) → latency table → candidate feasibility, per task.

    THE single implementation of the min-z pipeline: instance construction
    (:func:`build_instance`) and the serving delta path
    (``serving.admission.SESM._sync_rows``) both call it, so a drifted
    :class:`~repro.core.semantics.SemanticModel` produces identical rows
    whether a stack is rebuilt from scratch or delta-scattered in place.
    ``semantic=False`` evaluates Eq. (2) on the service-wide 'All' fallback
    curve (``model.agnostic_app``) instead of each task's own.
    """
    model = semantics.resolve(model)
    lat_params = lat_params or lat_mod.LatencyParams()
    app = tasks.app_idx if semantic else model.agnostic_app(tasks.app_idx)
    z_idx = model.min_z_for_accuracy(app, tasks.min_accuracy, z_grid)
    # pruned tasks get z=1 rows; they are excluded by z_idx == -1 anyway
    z = _z_star_of(z_grid, z_idx)
    lat, lat_ok = _latency_rows(lat_params, tasks, z, grid)
    alive = (z_idx >= 0) & lat_ok.any(axis=1)
    load = tasks.bits_per_job * tasks.jobs_per_sec * z
    return TaskRows(z_idx=z_idx, z_star=z, lat=lat, lat_ok=lat_ok,
                    alive=alive, load=load)


def build_instance(pool: ResourcePool, tasks: TaskSet,
                   lat_params: lat_mod.LatencyParams | None = None,
                   z_grid: np.ndarray | None = None,
                   coupling: CouplingSpec | None = None,
                   model=None) -> ProblemInstance:
    model = semantics.resolve(model)
    lat_params = lat_params or lat_mod.LatencyParams()
    z_grid = default_z_grid() if z_grid is None else np.asarray(z_grid)
    grid = make_allocation_grid(pool.levels)

    acc = model.accuracy_table(tasks.app_idx, z_grid)
    acc_agn = model.accuracy_table(model.agnostic_app(tasks.app_idx), z_grid)

    sem = task_feasibility_rows(tasks, z_grid, grid, lat_params,
                                semantic=True, model=model)
    agn = task_feasibility_rows(tasks, z_grid, grid, lat_params,
                                semantic=False, model=model)

    return ProblemInstance(
        pool=pool, tasks=tasks, z_grid=z_grid,
        acc=acc, acc_agnostic=acc_agn, grid=grid,
        lat=sem.lat, lat_agnostic=agn.lat,
        z_star_idx=sem.z_idx, z_star_idx_agnostic=agn.z_idx,
        coupling=coupling, semantics=model,
    )


def task_link_load(inst: ProblemInstance, *, semantic: bool = True
                   ) -> np.ndarray:
    """Per-task shared-link load ``b_τ · λ_τ · z*_τ`` (Mbit/s) → (T,).

    The network traffic an admitted task puts on every shared link its cell
    traverses — the quantity SEM-O-RAN's semantic compression shrinks, and the
    quantity a :class:`~repro.core.types.CouplingSpec` budgets.
    """
    z_idx = inst.z_star_idx if semantic else inst.z_star_idx_agnostic
    z = _z_star_of(inst.z_grid, z_idx)
    return inst.tasks.bits_per_job * inst.tasks.jobs_per_sec * z


def merge_coupling(insts: Sequence[ProblemInstance]) -> CouplingSpec | None:
    """Merge per-instance single-cell coupling rows into one (B, L) spec.

    Every coupled instance must reference the SAME shared link set — the
    identical ``link_capacity`` array OBJECT (build all per-cell rows from
    one spec / one capacity array, as ``CouplingSpec.row`` and the scenario
    generators do). Identity rather than value equality is deliberate: two
    logically independent deployments can carry equal budget vectors, and
    merging them by value would silently charge both against one budget.
    Instances without a spec become all-zero (uncoupled) rows. Returns
    ``None`` when no instance is coupled.
    """
    specs = [inst.coupling for inst in insts]
    ref = next((s for s in specs if s is not None), None)
    if ref is None:
        return None
    inc = np.zeros((len(insts), ref.num_links), bool)
    for b, spec in enumerate(specs):
        if spec is None:
            continue
        if spec.incidence.shape != (1, ref.num_links) or \
                spec.link_capacity is not ref.link_capacity or \
                spec.names != ref.names:
            raise ValueError(
                "all coupled instances in a batch must reference one shared "
                "link set (the same link_capacity array object, single-row "
                "incidence) — build per-cell rows from one CouplingSpec")
        inc[b] = spec.incidence[0]
    return CouplingSpec(ref.link_capacity, inc, ref.names)


def group_major_order(insts: Sequence[ProblemInstance]) -> np.ndarray:
    """Permutation putting every coupling group's instances contiguous.

    The stable sort by group id (``CouplingSpec.groups`` on the merged batch
    spec): instances of one connected component become a contiguous span of
    the batch axis while their RELATIVE order — the cell-major order the
    coupled round's first-cell tie-break scans — is preserved, so solving
    the permuted batch yields bit-identical per-instance decisions.
    Uncoupled instances are singleton groups keyed by their own index.
    """
    insts = tuple(insts)
    coupling = merge_coupling(insts)
    if coupling is None:
        return np.arange(len(insts), dtype=np.int64)
    return np.argsort(coupling.groups(), kind="stable").astype(np.int64)


def group_offsets_of(coupling: CouplingSpec | None,
                     batch_size: int) -> np.ndarray:
    """Span boundaries (G+1,) of a GROUP-MAJOR batch's coupling groups.

    Requires the batch to already be in group-major order (each connected
    component contiguous — e.g. after :func:`group_major_order`); raises
    otherwise, because silently returning spans of an interleaved batch
    would let a sharded solve split a coupling group across devices.
    """
    if coupling is None:
        return np.arange(batch_size + 1, dtype=np.int64)
    gid = coupling.groups()
    changed = np.r_[True, gid[1:] != gid[:-1]]
    starts = np.flatnonzero(changed)
    if len(np.unique(gid)) != len(starts):
        raise ValueError(
            "batch is not group-major: a coupling group occupies "
            "non-contiguous rows; permute via group_major_order first")
    return np.r_[starts, batch_size].astype(np.int64)


def stack_grid(insts: Sequence[ProblemInstance]) -> UnionGrid:
    """The grid a batch stacks onto: the instances' shared grid, or the
    union of their grids with each row's own allocations masked."""
    grid = insts[0].grid
    if all(np.array_equal(inst.grid, grid) for inst in insts[1:]):
        return UnionGrid(grid, None)
    return union_allocation_grid([inst.pool for inst in insts])


def _on_grid(table: np.ndarray, mask: np.ndarray | None,
            fill=np.inf) -> np.ndarray:
    """``table`` (T, A_own), one column per allocation of a cell's own grid,
    laid onto the union grid (T, A) of ``mask``, ``fill`` elsewhere; the
    table itself where the cell is on the shared grid (``mask`` None)."""
    if mask is None:
        return table
    out = np.full((table.shape[0], mask.shape[0]), fill, table.dtype)
    out[:, mask] = table
    return out


def _shared_model(insts: Sequence[ProblemInstance], what: str):
    """The one SemanticModel of a batch (identity check, None = default).

    Mixing models in one stack would bake rows from different curve truths
    into one device program — a build error, not something to merge.
    """
    ref = semantics.resolve(insts[0].semantics)
    for inst in insts[1:]:
        if semantics.resolve(inst.semantics) is not ref:
            raise ValueError(
                f"all {what} instances must share one SemanticModel object; "
                "build every cell's instance from the same model")
    return ref


def _z_star_of(z_grid: np.ndarray, z_idx: np.ndarray) -> np.ndarray:
    return np.where(z_idx >= 0, z_grid[np.clip(z_idx, 0, None)], 1.0)


def _fill_stacked(st: StackedInstances, insts: tuple[ProblemInstance, ...],
                  n_tasks: np.ndarray):
    """Vectorized scatter of per-instance fields into the padded buffers.

    One concatenate + one fancy-index store per field instead of a B-fold
    Python copy loop — the stacking cost is dominated by the two (ΣT, A)
    latency-table writes, which run at memcpy speed.
    """
    B = len(insts)
    total = int(n_tasks.sum())
    rows = np.repeat(np.arange(B), n_tasks)
    starts = np.concatenate([[0], np.cumsum(n_tasks)[:-1]]).astype(np.int64)
    cols = np.arange(total) - np.repeat(starts, n_tasks)

    def cat(get):
        return np.concatenate([np.asarray(get(i)) for i in insts], axis=0)

    masks = [None] * B if st.cell_mask is None else st.cell_mask
    # rows of a union grid: +inf (never within L_c) off the cell's own grid
    st.lat[rows, cols] = np.concatenate(
        [_on_grid(i.lat, k) for i, k in zip(insts, masks)], axis=0)
    st.lat_agnostic[rows, cols] = np.concatenate(
        [_on_grid(i.lat_agnostic, k) for i, k in zip(insts, masks)], axis=0)
    st.z_star_idx[rows, cols] = cat(lambda i: i.z_star_idx)
    st.z_star_idx_agnostic[rows, cols] = cat(lambda i: i.z_star_idx_agnostic)
    st.z_star[rows, cols] = cat(lambda i: _z_star_of(i.z_grid, i.z_star_idx))
    st.z_star_agnostic[rows, cols] = cat(
        lambda i: _z_star_of(i.z_grid, i.z_star_idx_agnostic))
    st.app_idx[rows, cols] = cat(lambda i: i.tasks.app_idx)
    st.min_accuracy[rows, cols] = cat(lambda i: i.tasks.min_accuracy)
    st.max_latency[rows, cols] = cat(lambda i: i.tasks.max_latency)
    if st.coupling is not None:
        # only coupled batches read the load tables; skipping them keeps the
        # uncoupled restack hot path free of two per-instance passes
        st.link_load[rows, cols] = cat(lambda i: task_link_load(i))
        st.link_load_agnostic[rows, cols] = cat(
            lambda i: task_link_load(i, semantic=False))
    st.task_mask[rows, cols] = True
    st.capacity[:] = [i.pool.capacity for i in insts]
    st.price[:] = [i.pool.price for i in insts]


def stack_instances(insts: Sequence[ProblemInstance], *,
                    tmax: int | None = None,
                    group_major: bool = False) -> StackedInstances:
    """Stack instances into one padded batch for the sweep engine.

    Instances on one allocation grid (identical ``pool.levels``) stack onto
    it; instances on different grids (same resources) stack onto their
    union grid, each row masked to its own allocations (``cell_mask``);
    capacities/prices may differ per instance (multi-cell pools). Tasks are
    padded to ``Tmax`` with never-feasible rows (lat=+inf, z*_idx=-1) so the
    batched solver's masked rounds ignore them. ``tmax`` overrides the
    natural padding target (must be >= the largest task count) — the grouped
    dispatcher passes power-of-two buckets so repeated sweeps share device
    programs.

    ``group_major=True`` permutes the instances so every coupling group is a
    contiguous span of the batch axis (the sharded solve's layout; see
    :class:`~repro.core.types.StackedInstances`), recording ``perm`` (stacked
    row → input index) and ``group_offsets`` on the result. Per-instance
    decisions are unaffected — the stable permutation preserves each group's
    internal cell order, hence the coupled tie-breaks.
    """
    insts = tuple(insts)
    if not insts:
        raise ValueError("stack_instances needs at least one instance")
    perm = None
    if group_major:
        perm = group_major_order(insts)
        insts = tuple(insts[i] for i in perm)
    union = stack_grid(insts)
    grid = union.grid
    B = len(insts)
    A, m = grid.shape
    n_tasks = np.array([inst.num_tasks for inst in insts], np.int64)
    natural = max(1, int(n_tasks.max()))
    tmax = natural if tmax is None else int(tmax)
    if tmax < natural:
        raise ValueError(f"tmax={tmax} < largest task count {natural}")

    st = StackedInstances(
        instances=insts, grid=grid,
        capacity=np.zeros((B, m)), price=np.zeros((B, m)),
        lat=np.full((B, tmax, A), np.inf),
        lat_agnostic=np.full((B, tmax, A), np.inf),
        z_star_idx=np.full((B, tmax), -1, np.int64),
        z_star_idx_agnostic=np.full((B, tmax), -1, np.int64),
        z_star=np.ones((B, tmax)), z_star_agnostic=np.ones((B, tmax)),
        app_idx=np.zeros((B, tmax), np.int64),
        min_accuracy=np.full((B, tmax), np.inf),
        max_latency=np.zeros((B, tmax)),
        task_mask=np.zeros((B, tmax), bool), num_tasks=n_tasks,
        link_load=np.zeros((B, tmax)),
        link_load_agnostic=np.zeros((B, tmax)),
        coupling=merge_coupling(insts),
        semantics=_shared_model(insts, "stacked"),
        cell_mask=union.mask,
    )
    if group_major:
        st = dataclasses.replace(
            st, perm=perm, group_offsets=group_offsets_of(st.coupling, B))
    _fill_stacked(st, insts, n_tasks)
    return st


def restack(stacked: StackedInstances,
            insts: Sequence[ProblemInstance]) -> StackedInstances:
    """Refill a stacked batch with new instances, REUSING the padded buffers.

    The closed-loop trace case: every step re-solves an admission problem
    whose grid and batch size are fixed while tasks and capacities change;
    reallocating the (B, Tmax, A) latency tables each step dominates the
    host-side cost. Contract: same (union) allocation grid, same batch
    size, and every new instance's task count must fit the existing ``Tmax``
    (otherwise a ValueError asks the caller to re-stack at a larger bucket).

    The returned :class:`StackedInstances` SHARES the buffers of ``stacked``,
    which must not be used afterwards. A group-major batch stays group-major:
    the new instances are re-permuted against their OWN coupling topology
    (which may differ from the old batch's), and ``perm``/``group_offsets``
    are refreshed accordingly.
    """
    insts = tuple(insts)
    if len(insts) != stacked.batch_size:
        raise ValueError(
            f"restack needs the original batch size {stacked.batch_size}, "
            f"got {len(insts)} instances; re-stack instead")
    perm = None
    if stacked.group_major:
        perm = group_major_order(insts)
        insts = tuple(insts[i] for i in perm)
    union = stack_grid(insts)
    if not np.array_equal(union.grid, stacked.grid):
        raise ValueError(
            "restacked instances must stack onto the batch's allocation "
            "grid; re-stack instead")
    n_tasks = np.array([inst.num_tasks for inst in insts], np.int64)
    if n_tasks.max(initial=0) > stacked.max_tasks:
        raise ValueError(
            f"instance with {int(n_tasks.max())} tasks does not fit the "
            f"stacked Tmax={stacked.max_tasks}; re-stack at a larger bucket")

    # reset padding values, then vectorized refill
    stacked.lat.fill(np.inf)
    stacked.lat_agnostic.fill(np.inf)
    stacked.z_star_idx.fill(-1)
    stacked.z_star_idx_agnostic.fill(-1)
    stacked.z_star.fill(1.0)
    stacked.z_star_agnostic.fill(1.0)
    stacked.app_idx.fill(0)
    stacked.min_accuracy.fill(np.inf)
    stacked.max_latency.fill(0.0)
    stacked.task_mask.fill(False)
    stacked.link_load.fill(0.0)
    stacked.link_load_agnostic.fill(0.0)
    coupling = merge_coupling(insts)
    st = dataclasses.replace(
        stacked, instances=insts, num_tasks=n_tasks, coupling=coupling,
        perm=perm,
        group_offsets=(group_offsets_of(coupling, len(insts))
                       if stacked.group_major else None),
        semantics=_shared_model(insts, "restacked"), cell_mask=union.mask)
    _fill_stacked(st, insts, n_tasks)
    return st


# ---------------------------------------------------------------------------
# Device half of the stacking cache
#
# Contracts at a glance (the serving fast path and the sharded solve both
# build on these; tests/test_device_stack.py pins them):
#
# * CACHE KEYS — ``device_stack`` memoizes per stacked-batch OBJECT, keyed by
#   ``(semantic, pad_batch_to, semantic_signature)``; ``device_stack_sharded``
#   likewise, keyed by ``(mesh, axis, semantic, semantic_signature)``. The
#   ``semantic_signature`` component is the batch's SemanticModel
#   ``(uid, version)``: a model drifted IN PLACE after an upload reads as a
#   new key, so a stale device half can never be reused silently (the serving
#   session avoids the re-upload entirely by delta-scattering the drifted
#   rows — ``DeviceStack.update_semantics``). A cache entry lives exactly as
#   long as the stacked batch object does.
# * INVALIDATION / REBUILD TRIGGERS — ``restack`` returns a NEW
#   StackedInstances (fresh, empty caches), so any in-place refill
#   invalidates the device halves by construction; mutating a stacked
#   batch's buffers after its first solve is undefined. A
#   ``DeviceStack.update_rows`` call whose slot index exceeds the Tmax
#   bucket raises — the caller must rebuild at a larger bucket (the serving
#   session does; see ``serving.admission._ServeSession`` for the
#   session-level triggers: batch size, algorithm, coupling/pools identity,
#   latency-scale change).
# * DIRTY-BIT ACCUMULATION — delta consumers (``CellRuntime.sync_slots`` →
#   ``SESM.solve_slots``) accumulate dirty slots until a LIVE solve consumes
#   them; a skipped tick must carry its deltas forward. ``update_rows``
#   itself is stateless per call: it scatters exactly the rows it is given,
#   pow2-bucketed, with out-of-bucket padding indices dropped on device.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _scatter_rows(lat_ok, alive0, link_load, b_idx, t_idx,
                  lat_rows, alive_rows, load_rows):
    """Scatter dirty task rows into the donated device buffers.

    ``t_idx`` entries >= Tmax are padding (the dirty count is bucketed to a
    power of two so fluctuating delta sizes hit a handful of compiled
    scatters); ``mode="drop"`` discards them.
    """
    lat_ok = lat_ok.at[b_idx, t_idx].set(lat_rows, mode="drop")
    alive0 = alive0.at[b_idx, t_idx].set(alive_rows, mode="drop")
    link_load = link_load.at[b_idx, t_idx].set(load_rows, mode="drop")
    return lat_ok, alive0, link_load


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_budgets(link_cap, new_cap):
    """Overwrite the (L, 2) device link-budget buffer in place (donated)."""
    return link_cap.at[:].set(new_cap)


def split_f32(x) -> np.ndarray:
    """``x`` as float32 pairs (..., 2): ``hi`` the float32 nearest ``x``,
    ``lo`` the float32 nearest what ``hi`` leaves out (0 where ``x`` is not
    finite). The device's link accounting adds and compares such pairs
    (``greedy._batch_solve_coupled``), so a link budget binds where it would
    in float64: a plain float32 sum of a few dozen loads errs by more than
    the margin by which a load can miss its link's remaining budget."""
    x = np.asarray(x, np.float64)
    hi = x.astype(np.float32)
    lo = np.zeros_like(hi)
    fin = np.isfinite(x)
    lo[fin] = (x[fin] - hi[fin].astype(np.float64)).astype(np.float32)
    return np.stack([hi, lo], axis=-1)


@dataclasses.dataclass
class DeviceStack:
    """Device-resident half of a stacked batch, for ONE solver mode.

    Holds everything the batched greedy device program consumes as jax
    arrays, so repeated solves of the same batch re-upload nothing and a
    serving loop can *delta-update* only the task rows that changed since the
    previous tick (:meth:`update_rows`) instead of refilling and re-uploading
    the full (B, Tmax, A) host tables. Invariant tables — the allocation
    grid, the MinRes lexicographic cost, per-cell prices/capacities and the
    coupling topology — are uploaded once at construction.

    Build from a host :class:`StackedInstances` via :func:`device_stack`
    (memoized per batch + mode) or as cleared padding rows via
    :func:`empty_device_stack` (the serving fast path, which then scatters
    live rows in). ``rows_scattered``/``scatter_calls`` count delta traffic.
    """

    grid: jax.Array                  # (A, m)
    cost: jax.Array                  # (A,) lexicographic MinRes cost
    price: jax.Array                 # (B', m)
    capacity: jax.Array              # (B', m)
    lat_ok: jax.Array                # (B', Tmax, A) bool
    alive0: jax.Array                # (B', Tmax) bool
    link_load: jax.Array             # (B', Tmax, 2) f32 pairs (split_f32),
    # zeros when uncoupled
    link_cap: jax.Array | None       # (L, 2) f32 pairs
    incidence: jax.Array | None      # (B', L) bool
    group: jax.Array | None          # (B',) int
    semantic: bool
    batch_size: int                  # real B (B' may include inert padding)
    grids: int = 1                   # distinct cell grids on ``grid``
    scatter_calls: int = 0
    rows_scattered: int = 0
    budget_updates: int = 0
    semantic_updates: int = 0        # update_semantics calls (drift traffic)
    semantic_rows: int = 0           # rows re-scattered because curves moved

    @property
    def coupled(self) -> bool:
        return self.link_cap is not None

    @property
    def max_tasks(self) -> int:
        return self.lat_ok.shape[1]

    def inputs(self) -> tuple:
        """Capture the solver's input bindings — the DOUBLE-BUFFER hand-off.

        A dispatched-but-unawaited solve must read tick N's tables even if
        the serving loop starts preparing tick N+1 while it is in flight.
        The mutable buffers here (``lat_ok``/``alive0``/``link_load``/
        ``link_cap``) are replaced — not written through — by the donated
        scatters of :meth:`update_rows` / :meth:`update_link_budgets`: the
        scatter output becomes the NEW front buffer bound on ``self``, while
        any solve dispatched from a previous capture keeps the old arrays
        alive as its back buffer (XLA copies instead of aliasing a donated
        buffer that still has a pending consumer). So an async dispatcher
        takes this snapshot once at launch and never re-reads ``self``.
        """
        return (self.lat_ok, self.grid, self.price, self.capacity,
                self.alive0, self.cost, self.link_load, self.link_cap,
                self.incidence, self.group)

    def update_rows(self, b_idx, t_idx, lat_ok_rows, alive_rows,
                    load_rows=None):
        """Delta-scatter changed task rows into the device buffers.

        ``b_idx``/``t_idx`` (D,) address the rows; ``lat_ok_rows`` (D, A)
        bool, ``alive_rows`` (D,) bool, ``load_rows`` (D,) float64 (defaults
        to zeros for uncoupled batches; uploaded as :func:`split_f32`
        pairs). Row counts are padded to a power-of-two
        bucket with out-of-range ``t_idx`` entries, which the jitted scatter
        drops — so arrival/departure bursts of any size reuse a handful of
        compiled programs. A ``t_idx`` >= ``max_tasks`` is a bucket overflow:
        the caller must rebuild at a larger Tmax (ValueError).
        """
        b_idx = np.asarray(b_idx, np.int32)
        t_idx = np.asarray(t_idx, np.int32)
        d = len(t_idx)
        if d == 0:
            return
        if t_idx.max(initial=0) >= self.max_tasks:
            raise ValueError(
                f"slot {int(t_idx.max())} does not fit the device bucket "
                f"Tmax={self.max_tasks}; rebuild the stack at a larger "
                "bucket")
        nrows = self.alive0.shape[0]
        if b_idx.max(initial=0) >= nrows or b_idx.min(initial=0) < 0:
            # without this check an off-range cell index would be silently
            # swallowed by the same mode="drop" that handles bucket padding
            raise ValueError(
                f"cell index {int(b_idx.max())} outside the stacked batch "
                f"of {nrows} rows")
        load_rows = split_f32(np.zeros(d) if load_rows is None else load_rows)
        bucket = next_pow2(d)
        pad = bucket - d
        if pad:
            b_idx = np.concatenate([b_idx, np.zeros(pad, np.int32)])
            # out-of-bounds task index → dropped by the scatter
            t_idx = np.concatenate(
                [t_idx, np.full(pad, self.max_tasks, np.int32)])
            lat_ok_rows = np.concatenate(
                [lat_ok_rows, np.zeros((pad,) + lat_ok_rows.shape[1:], bool)])
            alive_rows = np.concatenate([alive_rows, np.zeros(pad, bool)])
            load_rows = np.concatenate([load_rows,
                                        np.zeros((pad, 2), np.float32)])
        self.lat_ok, self.alive0, self.link_load = _scatter_rows(
            self.lat_ok, self.alive0, self.link_load,
            jnp.asarray(b_idx), jnp.asarray(t_idx),
            jnp.asarray(np.asarray(lat_ok_rows, bool)),
            jnp.asarray(np.asarray(alive_rows, bool)),
            jnp.asarray(load_rows))
        self.scatter_calls += 1
        self.rows_scattered += d

    def update_semantics(self, b_idx, t_idx, lat_ok_rows, alive_rows,
                         load_rows=None):
        """Drift half of the delta path: re-scatter the task rows whose
        Eq. (2) min-z / feasibility moved because the
        :class:`~repro.core.semantics.SemanticModel` was recalibrated.

        Identical scatter semantics to :meth:`update_rows` (same donated
        jitted program, pow2 bucketing, drop-padding) — the point of the
        separate entry is ACCOUNTING: ``semantic_updates``/``semantic_rows``
        make drift traffic observable apart from arrival/departure churn, so
        the bench gate can assert a drifted tick scattered only its dirty
        rows while ``session_rebuilds`` stayed 0 (the ``update_link_budgets``
        pattern applied to the accuracy curves).
        """
        d = len(np.asarray(t_idx))
        if d == 0:
            return
        self.update_rows(b_idx, t_idx, lat_ok_rows, alive_rows, load_rows)
        self.semantic_updates += 1
        self.semantic_rows += d

    def update_link_budgets(self, budgets):
        """Refresh the (L,) per-link budgets on device, in place.

        The budget-only half of link degradation: the link SET (incidence,
        coupling groups) is invariant, only the capacities move, so the
        device session survives with one tiny donated scatter — the
        :meth:`update_rows` pattern applied to the coupling budgets. The
        budgets are a traced input of the solve, so no recompile either.
        Changing the link set itself is a topology change and needs a
        rebuilt stack (ValueError here).
        """
        if not self.coupled:
            raise ValueError(
                "this stack is uncoupled (no link budgets to update); "
                "introducing links is a topology change — rebuild")
        new = np.asarray(budgets, np.float64)
        if new.shape != self.link_cap.shape[:1]:
            raise ValueError(
                f"budget shape {new.shape} != device link set "
                f"{self.link_cap.shape[:1]}; changing the link set is a "
                "topology change — rebuild the stack")
        self.link_cap = _scatter_budgets(self.link_cap,
                                         jnp.asarray(split_f32(new)))
        self.budget_updates += 1


def _solver_tables(stacked: StackedInstances, semantic: bool):
    """Host-side solver inputs of a stacked batch: (lat_ok, alive0, load)."""
    if semantic:
        lat, z_idx = stacked.lat, stacked.z_star_idx
        load = stacked.link_load
    else:
        lat, z_idx = stacked.lat_agnostic, stacked.z_star_idx_agnostic
        load = stacked.link_load_agnostic
    lat_ok = lat <= stacked.max_latency[:, :, None]       # padded rows: False
    alive0 = (z_idx >= 0) & lat_ok.any(axis=2) & stacked.task_mask
    return lat_ok, alive0, load


def device_stack(stacked: StackedInstances, *, semantic: bool = True,
                 pad_batch_to: int | None = None) -> DeviceStack:
    """The memoized device half of ``stacked`` for one solver mode.

    Uploads the solver inputs once and caches the result ON the stacked batch
    (keyed by ``(semantic, pad_batch_to)``), so repeated
    ``solve_greedy_batch`` calls on the same batch dispatch straight from
    device memory instead of re-running ``jnp.asarray`` on every (B, Tmax, A)
    table per call. Contract: the stacked buffers must not be mutated after
    the first solve — :func:`restack` honors this by returning a NEW
    :class:`StackedInstances` (fresh cache) and invalidating the old one.
    The device copies live exactly as long as the stacked batch object does
    (one entry per mode/bucket solved): drop the batch to release them —
    callers that retain many solved batches retain their device halves too.

    ``pad_batch_to`` pads the device batch with inert instances (never-alive,
    unit capacity) exactly as the grouped dispatcher's pow2 buckets expect.
    """
    cache = stacked.__dict__.get("_device_half")
    if cache is None:
        cache = {}
        object.__setattr__(stacked, "_device_half", cache)
    key = (bool(semantic), pad_batch_to, stacked.semantic_signature)
    if key in cache:
        return cache[key]

    lat_ok, alive0, load = _solver_tables(stacked, semantic)
    price, cap = stacked.price, stacked.capacity
    coupling = stacked.coupling
    coupled = coupling is not None and bool(coupling.incidence.any())
    inc = coupling.incidence if coupled else None
    B = stacked.batch_size
    if pad_batch_to is not None and pad_batch_to > B:
        pad = pad_batch_to - B
        m = stacked.m
        lat_ok = np.concatenate(
            [lat_ok, np.zeros((pad,) + lat_ok.shape[1:], bool)])
        alive0 = np.concatenate(
            [alive0, np.zeros((pad, alive0.shape[1]), bool)])
        # unit capacity keeps the in-kernel gradient NaN-free; the padded
        # instances start with no alive candidates, so they never admit
        price = np.concatenate([price, np.zeros((pad, m))])
        cap = np.concatenate([cap, np.ones((pad, m))])
        if coupled:
            # link-free padded cells: singleton groups that never admit
            load = np.concatenate([load, np.zeros((pad, load.shape[1]))])
            inc = np.concatenate([inc, np.zeros((pad, inc.shape[1]), bool)])
    if coupled:
        group = CouplingSpec(coupling.link_capacity, inc).groups()
        link = (jnp.asarray(split_f32(coupling.link_capacity)),
                jnp.asarray(inc), jnp.asarray(group))
    else:
        link = (None, None, None)
    dev = DeviceStack(
        grid=jnp.asarray(stacked.grid),
        cost=jnp.asarray(lexicographic_cost(stacked.grid)),
        price=jnp.asarray(price), capacity=jnp.asarray(cap),
        lat_ok=jnp.asarray(lat_ok), alive0=jnp.asarray(alive0),
        link_load=jnp.asarray(split_f32(load)),
        link_cap=link[0], incidence=link[1], group=link[2],
        semantic=bool(semantic), batch_size=B, grids=stacked.grids,
    )
    cache[key] = dev
    return dev


def empty_device_stack(grid: np.ndarray, price: np.ndarray,
                       capacity: np.ndarray, tmax: int, *,
                       coupling: CouplingSpec | None = None,
                       semantic: bool = True, grids: int = 1) -> DeviceStack:
    """A device stack of CLEARED rows (never feasible, never alive).

    The serving fast path allocates one per (batch, Tmax-bucket) and scatters
    live task rows in as they arrive/change (:meth:`DeviceStack.update_rows`);
    cells' prices/capacities (B, m) and the coupling topology are the
    invariants uploaded here, once. ``grid`` may be the union of ``grids``
    cell grids: the caller masks each row it scatters to its cell's own.
    """
    price = np.asarray(price)
    B, A = price.shape[0], grid.shape[0]
    coupled = coupling is not None and bool(coupling.incidence.any())
    if coupled:
        if coupling.num_cells != B:
            raise ValueError(
                f"coupling.incidence has {coupling.num_cells} rows for "
                f"{B} cells")
        link = (jnp.asarray(split_f32(coupling.link_capacity)),
                jnp.asarray(coupling.incidence),
                jnp.asarray(coupling.groups()))
    else:
        link = (None, None, None)
    return DeviceStack(
        grid=jnp.asarray(grid),
        cost=jnp.asarray(lexicographic_cost(grid)),
        price=jnp.asarray(price), capacity=jnp.asarray(capacity),
        lat_ok=jnp.zeros((B, tmax, A), bool),
        alive0=jnp.zeros((B, tmax), bool),
        link_load=jnp.zeros((B, tmax, 2), jnp.float32),
        link_cap=link[0], incidence=link[1], group=link[2],
        semantic=bool(semantic), batch_size=B, grids=grids,
    )


# --------------------------------------------------------------- sharded half

@dataclasses.dataclass
class ShardedStack:
    """Group-major device half laid out across a 1-D device mesh.

    The metro-scale layout: the batch axis is split into ``num_shards``
    equal blocks of ``shard_rows`` rows, every coupling group lives WHOLLY
    inside one block (``shard_plan``), and each per-cell table is placed
    with a ``NamedSharding`` that puts block ``s`` on mesh device ``s``.
    ``greedy.solve_greedy_sharded`` then runs the unmodified coupled batch
    core per shard under ``shard_map`` — no collective appears in the round,
    so every shard's admission ``while_loop`` converges independently (a
    congested group never serializes the fleet).

    ``group`` holds shard-LOCAL group ids (each group's local span start),
    ``row_of`` maps every padded row back to its stacked-batch row (``-1``
    marks inert balance padding: never-alive, unit-capacity, link-free).
    Built/memoized per stacked batch via :func:`device_stack_sharded`.
    """

    mesh: object                     # jax.sharding.Mesh
    axis: str                        # mesh axis the batch is split over
    grid: jax.Array                  # (A, m) replicated
    cost: jax.Array                  # (A,) replicated
    price: jax.Array                 # (B', m) sharded
    capacity: jax.Array              # (B', m) sharded
    lat_ok: jax.Array                # (B', Tmax, A) sharded
    alive0: jax.Array                # (B', Tmax) sharded
    link_load: jax.Array             # (B', Tmax, 2) f32 pairs, sharded
    link_cap: jax.Array              # (L, 2) f32 pairs, replicated
    incidence: jax.Array             # (B', L) sharded
    group: jax.Array                 # (B',) shard-local group ids
    row_of: np.ndarray               # (B',) stacked row per padded row, -1 pad
    batch_size: int                  # real B
    shard_rows: int                  # rows per shard (B' / num_shards)
    groups_per_shard: np.ndarray     # (num_shards,) assigned group counts
    padded_of: np.ndarray            # (B,) padded row per stacked row
    coupled: bool = True             # real links (vs the dummy inf link)
    grids: int = 1                   # distinct cell grids on ``grid``
    scatter_calls: int = 0
    rows_scattered: int = 0
    budget_updates: int = 0
    semantic_updates: int = 0        # update_semantics calls (drift traffic)
    semantic_rows: int = 0           # rows re-scattered because curves moved

    @property
    def num_shards(self) -> int:
        return len(self.groups_per_shard)

    @property
    def max_tasks(self) -> int:
        return self.lat_ok.shape[1]

    def inputs(self) -> tuple:
        """Capture the solver's input bindings — the DOUBLE-BUFFER hand-off.

        Same contract as :meth:`DeviceStack.inputs`: the donated scatters of
        :meth:`update_rows` / :meth:`update_link_budgets` REBIND the mutable
        tables on ``self``, so a sharded solve dispatched from an earlier
        snapshot keeps reading the old (back) buffers while the serving loop
        scatters tick N+1's deltas into the new front buffers.
        """
        return (self.lat_ok, self.grid, self.price, self.capacity,
                self.alive0, self.cost, self.link_load, self.link_cap,
                self.incidence, self.group)

    def update_rows(self, b_idx, t_idx, lat_ok_rows, alive_rows,
                    load_rows=None):
        """Delta-scatter changed task rows into the SHARDED device buffers.

        Identical surface to :meth:`DeviceStack.update_rows` — ``b_idx``
        addresses STACKED (input-order) rows; the scatter routes each one to
        its (shard, local_row) slot through ``padded_of``, the inverse of the
        group-major ``shard_plan`` placement, so callers never see the padded
        layout. Same pow2 bucketing with ``mode="drop"`` padding, same
        bucket-overflow / off-range ValueErrors, same donated jitted program
        (compiled once more for the sharded layout and reused).
        """
        b_idx = np.asarray(b_idx, np.int64)
        t_idx = np.asarray(t_idx, np.int32)
        d = len(t_idx)
        if d == 0:
            return
        if t_idx.max(initial=0) >= self.max_tasks:
            raise ValueError(
                f"slot {int(t_idx.max())} does not fit the device bucket "
                f"Tmax={self.max_tasks}; rebuild the stack at a larger "
                "bucket")
        if b_idx.max(initial=0) >= self.batch_size or \
                b_idx.min(initial=0) < 0:
            raise ValueError(
                f"cell index {int(b_idx.max())} outside the stacked batch "
                f"of {self.batch_size} rows")
        # stacked row -> padded (shard-blocked) row, then the plain scatter
        p_idx = self.padded_of[b_idx].astype(np.int32)
        load_rows = split_f32(np.zeros(d) if load_rows is None else load_rows)
        bucket = next_pow2(d)
        pad = bucket - d
        if pad:
            p_idx = np.concatenate([p_idx, np.zeros(pad, np.int32)])
            t_idx = np.concatenate(
                [t_idx, np.full(pad, self.max_tasks, np.int32)])
            lat_ok_rows = np.concatenate(
                [lat_ok_rows, np.zeros((pad,) + lat_ok_rows.shape[1:], bool)])
            alive_rows = np.concatenate([alive_rows, np.zeros(pad, bool)])
            load_rows = np.concatenate([load_rows,
                                        np.zeros((pad, 2), np.float32)])
        self.lat_ok, self.alive0, self.link_load = _scatter_rows(
            self.lat_ok, self.alive0, self.link_load,
            jnp.asarray(p_idx), jnp.asarray(t_idx),
            jnp.asarray(np.asarray(lat_ok_rows, bool)),
            jnp.asarray(np.asarray(alive_rows, bool)),
            jnp.asarray(load_rows))
        self.scatter_calls += 1
        self.rows_scattered += d

    def update_semantics(self, b_idx, t_idx, lat_ok_rows, alive_rows,
                         load_rows=None):
        """Drift half of the sharded delta path — same scatter as
        :meth:`update_rows`, accounted separately (``semantic_updates`` /
        ``semantic_rows``) exactly like :meth:`DeviceStack.update_semantics`.
        """
        d = len(np.asarray(t_idx))
        if d == 0:
            return
        self.update_rows(b_idx, t_idx, lat_ok_rows, alive_rows, load_rows)
        self.semantic_updates += 1
        self.semantic_rows += d

    def update_link_budgets(self, budgets):
        """Refresh the replicated (L,) link budgets in place (donated).

        Budget-only degradation on a mesh-resident session: the link set and
        the shard plan are invariant (links live wholly inside one shard's
        groups), only capacities move — one tiny scatter, no replan.
        """
        if not self.coupled:
            raise ValueError(
                "this stack is uncoupled (no link budgets to update); "
                "introducing links is a topology change — rebuild")
        new = np.asarray(budgets, np.float64)
        if new.shape != self.link_cap.shape[:1]:
            raise ValueError(
                f"budget shape {new.shape} != device link set "
                f"{self.link_cap.shape[:1]}; changing the link set is a "
                "topology change — rebuild the stack")
        self.link_cap = _scatter_budgets(self.link_cap,
                                         jnp.asarray(split_f32(new)))
        self.budget_updates += 1


def shard_plan(group_offsets: np.ndarray,
               n_shards: int) -> tuple[list[list[int]], np.ndarray]:
    """Balanced groups→shards assignment: largest group first, into the
    currently least-loaded shard (LPT scheduling). Returns the per-shard
    group-index lists and the per-shard row loads; the device block size is
    ``loads.max()`` and lighter shards are padded with inert rows. Groups
    are never split — a coupling group is the atomic unit of parallelism.
    """
    sizes = np.diff(np.asarray(group_offsets, np.int64))
    shards: list[list[int]] = [[] for _ in range(n_shards)]
    loads = np.zeros(n_shards, np.int64)
    for g in np.argsort(-sizes, kind="stable"):
        s = int(np.argmin(loads))
        shards[s].append(int(g))
        loads[s] += int(sizes[g])
    return shards, loads


def _plan_layout(order: np.ndarray, offsets: np.ndarray, n_shards: int
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int,
                            np.ndarray]:
    """Materialize a :func:`shard_plan` as row maps.

    Returns ``(row_of, local_gid, padded_of, rows, groups_per_shard)``:
    ``row_of`` (B',) maps padded row → stacked row (-1 = inert balance
    padding), ``local_gid`` (B',) holds shard-LOCAL group ids, ``padded_of``
    (B,) is the inverse map stacked row → padded row — the address
    translation the sharded delta scatters route through.
    """
    shards, loads = shard_plan(offsets, n_shards)
    rows = max(1, int(loads.max()))
    bp = n_shards * rows
    row_of = np.full(bp, -1, np.int64)
    local_gid = np.zeros(bp, np.int64)
    for s, group_list in enumerate(shards):
        pos = s * rows
        for g in group_list:
            span = order[offsets[g]:offsets[g + 1]]
            n = len(span)
            row_of[pos:pos + n] = span
            local_gid[pos:pos + n] = pos - s * rows
            pos += n
        # inert padding rows: singleton groups that never admit
        local_gid[pos:(s + 1) * rows] = \
            np.arange(pos, (s + 1) * rows) - s * rows
    live = row_of >= 0
    padded_of = np.empty(len(order), np.int64)
    padded_of[row_of[live]] = np.flatnonzero(live)
    return row_of, local_gid, padded_of, rows, \
        np.array([len(g) for g in shards], np.int64)


def _group_major_view(stacked: StackedInstances
                      ) -> tuple[np.ndarray, np.ndarray]:
    """(order, offsets) presenting ``stacked`` in group-major order.

    Identity order when the batch already carries the layout (or is
    uncoupled); otherwise the stable group permutation is derived on the
    fly so plainly-stacked batches can still dispatch sharded.
    """
    B = stacked.batch_size
    if stacked.group_major:
        return np.arange(B, dtype=np.int64), \
            np.asarray(stacked.group_offsets, np.int64)
    coupling = stacked.coupling
    if coupling is None or not bool(coupling.incidence.any()):
        return np.arange(B, dtype=np.int64), np.arange(B + 1, dtype=np.int64)
    gid = coupling.groups()
    order = np.argsort(gid, kind="stable").astype(np.int64)
    gs = gid[order]
    starts = np.flatnonzero(np.r_[True, gs[1:] != gs[:-1]])
    return order, np.r_[starts, B].astype(np.int64)


def device_stack_sharded(stacked: StackedInstances, mesh, *,
                         semantic: bool = True,
                         axis: str = "cells") -> ShardedStack:
    """The memoized SHARDED device half of ``stacked`` for one solver mode.

    Same cache discipline as :func:`device_stack` (entry keyed by
    ``(mesh, axis, semantic)`` on the stacked batch object; ``restack``
    invalidates by returning a new object), but the batch axis is permuted
    group-major, balanced over ``mesh.shape[axis]`` blocks (``shard_plan``),
    padded with inert rows to a uniform block size, and uploaded with a
    block-cyclic ``NamedSharding`` so shard ``s`` of the solve reads only
    device ``s``'s rows. Uncoupled batches shard as singleton groups over a
    single dummy link of infinite budget (bit-identical admissions — an
    all-zero incidence row never constrains).
    """
    cache = stacked.__dict__.get("_sharded_half")
    if cache is None:
        cache = {}
        object.__setattr__(stacked, "_sharded_half", cache)
    key = (mesh, axis, bool(semantic), stacked.semantic_signature)
    if key in cache:
        return cache[key]

    order, offsets = _group_major_view(stacked)
    n_shards = int(mesh.shape[axis])
    row_of, local_gid, padded_of, rows, gps = \
        _plan_layout(order, offsets, n_shards)

    lat_ok, alive0, load = _solver_tables(stacked, semantic)
    coupling = stacked.coupling
    coupled = coupling is not None and bool(coupling.incidence.any())
    if coupled:
        link_cap = np.asarray(coupling.link_capacity, np.float64)
        inc = coupling.incidence
    else:
        # one dummy link nobody traverses keeps the coupled core's per-link
        # reductions well-shaped without constraining anything
        link_cap = np.array([np.inf])
        inc = np.zeros((stacked.batch_size, 1), bool)

    live = row_of >= 0
    src = np.clip(row_of, 0, None)

    def pad(table, fill):
        out = table[src].copy()
        out[~live] = fill
        return out

    from repro.distributed.sharding import named_sharding_for
    rules = {"cells": axis}

    def put(host, logical):
        arr = jnp.asarray(host)
        return jax.device_put(
            arr, named_sharding_for(arr.shape, logical, mesh, rules))

    shd = ShardedStack(
        mesh=mesh, axis=axis,
        grid=put(stacked.grid, (None, None)),
        cost=put(lexicographic_cost(stacked.grid), (None,)),
        price=put(pad(stacked.price, 0.0), ("cells", None)),
        # unit capacity keeps the padded rows' gradient NaN-free, exactly as
        # device_stack's pad_batch_to convention
        capacity=put(pad(stacked.capacity, 1.0), ("cells", None)),
        lat_ok=put(pad(lat_ok, False), ("cells", None, None)),
        alive0=put(pad(alive0, False), ("cells", None)),
        link_load=put(pad(split_f32(load), 0.0), ("cells", None, None)),
        link_cap=put(split_f32(link_cap), (None, None)),
        incidence=put(pad(inc, False), ("cells", None)),
        group=put(local_gid, ("cells",)),
        row_of=row_of, batch_size=stacked.batch_size, shard_rows=rows,
        groups_per_shard=gps, padded_of=padded_of, coupled=coupled,
        grids=stacked.grids,
    )
    cache[key] = shd
    return shd


def empty_sharded_stack(grid: np.ndarray, price: np.ndarray,
                        capacity: np.ndarray, tmax: int, mesh, *,
                        coupling: CouplingSpec | None = None,
                        semantic: bool = True,
                        axis: str | None = None,
                        grids: int = 1) -> ShardedStack:
    """A MESH-RESIDENT stack of cleared rows — :func:`empty_device_stack`
    laid out across the device mesh.

    The metro serving session allocates one per (batch, Tmax-bucket): the
    coupling groups are LPT-packed over ``mesh.shape[axis]`` blocks once
    (``shard_plan``), the invariants (grid, cost, prices, capacities,
    incidence, budgets) are uploaded once into that layout, and live task
    rows then arrive as perm-addressed delta scatters
    (:meth:`ShardedStack.update_rows`). A coupling-group membership change
    invalidates the plan itself — the session layer rebuilds; budget and
    semantic drift ride the in-place scatters. As with
    :func:`empty_device_stack`, ``grid`` may be the union of ``grids`` cell
    grids, with every scattered row masked to its cell's own.
    """
    if axis is None:
        axis = mesh.axis_names[0]
    price = np.asarray(price)
    capacity = np.asarray(capacity)
    B = price.shape[0]
    coupled = coupling is not None and bool(coupling.incidence.any())
    if coupled:
        if coupling.num_cells != B:
            raise ValueError(
                f"coupling.incidence has {coupling.num_cells} rows for "
                f"{B} cells")
        gid = coupling.groups()
        order = np.argsort(gid, kind="stable").astype(np.int64)
        gs = gid[order]
        starts = np.flatnonzero(np.r_[True, gs[1:] != gs[:-1]])
        offsets = np.r_[starts, B].astype(np.int64)
        link_cap = np.asarray(coupling.link_capacity, np.float64)
        inc = np.asarray(coupling.incidence, bool)
    else:
        order = np.arange(B, dtype=np.int64)
        offsets = np.arange(B + 1, dtype=np.int64)
        # dummy inf link: keeps the coupled core's per-link reductions
        # well-shaped without constraining anything
        link_cap = np.array([np.inf])
        inc = np.zeros((B, 1), bool)

    n_shards = int(mesh.shape[axis])
    row_of, local_gid, padded_of, rows, gps = \
        _plan_layout(order, offsets, n_shards)
    bp = n_shards * rows
    live = row_of >= 0
    src = np.clip(row_of, 0, None)

    def pad(table, fill):
        out = table[src].copy()
        out[~live] = fill
        return out

    from repro.distributed.sharding import named_sharding_for
    rules = {"cells": axis}

    def put(host, logical):
        arr = jnp.asarray(host)
        return jax.device_put(
            arr, named_sharding_for(arr.shape, logical, mesh, rules))

    A = grid.shape[0]
    return ShardedStack(
        mesh=mesh, axis=axis,
        grid=put(grid, (None, None)),
        cost=put(lexicographic_cost(grid), (None,)),
        price=put(pad(price, 0.0), ("cells", None)),
        capacity=put(pad(capacity, 1.0), ("cells", None)),
        lat_ok=put(np.zeros((bp, tmax, A), bool), ("cells", None, None)),
        alive0=put(np.zeros((bp, tmax), bool), ("cells", None)),
        link_load=put(np.zeros((bp, tmax, 2), np.float32),
                      ("cells", None, None)),
        link_cap=put(split_f32(link_cap), (None, None)),
        incidence=put(pad(inc, False), ("cells", None)),
        group=put(local_gid, ("cells",)),
        row_of=row_of, batch_size=B, shard_rows=rows,
        groups_per_shard=gps, padded_of=padded_of, coupled=coupled,
        grids=grids,
    )


def objective_value(inst: ProblemInstance, admitted: np.ndarray,
                    alloc: np.ndarray) -> float:
    """Paper Eq. (1a): Σ_τ Σ_k p_k (S_k - s_τk) x_τ."""
    p, S = inst.pool.price, inst.pool.capacity
    per_task = (p[None, :] * (S[None, :] - alloc)).sum(axis=1)
    return float((per_task * admitted).sum())


def check_solution(inst: ProblemInstance, sol: Solution,
                   lat_params: lat_mod.LatencyParams | None = None,
                   atol: float = 1e-9) -> dict:
    """Independent re-validation of a solution against constraints (1b)-(1f).

    Returns a report dict; ``report["valid"]`` means capacity is respected and
    every *admitted* task actually meets its accuracy and latency bounds when
    re-evaluated from first principles (not from the solver's own tables).
    """
    lat_params = lat_params or lat_mod.LatencyParams()
    t = inst.tasks
    x = sol.admitted.astype(bool)

    used = (sol.alloc * x[:, None]).sum(axis=0)
    cap_ok = bool((used <= inst.pool.capacity + atol).all())

    # validate on the curves that DEFINED the instance — under a drifted
    # model "first principles" means the drifted truth, not the paper default
    a = semantics.resolve(inst.semantics).accuracy(t.app_idx, sol.z)
    acc_ok = a + atol >= t.min_accuracy

    l = lat_mod.latency(lat_params, t.bits_per_job, t.jobs_per_sec,
                        t.gpu_time_per_job, sol.z, sol.alloc)
    lat_ok = l <= t.max_latency + atol

    admitted_ok = (~x) | (acc_ok & lat_ok)
    return {
        "valid": cap_ok and bool(admitted_ok.all()),
        "capacity_ok": cap_ok,
        "used": used,
        "accuracy_ok": acc_ok,
        "latency_ok": lat_ok,
        "latency": l,
        "accuracy": a,
        "objective": objective_value(inst, x, sol.alloc),
    }
