"""The comparison that decides ``correct``: decisions the timed path applied
against the plain reference (``bench/reference.py``) on the same inputs.

Serving cells: for each sampled tick, every cell's decision list (the
candidates the engine solved, in its order) is rebuilt in the reference from
the requests' own parameters, solved jointly over each coupling group (the
connected components of the cell-link graph) against each cell's own pool,
and each task's admission flag and allocation compared. Besides, every decided
request must be one the traffic sent to that cell and had not withdrawn
before the tick (``stale``), and every arrival sent just before a tick must
be decided in it (``missing``).

Sweep cells: a sample of each batch's instances, drawn from the seed, in
each sampled readback and in the batch's last readback, against Algorithm 1
on those instances.

Near ties (gradients within ``reference.TIE`` of the best) are resolved the
way the decisions under test resolved them (``reference.Cell.hint``);
everything else is compared exactly: each count has the limit 0. Each
comparison also returns its tie readings: the ties it followed and the
largest relative gap it looked at (``reference.Cell.look``).

Controls (``bench/control.py``; never part of a benchmark run) are put in
the program's place and compared the same way: the reference with its
gradient in bfloat16, and the reference with one of the configuration's
guarantees broken (serving: the link budgets left out, each cell solved
alone; sweep: admitted allocations not charged to the pool).
"""

from __future__ import annotations

import numpy as np

from bench import reference as ref


def _cell_tasks(decisions, grid) -> list[ref.Task]:
    return [ref.Task(d.request.app_class, d.request.min_accuracy,
                     d.request.max_latency_s,
                     d.request.jobs_per_sec * d.request.n_ues, grid)
            for d in decisions]


def hints(table) -> list[frozenset]:
    """The (task, allocation) pairs each cell of a decision table admitted."""
    return [frozenset((int(t), int(i[t])) for t in np.flatnonzero(a))
            for a, i in table]


def _ties(cells, into: dict | None = None) -> dict:
    into = into or {"ties_followed": 0, "tie_gap_max": 0.0}
    for cell in cells:
        into["ties_followed"] += cell.ties_followed
        into["tie_gap_max"] = max(into["tie_gap_max"], cell.gap_max)
    return into


def reference_tick(dep, decisions, dtype=np.float64, coupled: bool = True,
                   hint=None):
    """Reference (admitted, allocation index) per cell for one tick's
    candidate lists, solved jointly over each coupling group in cell order,
    and its tie readings on ``hint``; ``coupled=False`` drops the link
    budgets, each cell solved alone."""
    out = [None] * dep.n_cells
    ties = None
    for group in dep.groups():
        cells = []
        for c in group:
            grid = dep.pool(c).grid
            cells.append(ref.Cell(_cell_tasks(decisions[c], grid),
                                  dep.price[c], dep.capacity[c], grid, dtype,
                                  hint=hint[c] if hint else frozenset()))
        if coupled:
            ref.solve_group(cells, dep.link_budget, dep.incidence[group])
        else:
            for cell in cells:
                ref.solve_cell(cell)
        for c, cell in zip(group, cells):
            out[c] = (cell.admitted, cell.alloc)
        ties = _ties(cells, ties)
    return out, ties


def decision_table(dep, decisions):
    """The engine's decisions as (admitted, allocation index) per cell, each
    allocation indexed in its cell's own grid."""
    index = [{tuple(row): i for i, row in enumerate(p.grid.tolist())}
             for p in dep.pools]
    out = []
    for c, ds in enumerate(decisions):
        pool, at = dep.pool(c), index[dep.pool_of[c]]
        adm = np.array([d.admitted for d in ds], bool)
        alloc = np.array([at[tuple(d.alloc[n] for n in pool.names)]
                          if d.admitted else -1 for d in ds], np.int64)
        out.append((adm, alloc))
    return out


def mismatches(got, expected) -> int:
    """Tasks whose admission or admitted allocation differs."""
    bad = 0
    for (ga, gi), (ea, ei) in zip(got, expected):
        bad += int((ga != ea).sum()) + int(((gi != ei) & ga & ea).sum())
    return bad


def compare_tick(dep, decisions, got=None) -> tuple[int, dict, list]:
    """(mismatches, tie readings, the reference's table) of a decision table
    ``got`` (the engine's own by default) against the reference on
    ``decisions``' candidate lists."""
    got = decision_table(dep, decisions) if got is None else got
    hint = hints(got)
    expected, ties = reference_tick(dep, decisions, hint=hint)
    return mismatches(got, expected), ties, expected


def groups_bound(dep, decisions, expected) -> int:
    """The coupling groups of one tick in which the link budgets decide
    something: Algorithm 1 without them (each cell alone) differs from
    ``expected``, the reference's table with them."""
    loose, _ = reference_tick(dep, decisions, coupled=False,
                              hint=hints(expected))
    return sum(mismatches([expected[c] for c in g], [loose[c] for c in g]) > 0
               for g in dep.groups())


def membership(decisions, tick: int, sent: dict, withdrawn: dict,
               fresh: list[tuple[int, int]]) -> tuple[int, int]:
    """(stale, missing) for one tick. ``sent`` maps each request id to its
    cell, ``withdrawn`` to the first dispatch after its departure went in,
    ``fresh`` lists the (id, cell) arrivals that went in just before this
    tick's dispatch."""
    stale = 0
    decided: set[int] = set()
    for c, ds in enumerate(decisions):
        for d in ds:
            rid = d.request.request_id
            decided.add(rid)
            if sent.get(rid) != c or withdrawn.get(rid, tick + 1) <= tick:
                stale += 1
    missing = sum(1 for rid, _ in fresh if rid not in decided)
    return stale, missing


def sweep_reference(pool_cfg: dict, batch, dtype=np.float64,
                    charge: bool = True, hint=None):
    """Reference (admitted, alloc index) per instance of a sweep batch, and
    its tie readings on ``hint``."""
    levels = [np.asarray(lv, np.float64) for lv in pool_cfg["levels"]]
    grid = ref.allocation_grid(levels)
    cap = np.asarray(pool_cfg["capacity"], np.float64)
    price = 1.0 / cap
    out = []
    cells = []
    for k, tasks in enumerate(batch):
        cell = ref.Cell([ref.Task(a, acc, lat, r, grid) for a, acc, lat, r in
                         zip(tasks.apps, tasks.min_acc, tasks.max_lat,
                             tasks.rate)], price, cap, grid, dtype, charge,
                        hint=hint[k] if hint else frozenset())
        ref.solve_cell(cell)
        out.append((cell.admitted, cell.alloc))
        cells.append(cell)
    return out, _ties(cells)


def readback_rows(readback: dict, rows, batch):
    """The readback's (admitted, alloc index) of instances ``rows``, cut to
    each instance's own task count."""
    return [(readback["admitted"][k, :len(t.apps)],
             np.where(readback["admitted"][k, :len(t.apps)],
                      readback["alloc_idx"][k, :len(t.apps)], -1))
            for k, t in zip(rows, batch)]
