"""The serving commit keeps a tick's decisions as columns.

``SESM.solve_slots`` returns one ``CellDecisions`` per cell, a view over the
solve's columns, and ``apply_decisions`` updates every cell's slot tables
from those columns; a ``SliceDecision`` is built only where one is read.
Twin engines take the same events; one applies the columnar batches, the
other the same decisions as lists of objects built before the apply. Tick by
tick both must leave the same decisions, slot tables, tasks and queues,
counters, drop logs and registry, and so must a third engine that applies
each decision object in turn (``_row_by_row``, the loop the columns
replaced).
"""
import glob
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import CouplingSpec, ResourcePool
from repro.core.events import Arrival, CellFault, Departure, Handover
from repro.serving import (CellDecisions, MultiCellEngine, PendingSolve,
                           SliceDecision, SliceRequest, TaskRuntime,
                           multicell, pinned_accuracy_at)
from repro.serving.engine import _QUEUED, _RUNNING, SlotTables

MACRO = (np.arange(1.0, 16.0), np.arange(1.0, 21.0))
MICRO = (np.arange(2.0, 17.0, 2.0), np.arange(2.0, 11.0, 2.0))
APPS = [("coco_all", 0.35), ("coco_bags", 0.35), ("coco_person", 0.35),
        ("cityscapes_all", 0.5), ("cityscapes_flat", 0.5),
        ("cityscapes_person", 0.5)]
SITES = 2
COUNTERS = ("offered_by_tier", "admitted_by_tier", "evictions_by_tier",
            "drops_by_tier", "sheds_by_tier", "preemptions_by_tier")


def _engine(case: str) -> MultiCellEngine:
    """Two sites of four cells behind a site link: all on the macro grid,
    or a macro cell and three micro cells on a coarser grid."""
    rng = np.random.default_rng(5)
    pools = []
    for _ in range(SITES):
        for layer in ["macro"] + ["micro" if case == "two_grid"
                                  else "macro"] * 3:
            base = np.array([15.0, 20.0]) if layer == "macro" \
                else np.array([16.0, 10.0])
            cap = np.round(base * rng.uniform(0.75, 1.25, 2))
            pools.append(ResourcePool(
                names=("rbg", "gpu"), capacity=cap, price=1.0 / cap,
                levels=MACRO if layer == "macro" else MICRO))
    spec = CouplingSpec(np.full(SITES, 9.0),
                        np.repeat(np.eye(SITES, dtype=bool), 4, axis=0))
    return MultiCellEngine(pools, coupling=spec, max_retries=1,
                           preempt=case == "preempt")


def _listed(eng: MultiCellEngine) -> MultiCellEngine:
    """Make ``eng`` apply every solve's decisions as lists of objects,
    built before the apply."""
    solve = eng.sesm.solve_slots

    def solve_slots(*args, wait=True, **kw):
        pending = solve(*args, wait=False, **kw)

        def lists():
            return [list(ds) for ds in pending.wait()]

        return lists() if wait else PendingSolve(lists)

    eng.sesm.solve_slots = solve_slots
    return eng


def _row_by_row(cells, decisions):
    """The reference apply: each cell's decision objects in turn, as
    ``CellRuntime.apply`` did before its columns."""
    for cell, ds in zip(cells, decisions):
        prev = cell.tasks
        decided = {d.request.request_id for d in ds}
        cell.tasks = {rid: rt for rid, rt in prev.items()
                      if rid not in decided}
        cell._queue = [(rid, cell.gen_of(rid)) for rid in cell.queued_ids()
                       if rid not in decided]
        for d in ds:
            rid = d.request.request_id
            slot = cell._slot_of.get(rid)
            if slot is None:
                p = cell._pending_in.pop(rid, None)
                if p is None:
                    continue
                slot = cell._seat_one(rid, p)
            tier = int(cell._tier[slot])
            cell.offered_by_tier[tier] += 1
            if d.admitted:
                cell.admitted_by_tier[tier] += 1
                rt = cell._rt[slot] or TaskRuntime(d)
                rt.decision = d
                cell.tasks[rid] = cell._rt[slot] = rt
                cell._state[slot] = _RUNNING
                continue
            if rid in prev:
                d.evicted = True
                cell.evictions += 1
                cell.evictions_by_tier[tier] += 1
            if cell._pin[slot] != 0.0:
                cell._pin[slot] = 0.0
                cell._row[slot] = cell._req[slot]
            left = int(cell._retries_left[slot]) - 1
            cell._retries_left[slot] = left
            if left >= 0:
                cell._state[slot] = _QUEUED
                cell._queue.append((rid, int(cell._gen[slot])))
            else:
                cell.drops += 1
                cell.drops_by_tier[tier] += 1
                cell.dropped.append(cell._req[slot])
                cell._slot_of.pop(rid)
                cell._free_slot(slot)
                cell._leave(rid)
    return list(decisions)


def _request(rng) -> SliceRequest:
    app, acc = APPS[int(rng.integers(len(APPS)))]
    return SliceRequest("object-recognition", "yolox", app,
                        max_latency_s=0.7, min_accuracy=acc,
                        jobs_per_sec=float(rng.choice([3.0, 5.0, 8.0])),
                        tier=int(rng.integers(3)))


def _fields(d):
    return (d.request.request_id, d.request.min_accuracy, d.admitted, d.z,
            d.alloc, d.expected_latency_s, d.expected_accuracy, d.cell,
            d.evicted)


def _snapshot(eng: MultiCellEngine):
    cells = []
    for cell in eng.cells:
        hi = cell._hi
        cells.append(dict(
            hi=hi,
            # _drow, where a running task's runtime finds its decision, is
            # compared through the decisions themselves (rt below)
            tables={name: getattr(cell, name)[:hi].tolist()
                    for name, _, _ in SlotTables._FIELDS if name != "_drow"},
            req=[r and r.request_id for r in cell._req[:hi]],
            row=[r and (r.request_id, r.min_accuracy)
                 for r in cell._row[:hi]],
            rt=[rt and (rt.jobs_done, _fields(rt.decision))
                for rt in cell._rt[:hi]],
            free=sorted(cell._free_slots),
            slot_of=dict(cell._slot_of),
            pending=list(cell._pending_in),
            tasks=list(cell.tasks),
            queue=list(cell._queue),
            counters={name: dict(getattr(cell, name)) for name in COUNTERS},
            scalars=(cell.drops, cell.evictions, cell.sheds,
                     cell.preemptions),
            dropped=[r.request_id for r in cell.dropped]))
    return cells, dict(eng._cell_of)


def _live(eng, rng, share):
    return [rid for c in eng.cells for rid in c.live_ids()
            if rng.random() < share]


@pytest.mark.parametrize("case", ["macro", "two_grid", "preempt"])
def test_columnar_and_listed_commits_leave_the_same_state(case, monkeypatch):
    """Arrivals, departures, drops past ``max_retries``, handovers, a cell
    failure and its recovery, and events landing between dispatch and
    commit (stale decisions, re-seated slots, a request that departs and
    comes back while its decision is in flight)."""
    col, lst, ref = _engine(case), _listed(_engine(case)), _engine(case)
    apply = multicell.apply_decisions
    monkeypatch.setattr(
        multicell, "apply_decisions",
        lambda cells, ds: (_row_by_row if cells is ref.cells else apply)(
            cells, ds))
    rng = np.random.default_rng(17)
    moved = evicted = 0
    for tick in range(12):
        events = []
        if tick == 4:
            events.append(CellFault(1, failed=True))
        if tick == 8:
            events.append(CellFault(1, failed=False))
        events += [Departure(rid) for rid in _live(col, rng, 0.1)]
        for c, cell in enumerate(col.cells):
            for rid in list(cell.tasks):
                if rng.random() < 0.1:
                    events.append(Handover(rid, c, (c + 1) % len(col.cells)))
        # enough arrivals to overload the links and outgrow 8 slots a cell
        events += [Arrival(_request(rng), c) for c in range(len(col.cells))
                   for _ in range(int(rng.integers(0, 5 if tick < 3 else 3)))]
        gone = _live(col, rng, 0.08)
        back = gone[::2]
        late = [Departure(rid) for rid in gone]
        late += [Arrival(col.cells[col.locate(rid)].request_of(rid),
                         col.locate(rid)) for rid in back]
        late += [Arrival(_request(rng), c) for c in (0, 5)]
        for c, cell in enumerate(col.cells):
            for rid in list(cell.tasks)[:1]:
                if rng.random() < 0.2:
                    late.append(Handover(rid, c, (c + 2) % len(col.cells)))
        results = []
        for eng in (col, lst, ref):
            eng.ingest(events)
            handle = eng.reslice_dispatch()
            eng.ingest(late)
            if tick % 2:
                eng.gather()                  # seats the late arrivals
            out = eng.reslice_commit(handle)
            results.append(out)
        got, listed, want = results
        for c, (ds, ls, rs) in enumerate(zip(got, listed, want)):
            assert [_fields(d) for d in ds] == [_fields(d) for d in ls] \
                == [_fields(d) for d in rs], (tick, c)
            assert ds == ls
            evicted += sum(d.evicted for d in rs)
        assert _snapshot(col) == _snapshot(lst) == _snapshot(ref), tick
        assert [c.moved for c in col.cells] == [c.moved for c in lst.cells]
        moved = sum(c.moved for c in col.cells)
    totals = col.metrics()["totals"]
    assert totals["drops"] > 0 and totals["handovers"] > 0
    assert totals["evictions"] > 0 and evicted > 0 and moved > 0
    assert col.recoveries == 1
    if case == "preempt":
        assert totals["preemptions"] > 0
    assert col.sesm.decisions_built > 0      # the comparisons read them
    assert max(cell._hi for cell in col.cells) > 8


@pytest.mark.parametrize("seed", [1, 2])
def test_partial_list_apply_matches_row_by_row(seed):
    """A cell's own solve over its gather set (running tasks, then the
    queue, arrivals not yet seated last), applied in part: the undecided
    tasks and requests carry forward, the unseated ones seat as rows that
    drop free their slots."""
    engs = [_engine("macro"), _engine("macro")]
    rng = np.random.default_rng(seed)
    first = [Arrival(_request(rng), c) for c in range(8) for _ in range(12)]
    late = [Arrival(_request(rng), c) for c in (2, 3) for _ in range(6)]
    for eng in engs:
        eng.ingest(first)
        eng.reslice()
        eng.ingest(late)
    for k in range(6):
        outs = []
        for eng, apply in zip(engs, (None, _row_by_row)):
            cell = eng.cells[2 + k % 2]
            ds = eng.sesm.slice(cell.gather())[k % 2::2 if k < 2 else 1]
            outs.append(cell.apply(ds) if apply is None
                        else apply([cell], [ds])[0])
        assert [_fields(d) for d in outs[0]] == [_fields(d) for d in outs[1]]
        assert _snapshot(engs[0]) == _snapshot(engs[1]), k
    assert sum(c.drops for c in engs[0].cells) > 0


def test_running_row_out_of_its_slot_is_evicted_as_row_by_row(monkeypatch):
    """A request decided in one slot that, by commit, runs in another (it
    left, came back behind a newcomer, and an apply between dispatch and
    commit admitted it) takes the per-row path, and its rejection there is
    an eviction."""
    col, ref = _engine("macro"), _engine("macro")
    apply = multicell.apply_decisions
    monkeypatch.setattr(
        multicell, "apply_decisions",
        lambda cells, ds: (_row_by_row if cells is ref.cells else apply)(
            cells, ds))
    rng = np.random.default_rng(6)
    first = [Arrival(_request(rng), 0) for _ in range(3)]
    # never feasible: the solver rejects it on every tick
    hard = SliceRequest("object-recognition", "yolox", "coco_bags",
                        max_latency_s=0.7, min_accuracy=0.99)
    other = _request(rng)
    outs = []
    for eng in (col, ref):
        eng.ingest(first + [Arrival(hard, 0)])
        eng.reslice()
        cell = eng.cells[0]
        slot = cell._slot_of[hard.request_id]
        handle = eng.reslice_dispatch()
        eng.ingest([Departure(hard.request_id), Arrival(other, 0),
                    Arrival(hard, 0)])
        eng.gather()
        assert cell._slot_of[other.request_id] == slot
        forced = [SliceDecision(hard, True, 1.0, {"rbg": 1.0, "gpu": 1.0},
                                0.1, 0.9, cell=0)]
        if eng is col:
            cell.apply(forced)
        else:
            _row_by_row([cell], [forced])
        assert hard.request_id in cell.tasks
        outs.append(eng.reslice_commit(handle)[0])
    got, want = outs
    row = [d.request.request_id for d in want].index(hard.request_id)
    assert want[row].evicted and got[row].evicted
    assert [_fields(d) for d in got] == [_fields(d) for d in want]
    assert _snapshot(col) == _snapshot(ref)
    assert col.cells[0].evictions == 1


def test_unread_closed_loop_builds_no_decision():
    """Over 20 closed-loop ticks whose decisions nobody reads no
    ``SliceDecision`` is built; reading a cell's decisions twice builds
    its rows once."""
    eng = _engine("two_grid")
    rng = np.random.default_rng(3)
    eng.ingest([Arrival(_request(rng), c) for c in range(len(eng.cells))
                for _ in range(6)])
    for _ in range(20):
        gone = _live(eng, rng, 0.1)
        eng.ingest([Departure(rid) for rid in gone]
                   + [Arrival(_request(rng), int(rng.integers(8)))
                      for _ in gone])
        out = eng.reslice_commit(eng.reslice_dispatch())
    assert eng.sesm.decisions_built == 0
    assert sum(c.drops for c in eng.cells) > 0
    ds = next(ds for ds in out if len(ds))
    assert isinstance(ds, CellDecisions)
    first = list(ds)
    assert list(ds) == first and all(a is b for a, b in zip(ds, first))
    assert eng.sesm.decisions_built == len(ds)


def test_runtime_decision_is_built_when_read_and_matches_the_list_path():
    col, lst = _engine("macro"), _listed(_engine("macro"))
    rng = np.random.default_rng(9)
    arrivals = [Arrival(_request(rng), c) for c in range(8) for _ in range(4)]
    for eng in (col, lst):
        eng.ingest(arrivals)
        eng.reslice_commit(eng.reslice_dispatch())
        eng.reslice_commit(eng.reslice_dispatch())
    assert col.sesm.decisions_built == 0
    rid = next(iter(col.cells[0].tasks))
    rt = col.cells[0].tasks[rid]
    d = rt.decision
    assert col.sesm.decisions_built == 1
    assert rt.decision is d and col.sesm.decisions_built == 1
    assert _fields(d) == _fields(lst.cells[0].tasks[rid].decision)
    assert d.admitted and not d.evicted
    # the handover pin reads the same achieved z on both paths
    want = pinned_accuracy_at(d.request, d.z, model=col.sdla.semantics)
    rid2 = list(col.cells[2].tasks)[-1]
    assert col.handover(rid2, 2, 3) == lst.handover(rid2, 2, 3)
    assert col.handover(rid, 0, 1) == lst.handover(rid, 0, 1) == want


def test_cell_decisions_read_as_a_list():
    eng = _engine("macro")
    rng = np.random.default_rng(4)
    eng.ingest([Arrival(_request(rng), c) for c in range(8) for _ in range(5)])
    ds = eng.reslice()[3]
    ref = list(ds)
    assert len(ds) == len(ref) == 5
    assert ds[-1] is ref[-1] and ds[1:4] == ref[1:4]
    assert ds == ref and ds == tuple(ref) and ds != ref[:-1]
    assert ds.admitted.tolist() == [d.admitted for d in ref]
    assert ds.rid.tolist() == [d.request.request_id for d in ref]
    assert np.array_equal(ds.alloc, [[d.alloc[n] for n in ds.names]
                                     for d in ref])
    assert all(d.cell == 3 for d in ref)
    with pytest.raises(IndexError):
        ds[5]
    # a list's columns keep its objects: flags written through them reach
    # the caller's objects
    listed = CellDecisions.of(ref)
    assert listed.slot is None and listed == ds
    listed.columns.flag_evicted([2])
    assert ref[2].evicted and listed.evicted.tolist()[2]


def test_tables_are_one_row_per_cell_and_grow_together():
    eng = _engine("macro")
    tables = eng.cells[0]._tables
    assert all(c._tables is tables and c._trow == k
               for k, c in enumerate(eng.cells))
    rng = np.random.default_rng(2)
    eng.ingest([Arrival(_request(rng), 5) for _ in range(11)])
    eng.reslice()
    assert tables.cap >= 11 and tables._rid.shape == (8, tables.cap)
    for c, cell in enumerate(eng.cells):
        assert len(cell._req) == tables.cap
        assert np.shares_memory(cell._state, tables._state)
    assert tables._state[5, :11].tolist() == eng.cells[5]._state[:11].tolist()


def _capture(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return out, [dict(e.stats)
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name == "repro.tick.apply"]


def test_apply_span_counts_rows_and_moved_rows(tmp_path):
    eng = _engine("two_grid")
    rng = np.random.default_rng(8)
    eng.ingest([Arrival(_request(rng), c) for c in range(8) for _ in range(6)])
    eng.reslice()                                # compile outside the trace
    moved = sum(c.moved for c in eng.cells)
    eng.ingest([Arrival(_request(rng), c) for c in range(8)])
    out, (stats,) = _capture(
        tmp_path, lambda: eng.reslice_commit(eng.reslice_dispatch()))
    assert stats["rows"] == sum(len(ds) for ds in out) == 56
    assert stats["moved"] == sum(c.moved for c in eng.cells) - moved > 0
