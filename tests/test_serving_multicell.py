"""Multi-cell serving engine: coupled re-slicing end-to-end.

The closed-loop acceptance scenario: 3 cells share one backhaul link, every
engine re-slice is ONE coupled ``SESM.solve_batch`` device program whose
admitted sets bit-match the numpy coupled oracle
(``baselines.solve_coupled_ref``) on the gathered instances, the restack
pow2-bucket cache never misses after the first tick, rejected requests drain
through the bounded retry queue, and handover preserves the achieved-z
accuracy pin.
"""
import dataclasses
import gc

import numpy as np
import pytest

from repro.core import CouplingSpec, scenarios, semantics, solve_coupled_ref
from repro.core.greedy import _serve_batch_coupled
from repro.serving import MultiCellEngine, SliceRequest, drive_closed_loop


def _req(app, acc=0.30, lat=0.7, fps=5.0):
    return SliceRequest("object-recognition", "yolox", app,
                        max_latency_s=lat, min_accuracy=acc,
                        jobs_per_sec=fps)


def _submit_mix(eng, cell):
    eng.submit(_req("coco_bags", acc=0.35, fps=8.0), cell)
    eng.submit(_req("coco_animals", acc=0.50, fps=6.0), cell)
    eng.submit(_req("cityscapes_flat", acc=0.35, fps=5.0), cell)


def _coupled_engine(budget=1.0, max_retries=2):
    pools = scenarios.multi_cell_pools(3, seed=2)
    spec = CouplingSpec(np.array([budget]), np.ones((3, 1), bool),
                        names=("backhaul",))
    eng = MultiCellEngine(pools, coupling=spec, max_retries=max_retries)
    for c in range(3):
        _submit_mix(eng, c)
    return eng, pools, spec


def _assert_matches_oracle(eng, pools, spec):
    """One engine re-slice == solve_coupled_ref on the gathered instances."""
    sets = eng.gather()
    assert all(sets), "scenario must keep every cell non-empty"
    insts = [dataclasses.replace(
        eng.sdla.build_instance(rs, pools[i]), coupling=spec.row(i))
        for i, rs in enumerate(sets)]
    refs = solve_coupled_ref(insts)
    decisions = eng.reslice()
    for ds, ref in zip(decisions, refs):
        assert [d.admitted for d in ds] == [bool(a) for a in ref.admitted]
    return decisions


def test_multicell_engine_validates_pools_and_coupling():
    pools = scenarios.multi_cell_pools(3, seed=2)
    with pytest.raises(ValueError, match="rows"):
        MultiCellEngine(pools, coupling=CouplingSpec(
            np.array([1.0]), np.ones((2, 1), bool)))
    # pools on different grids share one engine (a union grid) ...
    mixed = pools[:2] + [scenarios.multi_cell_pools(4, seed=0, n_grids=2)[1]]
    assert MultiCellEngine(mixed).num_cells == 3
    # ... pools of different resources do not
    wider = pools[:2] + [scenarios.multi_cell_pools(1, m=4, seed=0)[0]]
    with pytest.raises(ValueError, match="same resources"):
        MultiCellEngine(wider)
    with pytest.raises(ValueError, match="at least one"):
        MultiCellEngine([])


def test_three_cell_shared_backhaul_closed_loop():
    """6 closed-loop ticks: per-step admissions bit-match the coupled oracle,
    the restack cache never misses after tick 0, and the retry queue drains
    (rejected requests re-offer max_retries times, then drop)."""
    eng, pools, spec = _coupled_engine(budget=1.0, max_retries=2)
    rejected0 = None
    compiled_after_first = None
    for tick in range(6):
        decisions = _assert_matches_oracle(eng, pools, spec)
        if tick == 0:
            rejected0 = {d.request.request_id
                         for ds in decisions for d in ds if not d.admitted}
            assert rejected0, "budget must bind to exercise the retry queue"
            compiled_after_first = _serve_batch_coupled._cache_size()
    # one fresh stack (tick 0), all later ticks restack in place: ZERO misses
    assert eng.sesm.fresh_stacks == 1
    assert eng.sesm.restacks == 5
    # ... and the pow2 buckets kept the device program cached: no recompiles
    assert _serve_batch_coupled._cache_size() == compiled_after_first
    # retry queue drained: every tick-0 reject re-offered max_retries times,
    # then dropped — never silently discarded
    assert all(not cell.pending for cell in eng.cells)
    dropped = {r.request_id for cell in eng.cells for r in cell.dropped}
    assert dropped == rejected0
    # every cell still serves at least one admitted task
    assert all(cell.tasks for cell in eng.cells)
    # the shared budget binds: an uncoupled twin admits strictly more
    unc = MultiCellEngine(pools, max_retries=2)
    for c in range(3):
        _submit_mix(unc, c)
    n_unc = sum(d.admitted for ds in unc.reslice() for d in ds)
    n_cpl = sum(len(cell.tasks) for cell in eng.cells)
    assert n_cpl < n_unc


def test_handover_preserves_z_pin_in_coupled_loop():
    """A handed-over task re-arrives with its accuracy bound pinned at the
    level achieved at its admitted z, and the next coupled re-slice (still
    oracle-matched, still restacking in place) re-derives that same z."""
    eng, pools, spec = _coupled_engine(budget=1.0, max_retries=2)
    _assert_matches_oracle(eng, pools, spec)
    rid = next(iter(eng.cells[0].tasks))
    rt = eng.cells[0].tasks[rid]
    z0 = rt.decision.z
    app_idx = semantics.APP_INDEX[rt.decision.request.app_class]
    pin = eng.handover(rid, 0, 1)
    assert pin == pytest.approx(float(semantics.accuracy(
        np.array([app_idx]), np.array([z0]))[0]))
    # the pin rides the gathered request of the TARGET cell
    gathered = {r.request_id: r for r in eng.cells[1].gather()}
    assert gathered[rid].min_accuracy == pytest.approx(pin)
    assert rid not in {r.request_id for r in eng.cells[0].gather()}
    for tick in (1, 2):
        decisions = _assert_matches_oracle(eng, pools, spec)
        d = next(d for ds in decisions for d in ds
                 if d.request.request_id == rid)
        assert d.cell == 1
        if d.admitted:
            # warm start: Eq. (2) re-derives the same compression, the
            # stream is not renegotiated
            assert d.z == pytest.approx(z0)
    assert eng.sesm.fresh_stacks == 1   # handover stayed inside the bucket


def test_transiently_empty_cell_keeps_restack_cache():
    """A cell whose tasks all depart/drop rides the batch as a zero-task row
    instead of shrinking it — occupancy toggles must not miss the restack
    cache (which would also recompile the device program)."""
    eng = MultiCellEngine(scenarios.multi_cell_pools(2, seed=0))
    eng.submit(_req("coco_bags"), 0)
    ds = eng.reslice()                       # cell 1 empty
    assert [len(d) for d in ds] == [1, 0]
    eng.reslice()                            # still empty
    eng.submit(_req("cityscapes_flat"), 1)
    ds = eng.reslice()                       # cell 1 refills
    assert ds[1][0].admitted
    rid = ds[1][0].request.request_id
    eng.remove(rid, 1)
    eng.reslice()                            # empty again
    assert eng.sesm.fresh_stacks == 1 and eng.sesm.restacks == 3


def test_handover_carries_runtime_history():
    pools = scenarios.multi_cell_pools(2, seed=0)
    eng = MultiCellEngine(pools, max_batch=4)
    eng.submit(_req("cityscapes_flat", acc=0.30, fps=3.0), 0)
    eng.reslice()
    eng.process(wall_dt=1.0)
    rid = next(iter(eng.cells[0].tasks))
    jobs = eng.cells[0].tasks[rid].jobs_done
    assert jobs > 0
    eng.handover(rid, 0, 1)
    eng.reslice()
    assert rid in eng.cells[1].tasks, "generous capacity must re-admit"
    assert eng.cells[1].tasks[rid].jobs_done == jobs
    assert eng.handovers == 1
    # per-cell metrics follow the task
    assert rid in eng.metrics()[1] and rid not in eng.metrics()[0]


def test_handover_rejects_bad_moves():
    pools = scenarios.multi_cell_pools(2, seed=0)
    eng = MultiCellEngine(pools)
    eng.submit(_req("coco_bags"), 0)
    eng.reslice()
    rid = next(iter(eng.cells[0].tasks))
    with pytest.raises(ValueError, match="distinct"):
        eng.handover(rid, 0, 0)
    with pytest.raises(KeyError):
        eng.handover(10**9, 0, 1)


def test_cross_cell_duplicate_request_rejected():
    """One stream must load the shared transport once: a request live in any
    cell cannot be submitted to another (or handed into one that has it)."""
    eng = MultiCellEngine(scenarios.multi_cell_pools(2, seed=0))
    r = _req("coco_bags")
    eng.submit(r, 0)
    with pytest.raises(ValueError, match="already live"):
        eng.submit(r, 1)
    eng.reslice()
    rt = eng.cells[0].tasks[r.request_id]
    with pytest.raises(ValueError, match="already live"):
        eng.cells[0].hand_in(r, rt, 2, 0.5)


def test_drive_closed_loop_records():
    """The scenario library drives the live engine: one record per
    (step, cell), deterministic under seed, with mobility and retries."""
    def run():
        eng = MultiCellEngine(scenarios.multi_cell_pools(2, seed=0),
                              max_retries=1)
        return drive_closed_loop(eng, 6, arrival_rate=3.0,
                                 handover_prob=0.4, seed=1)
    recs = run()
    assert len(recs) == 12
    assert all(0 <= r["admitted"] <= r["offered"] for r in recs)
    assert recs[0]["restacked"]
    assert sum(r["handovers"] for r in recs) > 0
    assert run() == recs


def test_fastpath_matches_rebuild_under_churn():
    """The device-resident delta re-slice and the full-rebuild path make
    IDENTICAL decisions tick for tick under arrival/departure/handover
    churn (same structure driven through twin engines)."""
    def build():
        pools = scenarios.multi_cell_pools(3, seed=2)
        spec = CouplingSpec(np.array([2.0]), np.ones((3, 1), bool))
        eng = MultiCellEngine(pools, coupling=spec, max_retries=2)
        for c in range(3):
            _submit_mix(eng, c)
        return eng

    fast, slow = build(), build()
    rng = np.random.default_rng(11)
    for tick in range(6):
        df = fast.reslice()
        ds = slow.reslice_rebuild()
        for cf, cs in zip(df, ds):
            assert [(d.admitted, d.z, d.alloc, d.evicted) for d in cf] \
                == [(d.admitted, d.z, d.alloc, d.evicted) for d in cs], tick
        # identical churn on both engines (ids differ, structure matches)
        for eng in (fast, slow):
            running = [(c, rid) for c, cell in enumerate(eng.cells)
                       for rid in cell.tasks]
            state = rng.bit_generator.state
            if running and rng.random() < 0.7:
                c, rid = running[int(rng.integers(len(running)))]
                eng.handover(rid, c, (c + 1) % 3)
            if running and rng.random() < 0.5:
                c, rid = running[int(rng.integers(len(running)))]
                if eng.cells[c].is_live(rid):
                    eng.remove(rid, c)
            if rng.random() < 0.7:
                eng.submit(_req("coco_person", acc=0.25, fps=4.0),
                           int(rng.integers(3)))
            if eng is fast:                 # replay the same draws for slow
                rng.bit_generator.state = state
    # every tick either delta-synced the session or (at most once, when the
    # churn outgrew the initial pow2 bucket) rebuilt it at the next bucket
    assert fast.sesm.fresh_stacks <= 2
    assert fast.sesm.fresh_stacks + fast.sesm.restacks == 6


def test_rowid_reuse_invalidates_slot():
    """A request id reused by a NEW submission after departure must get a
    fresh solver row — never its predecessor's cached one."""
    eng = MultiCellEngine(scenarios.multi_cell_pools(2, seed=0))
    first = _req("coco_bags", acc=0.25)
    eng.submit(first, 0)
    eng.submit(_req("cityscapes_flat"), 1)
    d0 = next(d for d in eng.reslice()[0]
              if d.request.request_id == first.request_id)
    assert d0.admitted
    eng.remove(first.request_id, 0)
    eng.reslice()
    # same id, different requirements: unreachable accuracy → must reject
    reused = _req("coco_bags", acc=0.999)
    reused.request_id = first.request_id
    eng.submit(reused, 0)
    d1 = next(d for d in eng.reslice()[0]
              if d.request.request_id == first.request_id)
    assert not d1.admitted and d1.z == 1.0
    assert eng.sesm.fresh_stacks == 1, "id reuse must not rebuild the stack"


def test_inplace_pool_mutation_invalidates_session():
    """ResourcePool is frozen but its arrays are not: an in-place capacity
    edit between ticks must rebuild the device session (value snapshot), so
    the fast path never admits against stale pool state."""
    pools = scenarios.multi_cell_pools(2, seed=0)
    eng = MultiCellEngine(pools)
    eng.submit(_req("coco_bags"), 0)
    eng.reslice()
    eng.reslice()
    assert eng.sesm.fresh_stacks == 1
    pools[0].capacity[:] = pools[0].capacity * 0.5
    eng.reslice()
    assert eng.sesm.fresh_stacks == 2, \
        "capacity edit must invalidate the device session"


@pytest.mark.parametrize("freeze", [True, False])
def test_first_commit_of_each_session_freezes_the_heap(freeze):
    """With ``freeze_heap`` the first commit of every serve session moves
    what is live into the collector's permanent generation, once per
    session; without it the collector keeps tracking everything."""
    pools = scenarios.multi_cell_pools(2, seed=0)
    eng = MultiCellEngine(pools, freeze_heap=freeze)
    eng.submit(_req("coco_bags"), 0)
    held = [[]]                                   # a container made before
    eng.reslice()
    eng.reslice()

    def tracked():
        return any(o is held for o in gc.get_objects())

    assert eng.heap_freezes == int(freeze)
    assert tracked() is not freeze
    pools[0].capacity[:] = pools[0].capacity * 0.5
    eng.reslice()                                 # a new session
    assert eng.sesm.fresh_stacks == 2
    assert eng.heap_freezes == 2 * int(freeze)


def test_latency_scale_change_invalidates_session():
    """Every cached row depends on the SDLA latency scale: a radio-status
    update must rebuild the device session, and the next re-slice must match
    the oracle built at the NEW scale."""
    eng, pools, spec = _coupled_engine(budget=1.0, max_retries=2)
    _assert_matches_oracle(eng, pools, spec)
    assert eng.sesm.fresh_stacks == 1
    eng.sdla.update_radio_status(2.0)       # halves every latency budget
    decisions = _assert_matches_oracle(eng, pools, spec)
    assert eng.sesm.fresh_stacks == 2, \
        "scale change must invalidate the device session"
    assert any(d.admitted for ds in decisions for d in ds)


def test_drive_closed_loop_tolerates_preexisting_tasks():
    """Driving an engine that already serves manually-submitted tasks must
    not crash when mobility picks one of them for handover (they simply have
    no driver-side departure schedule)."""
    eng = MultiCellEngine(scenarios.multi_cell_pools(2, seed=0))
    eng.submit(_req("cityscapes_flat", acc=0.30, fps=3.0), 0)
    eng.reslice()
    recs = drive_closed_loop(eng, 4, arrival_rate=2.0, handover_prob=1.0,
                             seed=3)
    assert sum(r["handovers"] for r in recs) > 0
