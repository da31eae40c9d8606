"""The serving fault plane: outages, link degradation, SLA-scored shedding.

Acceptance scenario (ISSUE 7): a failed cell drains its candidate set into
live coupled neighbors (pins and retry budgets carried) and rides later
coupled solves as zero-task rows — admissions during and after the outage
bit-match ``solve_coupled_ref`` on the gathered post-drain instances with
the device ``_ServeSession`` NEVER rebuilt; a budget-only ``CouplingSpec``
degradation re-slices through one (L,) device refresh instead of a session
rebuild; heartbeat-silent cells auto-fail; TierPolicy sheds low-priority
tiers first under pressure; and the driver reduces scenario runs to an SLA
scorecard with per-tier floors asserted here.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import CouplingSpec, scenarios, solve_coupled_ref
from repro.core.events import Arrival, CellFault, LinkScale
from repro.core.sfesp import empty_device_stack
from repro.serving import (MultiCellEngine, SliceRequest, TierPolicy,
                           drive_closed_loop, sla_scorecard)

APPS = ["coco_bags", "coco_animals", "cityscapes_flat", "coco_urban",
        "cityscapes_person"]


def _req(app, acc=0.30, lat=0.7, fps=5.0, tier=0):
    return SliceRequest("object-recognition", "yolox", app,
                        max_latency_s=lat, min_accuracy=acc,
                        jobs_per_sec=fps, tier=tier)


def _outage_engine(budget=5.0, max_retries=5, n_per_cell=5, **kw):
    """3 coupled cells x n tasks each, sized so a full drain of one cell
    still fits the neighbors' initial pow2 Tmax bucket (no rebuild)."""
    pools = scenarios.multi_cell_pools(3, seed=0)
    spec = CouplingSpec(np.array([budget]), np.ones((3, 1), bool),
                        names=("backhaul",))
    eng = MultiCellEngine(pools, coupling=spec, max_retries=max_retries, **kw)
    for c in range(3):
        for k in range(n_per_cell):
            eng.submit(_req(APPS[k % len(APPS)], acc=0.35, fps=4.0), c)
    return eng, pools, spec


def _assert_oracle(eng, pools, spec):
    """One re-slice == solve_coupled_ref on the gathered (post-drain)
    instances; dead cells legitimately gather EMPTY sets (zero-task rows)."""
    sets = eng.gather()
    insts = [dataclasses.replace(
        eng.sdla.build_instance(rs, pools[i]), coupling=spec.row(i))
        for i, rs in enumerate(sets)]
    refs = solve_coupled_ref(insts)
    decisions = eng.reslice()
    for i, (ds, ref) in enumerate(zip(decisions, refs)):
        assert [d.admitted for d in ds] == [bool(a) for a in ref.admitted], i
        for d, z in zip(ds, ref.z):
            if d.admitted:
                assert d.z == pytest.approx(float(z), abs=1e-12)
    return decisions


# --------------------------------------------------------------- outages

def test_outage_drains_into_coupled_neighbors_oracle_pinned():
    """fail_cell re-homes the full candidate set into live coupled
    neighbors; admissions during AND after the outage bit-match the oracle
    on the gathered post-drain instances, with zero session rebuilds."""
    eng, pools, spec = _outage_engine()
    eng.reslice()
    eng.reslice()
    n_before = sum(len(s) for s in eng.gather())
    moves = eng.fail_cell(0)
    assert set(moves.values()) <= {1, 2}, "drain targets must be live peers"
    assert eng.drained == len(moves) > 0 and eng.drain_drops == 0
    assert eng.gather()[0] == [], "dead cell gathers as a zero-task row"
    assert sum(len(s) for s in eng.gather()) == n_before
    _assert_oracle(eng, pools, spec)             # during the outage
    eng.recover_cell(0)
    _assert_oracle(eng, pools, spec)             # after recovery
    # THE acceptance assertion: the whole episode lived on the fast path
    assert eng.sesm.fresh_stacks == 1
    assert eng.sesm.session_rebuilds == 0


def test_drain_carries_pins_and_retry_budgets():
    """A drained RUNNING task arrives pinned at its achieved-z accuracy
    (the handover warm start) and every drained request keeps its REMAINING
    retry budget — `max_retries` is honored across the drain."""
    eng, pools, spec = _outage_engine(budget=0.8, max_retries=2)
    eng.reslice()
    running = dict(eng.cells[0].tasks)
    spent = {rid: eng.cells[0].retries_left(rid)
             for rid in eng.cells[0].live_ids()}
    moves = eng.fail_cell(0)
    for rid, dst in moves.items():
        assert dst is not None
        cell = eng.cells[dst]
        assert cell.retries_left(rid) == spent[rid], \
            "remaining retry budget must travel with the drained request"
        if rid in running:
            pin = cell.pin_of(rid)
            assert pin is not None and 0.0 < pin <= 1.0
            assert cell.carried(rid) is running[rid], \
                "runtime (job/latency history) must carry over"
    # a drained request one rejection from dropping still drops on schedule:
    # keep rejecting against the tight budget until every budget is spent
    for _ in range(4):
        eng.reslice()
    assert all(c.retries_left(rid) >= 0
               for c in eng.cells for rid in c.live_ids())
    drops_by_cell = [c.drops for c in eng.cells]
    assert drops_by_cell[0] == 0, "the FAILED cell dropped nothing"
    assert sum(drops_by_cell) > 0, \
        "retry exhaustion must still drop in the new cells"


def test_outage_with_no_live_target_drops():
    pools = scenarios.multi_cell_pools(2, seed=0)
    eng = MultiCellEngine(pools)
    eng.submit(_req("coco_bags"), 0)
    eng.submit(_req("coco_animals"), 1)
    eng.reslice()
    eng.fail_cell(1)                             # its task drains into 0
    moves = eng.fail_cell(0)                     # no cell left alive
    assert list(moves.values()) == [None, None]
    assert eng.drain_drops == 2
    assert eng.fallback_cell(0) is None
    assert eng.reslice() == [[], []]             # an all-dead tick is valid


def test_recovery_mid_tick_and_resubmission():
    """Recover between a fail and the next re-slice: the cell rejoins empty,
    accepts fresh submissions, and the next solve is oracle-pinned."""
    eng, pools, spec = _outage_engine()
    eng.reslice()
    eng.fail_cell(2)
    eng.recover_cell(2)                          # before any re-slice
    eng.submit(_req("coco_person", acc=0.25), 2)
    assert 2 in eng.live_cells and not eng.degraded
    _assert_oracle(eng, pools, spec)
    assert eng.sesm.session_rebuilds == 0


def test_fastpath_matches_rebuild_under_outage_recovery_churn():
    """Fast path and full rebuild make IDENTICAL decisions tick for tick
    through a fail → degrade → recover → restore churn trace."""
    def build():
        return _outage_engine(budget=2.0, max_retries=3)[0]

    fast, slow = build(), build()
    script = [None, ("fail", 0), None, ("scale", 0.6), None,
              ("recover", 0), ("scale", 1.0), None]
    for tick, action in enumerate(script):
        for eng in (fast, slow):
            if action == ("fail", 0):
                eng.fail_cell(0)
            elif action == ("recover", 0):
                eng.recover_cell(0)
            elif action is not None and action[0] == "scale":
                eng.set_link_budgets(scale=action[1])
        df = fast.reslice()
        ds = slow.reslice_rebuild()
        for cf, cs in zip(df, ds):
            assert [(d.admitted, d.z, d.alloc, d.evicted) for d in cf] \
                == [(d.admitted, d.z, d.alloc, d.evicted) for d in cs], tick
    assert fast.sesm.session_rebuilds == 0
    assert fast.sesm.link_updates == 2


# ------------------------------------------------- budget-only degradation

def test_budget_only_degradation_keeps_session_alive():
    """CouplingSpec.set_budgets between ticks must NOT rebuild the device
    session: one (L,) refresh (sesm.link_updates), decisions tracking the
    squeezed budget, full capacity restored the same way."""
    eng, pools, spec = _outage_engine(budget=5.0)
    nominal = [sum(d.admitted for d in ds) for ds in eng.reslice()]
    eng.reslice()
    assert eng.sesm.fresh_stacks == 1 and eng.sesm.link_updates == 0
    eng.set_link_budgets(scale=0.1)              # squeeze hard
    assert eng.degraded
    squeezed = _assert_oracle(eng, pools, spec)
    assert eng.sesm.fresh_stacks == 1, "budget change must not restack"
    assert eng.sesm.session_rebuilds == 0
    assert eng.sesm.link_updates == 1
    assert sum(d.admitted for ds in squeezed for d in ds) \
        < sum(nominal), "a 10x tighter backhaul must evict someone"
    assert eng.degraded_ticks >= 1
    eng.set_link_budgets(budgets=spec.link_capacity * 10.0)
    _assert_oracle(eng, pools, spec)
    assert eng.sesm.link_updates == 2 and eng.sesm.session_rebuilds == 0
    assert not eng.degraded


def test_set_budgets_preserves_array_identity():
    spec = CouplingSpec(np.array([4.0, 2.0]), np.ones((2, 2), bool))
    buf = spec.link_capacity
    spec.set_budgets([1.0, 0.5])
    assert spec.link_capacity is buf            # identity = same link set
    assert spec.link_capacity.tolist() == [1.0, 0.5]
    with pytest.raises(ValueError, match="topology"):
        spec.set_budgets([1.0])                 # link-set change = rebuild


def test_device_stack_budget_update_guards():
    grid = np.array([[1.0], [2.0]])
    spec = CouplingSpec(np.array([3.0]), np.ones((2, 1), bool))
    dev = empty_device_stack(grid, np.ones((2, 1)), np.ones((2, 1)), 2,
                             coupling=spec)
    dev.update_link_budgets([1.5])
    assert dev.budget_updates == 1
    assert float(dev.link_cap[0].sum()) == 1.5         # (hi, lo) pair
    with pytest.raises(ValueError, match="topology"):
        dev.update_link_budgets([1.0, 2.0])
    plain = empty_device_stack(grid, np.ones((2, 1)), np.ones((2, 1)), 2)
    with pytest.raises(ValueError, match="uncoupled"):
        plain.update_link_budgets([1.0])


# ------------------------------------------------------------- heartbeats

def test_heartbeat_silence_auto_fails_and_drains():
    """A cell that stops processing (and so stops beating) is auto-declared
    dead after `heartbeat_timeout` ticks and drained on the next re-slice;
    recovery restarts its silence window (no instant re-kill)."""
    eng, pools, spec = _outage_engine(heartbeat_timeout=2)
    eng.reslice()
    for _ in range(2):
        eng.process(0.2)
    eng.silence_cell(2)
    n_tasks = len(eng.cells[2].tasks) + eng.cells[2].queue_depth
    assert n_tasks > 0
    for _ in range(3):                           # silence outlives timeout
        eng.process(0.2)
    drained = eng.check_faults()                 # reslice() runs this too
    assert eng.dead == {2}
    assert eng.fault_log[-1]["reason"] == "heartbeat"
    assert eng.drained == n_tasks == len(drained[2])
    _assert_oracle(eng, pools, spec)             # post-drain solve is pinned
    eng.recover_cell(2)
    eng.process(0.2)
    eng.reslice()
    assert eng.dead == set(), "revived cell must not be re-declared dead"
    assert eng.sesm.session_rebuilds == 0


# ---------------------------------------------------------- priority tiers

def test_tier_shedding_lowest_priority_first_within_budgets():
    """Under queue pressure the engine sheds lowest-tier queued requests
    first — newest first within a tier, per-tier drop budgets honored, and
    unbudgeted (high-priority) tiers never shed."""
    pools = scenarios.multi_cell_pools(1, seed=0)
    eng = MultiCellEngine(pools, max_retries=9,
                          tier_policy=TierPolicy(queue_threshold=2,
                                                 drop_budgets={2: 2, 1: 1}))
    reqs = [_req("coco_bags", acc=0.999, tier=t)   # unreachable: all queue
            for t in (0, 0, 1, 1, 2, 2, 2)]
    for r in reqs:
        eng.submit(r, 0)
    eng.reslice()                                # shed runs pre-solve
    cell = eng.cells[0]
    shed = list(cell.dropped)
    # budgets: at most 2 of tier 2 (the newest two) and 1 of tier 1
    assert [r.tier for r in shed] == [2, 2, 1]
    assert shed[0].request_id == reqs[6].request_id, "newest-first in tier"
    assert cell.sheds == 3 and cell.sheds_by_tier == {2: 2, 1: 1}
    assert cell.drops == 3, "sheds are drops (loops diff cell.drops)"
    # tier 0 never configured a budget → untouched even under pressure
    live = [r.tier for r in cell.pending]
    assert live.count(0) == 2
    # engine-wide totals surface the shed attribution
    totals = eng.metrics()["totals"]
    assert totals["sheds"] == 3
    assert totals["sheds_by_tier"] == {2: 2, 1: 1}


# ------------------------------------------------------------- error paths

def test_fault_plane_error_paths():
    eng, pools, spec = _outage_engine()
    eng.reslice()
    with pytest.raises(KeyError, match="not running"):
        eng.cells[0].hand_out(10**9)
    with pytest.raises(KeyError, match="not queued"):
        eng.cells[0].shed(10**9)
    with pytest.raises(ValueError, match="outside"):
        eng.fail_cell(7)
    with pytest.raises(ValueError, match="not failed"):
        eng.recover_cell(1)
    eng.fail_cell(1)
    with pytest.raises(ValueError, match="already failed"):
        eng.fail_cell(1)
    with pytest.raises(ValueError, match="failed"):
        eng.submit(_req("coco_bags"), 1)
    rid = next(iter(eng.cells[0].tasks), None) \
        or next(iter(eng.cells[0].live_ids()))
    with pytest.raises(ValueError, match="failed"):
        eng.handover(rid, 0, 1)
    with pytest.raises(ValueError, match="exactly one"):
        eng.set_link_budgets()
    with pytest.raises(ValueError, match="exactly one"):
        eng.set_link_budgets(np.array([1.0]), scale=0.5)
    plain = MultiCellEngine(scenarios.multi_cell_pools(2, seed=0))
    with pytest.raises(ValueError, match="no coupling"):
        plain.set_link_budgets(scale=0.5)


# ---------------------------------------------------------- fault schedules

def test_fault_schedules_deterministic_and_composable():
    a = scenarios.random_outage_schedule(4, 20, n_outages=2, duration=3,
                                         seed=5, spare_cells=(0,))
    assert a == scenarios.random_outage_schedule(4, 20, n_outages=2,
                                                 duration=3, seed=5,
                                                 spare_cells=(0,))
    assert all(isinstance(ev, CellFault) for evs in a.values() for ev in evs)
    cells = {ev.cell for evs in a.values() for ev in evs}
    assert 0 not in cells and cells <= {1, 2, 3}
    fails = sum(ev.failed for evs in a.values() for ev in evs)
    recovers = sum(not ev.failed for evs in a.values() for ev in evs)
    assert fails == recovers == 2

    b = scenarios.stepped_link_degradation(20, start=4, n_steps=3, floor=0.4)
    assert all(isinstance(ev, LinkScale) for evs in b.values() for ev in evs)
    scales = {s: evs[0].scale for s, evs in b.items()}
    assert scales[4] == pytest.approx(0.8)
    assert scales[5] == pytest.approx(0.6)
    assert scales[6] == pytest.approx(0.4)
    assert scales[7] == 1.0, "recover=True restores nominal"

    c = scenarios.flash_crowd(3, 20, step=2, duration=2, cells=[1],
                              arrival_rate=6.0, seed=3)
    assert c == scenarios.flash_crowd(3, 20, step=2, duration=2, cells=[1],
                                      arrival_rate=6.0, seed=3)
    assert all(isinstance(ev, Arrival) and ev.cell == 1
               for evs in c.values() for ev in evs)

    merged = scenarios.compose_faults(a, b, c)
    assert sum(map(len, merged.values())) \
        == sum(map(len, a.values())) + sum(map(len, b.values())) \
        + sum(map(len, c.values()))
    assert isinstance(merged[4][0], CellFault if 4 in a else LinkScale)

    with pytest.raises(ValueError, match="empty"):
        scenarios.outage_schedule([(0, 5, 5)])
    with pytest.raises(ValueError, match="spared"):
        scenarios.random_outage_schedule(2, 10, spare_cells=(0, 1))
    with pytest.raises(ValueError, match="floor"):
        scenarios.stepped_link_degradation(10, floor=1.5)


# ------------------------------------------------- driver + SLA scorecard

def test_driver_canonical_outage_scorecard_floors():
    """The canonical outage scenario end-to-end: one cell fails and
    recovers mid-run under tiered traffic and pressure shedding. The
    scorecard must hold the high-priority tier's floors — admission rate
    and deadline-hit rate — and account every lost/drained task."""
    pools = scenarios.multi_cell_pools(3, seed=0)
    spec = CouplingSpec(np.array([8.0]), np.ones((3, 1), bool))
    eng = MultiCellEngine(pools, coupling=spec, max_retries=3,
                          tier_policy=TierPolicy(queue_threshold=3,
                                                 drop_budgets={1: 2, 2: 4}))
    faults = scenarios.compose_faults(
        scenarios.outage_schedule([(1, 3, 7)]),
        scenarios.stepped_link_degradation(10, start=4, n_steps=2,
                                           floor=0.6))
    recs = drive_closed_loop(eng, 10, arrival_rate=3.0, seed=4,
                             faults=faults, tiers=[0, 1, 2], process=True,
                             wall_dt=0.2)
    assert len(recs) == 30
    assert recs == drive_closed_loop(           # deterministic per seed
        _rebuild_canonical(), 10, arrival_rate=3.0, seed=4,
        faults=scenarios.compose_faults(
            scenarios.outage_schedule([(1, 3, 7)]),
            scenarios.stepped_link_degradation(10, start=4, n_steps=2,
                                               floor=0.6)),
        tiers=[0, 1, 2], process=True, wall_dt=0.2)
    dead_steps = {r["step"] for r in recs if r["dead"]}
    assert dead_steps == set(range(3, 7))
    assert all(r["degraded"] for r in recs if 3 <= r["step"] < 7)
    sc = sla_scorecard(eng, recs)
    t0 = sc["tiers"][0]
    # the floors: tier 0 is never shed and keeps strong service through the
    # outage (values have slack over the observed ~0.5 / 1.0)
    assert t0["sheds"] == 0
    assert t0["admission_rate"] >= 0.35
    assert t0["latency_samples"] > 0
    assert t0["deadline_hit_rate"] >= 0.9
    assert sc["run"]["degraded_steps"] == 4
    assert sc["run"]["dead_cells"] == []
    assert sc["run"]["drained"] > 0
    assert sc["run"]["steps"] == 10
    # shed accounting flows through to the per-step records
    assert sum(r["shed"] for r in recs) == sc["run"]["sheds"]


def _rebuild_canonical():
    pools = scenarios.multi_cell_pools(3, seed=0)
    spec = CouplingSpec(np.array([8.0]), np.ones((3, 1), bool))
    return MultiCellEngine(pools, coupling=spec, max_retries=3,
                           tier_policy=TierPolicy(queue_threshold=3,
                                                  drop_budgets={1: 2, 2: 4}))


def test_metrics_totals_aggregate_across_cells():
    eng, pools, spec = _outage_engine(budget=0.8, max_retries=1)
    for _ in range(4):
        eng.reslice()
    m = eng.metrics()
    assert set(range(3)) < set(m)               # per-cell dicts still there
    t = m["totals"]
    assert t["drops"] == sum(c.drops for c in eng.cells) > 0
    assert t["evictions"] == sum(c.evictions for c in eng.cells)
    assert t["retry_depth"] == sum(c.queue_depth for c in eng.cells)
    assert t["running"] == sum(len(c.tasks) for c in eng.cells)
    assert sum(t["drops_by_tier"].values()) == t["drops"]
    assert t["dead_cells"] == [] and not t["degraded"]
    assert t["session_rebuilds"] == 0
