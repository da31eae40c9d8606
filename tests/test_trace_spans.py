"""Program spans and the admission-round counter (``repro.trace``).

One ``solve_device_batch`` and one ``MultiCellEngine`` tick are captured
under ``jax.profiler.start_trace`` and read back with
``jax.profiler.ProfileData``: the span names, their nesting and their
metadata are the ones ``repro.trace`` documents. The ``rounds`` the device
loop reports is checked against a recount from the decisions.
"""
import gc
import glob
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro import trace
from repro.core import (CouplingSpec, build_instance, device_stack,
                        device_stack_sharded, scenarios, solve_device_batch,
                        solve_greedy_batch, solve_sharded_batch,
                        stack_instances)
from repro.core.events import Arrival
from repro.serving import MultiCellEngine, SliceRequest


def _capture(tmp_path, fn):
    """Run ``fn`` under a profiler session; return its result and the host
    spans named ``repro.*`` as (name, start_ns, end_ns, line, stats)."""
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
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                          line.name, dict(e.stats)) for e in line.events
                         if e.name.startswith("repro."))
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _parent(spans, child):
    """The innermost span of the same thread that encloses ``child``."""
    outer = [s for s in spans if s is not child and s[3] == child[3]
             and s[1] <= child[1] and child[2] <= s[2]]
    return max(outer, key=lambda s: (s[1], -s[2]))[0] if outer else None


def _instances(n=6, seed=7):
    pool = scenarios.numerical_pool(2)
    rng = np.random.default_rng(seed)
    return [build_instance(pool, scenarios.numerical_tasks(
        int(rng.integers(3, 40)), ("low", "med", "high")[i % 3],
        ("low", "high")[i % 2], seed=seed + i)) for i in range(n)]


def _same_decisions(res, stacked, sols, grid):
    for b, (inst, sol) in enumerate(zip(stacked.instances, sols)):
        t = inst.num_tasks
        adm = res["admitted"][b, :t]
        assert np.array_equal(adm, sol.admitted), b
        alloc = grid[np.clip(res["alloc_idx"][b, :t], 0, None)] * adm[:, None]
        assert np.array_equal(alloc, sol.alloc), b


def test_solve_spans_name_and_order_the_phases(tmp_path):
    stacked = stack_instances(_instances())
    dev = device_stack(stacked)
    solve_device_batch(dev)                      # compile outside the trace
    res, spans = _capture(tmp_path, lambda: solve_device_batch(dev))
    names = [s[0] for s in spans]
    assert names == ["repro.solve.launch", "repro.solve.wait",
                     "repro.solve.fetch", "repro.solve.unpack"]
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1]                      # one after the other
    assert all(s[4]["B"] == stacked.batch_size for s in spans)
    assert spans[0][4]["program"] == "jit__serve_batch"
    assert res["rounds"] > 0


def test_tick_spans_nest_as_documented(tmp_path):
    pools = scenarios.multi_cell_pools(3, seed=2)
    spec = CouplingSpec(np.array([1.0]), np.ones((3, 1), bool),
                        names=("backhaul",))
    eng = MultiCellEngine(pools, coupling=spec)

    def req(app, acc):
        return SliceRequest("object-recognition", "yolox", app,
                            max_latency_s=0.7, min_accuracy=acc,
                            jobs_per_sec=5.0)

    for c in range(3):
        eng.submit(req("coco_bags", 0.35), c)
        eng.submit(req("coco_animals", 0.5), c)
    eng.reslice()                                # compile outside the trace
    rounds0 = eng.metrics()["totals"]["rounds"]
    events = [Arrival(req("cityscapes_flat", 0.35), c) for c in range(3)]

    def tick():
        eng.ingest(events)
        return eng.reslice_commit(eng.reslice_dispatch())

    decisions, spans = _capture(tmp_path, tick)
    parents = {s[0]: _parent(spans, s) for s in spans}
    assert parents == {
        "repro.tick.ingest": None,
        "repro.tick.dispatch": None,
        "repro.tick.sync_slots": "repro.tick.dispatch",
        # the third request per cell outgrows the Tmax bucket of two
        "repro.sesm.build_session": "repro.tick.dispatch",
        "repro.sesm.sync_rows": "repro.tick.dispatch",
        "repro.solve.launch": "repro.tick.dispatch",
        "repro.tick.commit": None,
        "repro.solve.wait": "repro.tick.commit",
        "repro.solve.fetch": "repro.tick.commit",
        "repro.solve.unpack": "repro.tick.commit",
        "repro.sesm.decisions": "repro.tick.commit",
        "repro.tick.apply": "repro.tick.commit",
        # the new session's first commit freezes the heap
        "repro.tick.freeze_heap": "repro.tick.commit",
    }
    assert len(spans) == len(parents)            # each phase once per tick
    top = [s[0] for s in spans if parents[s[0]] is None]
    assert top == ["repro.tick.ingest", "repro.tick.dispatch",
                   "repro.tick.commit"]
    assert all(s[4]["tick"] == eng.tick for s in spans
               if s[0].startswith("repro.tick."))
    launch = next(s for s in spans if s[0] == "repro.solve.launch")
    assert launch[4]["program"] == "jit__serve_batch_coupled"
    assert launch[4]["B"] == 3
    build = next(s for s in spans if s[0] == "repro.sesm.build_session")
    for s in (launch, build):
        assert (s[4]["A"], s[4]["grids"]) == (300, 1)
    sync = next(s for s in spans if s[0] == "repro.sesm.sync_rows")
    assert sync[4]["masked"] == 0
    assert sum(len(ds) for ds in decisions) == 9
    assert eng.metrics()["totals"]["rounds"] > rounds0
    assert eng.heap_freezes == 2                 # one per session


def test_gc_spans_record_collections_and_install_once(tmp_path):
    had = trace._gc_span in gc.callbacks
    try:
        trace.install_gc_spans()
        trace.install_gc_spans()
        assert gc.callbacks.count(trace._gc_span) == 1
        _, spans = _capture(tmp_path, lambda: gc.collect(1))
        names = [s[0] for s in spans]
        # the collection asked for, and any the interpreter made meanwhile
        assert "repro.gc.gen1" in names
        assert all(n.startswith("repro.gc.gen") for n in names)
        assert not trace._gc_open
    finally:
        if not had:
            gc.callbacks.remove(trace._gc_span)


def test_rounds_recount_uncoupled():
    """One round per admission, plus one retiring round for an instance
    that leaves a candidate unadmitted; the loop runs to the slowest."""
    stacked = stack_instances(_instances(10, seed=3))
    dev = device_stack(stacked)
    res = solve_device_batch(dev)
    B = stacked.batch_size
    alive0 = np.asarray(dev.alive0)[:B]
    adm = res["admitted"]
    per = adm.sum(axis=1) + (alive0 & ~adm).any(axis=1)
    assert res["rounds"] == int(per.max())
    assert res["alloc_idx"].shape == adm.shape == (B, stacked.max_tasks)
    _same_decisions(res, stacked, solve_greedy_batch(stacked), stacked.grid)


def test_rounds_bound_coupled_and_sharded(cells_mesh):
    """A coupling group admits one task per round, so the loop takes at
    least as many rounds as any group admits; the sharded serve reports the
    same count (the largest over shards) and the same decisions."""
    insts, _ = scenarios.multi_cell_trace(6, 2, seed=3, shared_backhaul=6.0)
    stacked = stack_instances(insts)
    res = solve_device_batch(device_stack(stacked))
    group = np.asarray(stacked.coupling.groups())
    per_group = np.bincount(group, weights=res["admitted"].sum(axis=1))
    assert per_group.max() > 1
    assert res["rounds"] >= per_group.max()
    _same_decisions(res, stacked, solve_greedy_batch(stacked), stacked.grid)
    shd = solve_sharded_batch(device_stack_sharded(stacked, cells_mesh))
    assert shd["rounds"] == res["rounds"]
    assert np.array_equal(shd["admitted"], res["admitted"])
    assert shd["alloc_idx"].shape == res["alloc_idx"].shape


@pytest.mark.parametrize("coupled", [False, True])
def test_greedy_batch_entries_keep_their_outputs(coupled):
    """The plain batch entries drop the round count: three outputs, as
    before."""
    from repro.core.greedy import _greedy_jax_batch, _greedy_jax_batch_coupled

    if coupled:
        insts, _ = scenarios.multi_cell_trace(4, 2, seed=1,
                                              shared_backhaul=6.0)
    else:
        insts = _instances(4)
    d = device_stack(stack_instances(insts))
    args = (d.lat_ok, d.grid, d.price, d.capacity, d.alive0, d.cost)
    if coupled:
        out = _greedy_jax_batch_coupled(*args, d.link_load, d.link_cap,
                                        d.incidence, d.group)
    else:
        out = _greedy_jax_batch(*args)
    assert len(out) == 3
