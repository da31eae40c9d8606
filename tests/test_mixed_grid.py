"""Cells on different allocation grids in one engine and one coupled group.

A two-layer deployment in miniature: each site holds one macro cell (every
whole level, A = 300) and three micro cells on a coarser grid (steps of 2,
A = 40), all four behind the site's link. The engine solves them on the
union grid (A = 320), each cell masked to its own allocations; its decisions
must equal the coupled numpy oracle, which solves each cell on its own grid.
"""
import dataclasses
import glob
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import (CouplingSpec, ResourcePool, make_allocation_grid,
                        solve_coupled_ref, task_feasibility_rows,
                        union_allocation_grid)
from repro.serving import MultiCellEngine, SliceRequest

MACRO = (np.arange(1.0, 16.0), np.arange(1.0, 21.0))
MICRO = (np.arange(2.0, 17.0, 2.0), np.arange(2.0, 11.0, 2.0))
APPS = [("coco_all", 0.35), ("coco_bags", 0.35), ("coco_person", 0.35),
        ("cityscapes_all", 0.5), ("cityscapes_flat", 0.5),
        ("cityscapes_person", 0.5)]


def _pools(sites: int, seed: int = 0, layers=("macro", "micro")):
    rng = np.random.default_rng(seed)
    pools = []
    for _ in range(sites):
        for layer in [layers[0]] + [layers[-1]] * 3:
            base = np.array([15.0, 20.0]) if layer == "macro" \
                else np.array([16.0, 10.0])
            cap = np.maximum(np.round(base * rng.uniform(0.75, 1.25, 2)), 2.0)
            pools.append(ResourcePool(
                names=("rbg", "gpu"), capacity=cap, price=1.0 / cap,
                levels=MACRO if layer == "macro" else MICRO))
    return pools


def _site_links(sites: int, budget: float) -> CouplingSpec:
    inc = np.repeat(np.eye(sites, dtype=bool), 4, axis=0)
    return CouplingSpec(np.full(sites, budget), inc)


def _request(rng) -> SliceRequest:
    app, acc = APPS[int(rng.integers(len(APPS)))]
    return SliceRequest("object-recognition", "yolox", app,
                        max_latency_s=0.7, min_accuracy=acc,
                        jobs_per_sec=5.0)


def _oracle(eng, pools, spec):
    """The coupled oracle on the engine's gathered candidate sets, each cell
    on its own pool and grid."""
    sets = eng.gather()
    insts = [dataclasses.replace(eng.sdla.build_instance(rs, pools[c]),
                                 coupling=spec.row(c))
             for c, rs in enumerate(sets)]
    return sets, solve_coupled_ref(insts)


def test_union_grid_keeps_each_cells_order():
    macro, micro = _pools(1)[:2]
    union = union_allocation_grid([macro, micro, macro])
    assert union.grids == 2 and union.grid.shape == (320, 2)
    assert union.mask.shape == (3, 320)
    assert union.mask.sum(axis=1).tolist() == [300, 40, 300]
    for k, levels in enumerate([MACRO, MICRO, MACRO]):
        # the masked union, in order, is the cell's own grid
        assert np.array_equal(union.grid[union.mask[k]],
                              make_allocation_grid(levels))
    same = union_allocation_grid([macro, macro])
    assert same.mask is None and same.grids == 1
    assert np.array_equal(same.grid, make_allocation_grid(MACRO))
    cpu = ResourcePool(names=("rbg", "cpu"), capacity=micro.capacity,
                       price=micro.price, levels=MICRO)
    wide = ResourcePool(names=("rbg", "gpu", "cpu"), capacity=np.ones(3),
                        price=np.ones(3), levels=MACRO + (MICRO[1],))
    for other in (cpu, wide):
        with pytest.raises(ValueError, match="same resources"):
            union_allocation_grid([macro, other])


@pytest.mark.parametrize("metro", [False, True])
def test_two_layer_closed_loop_matches_oracle(metro, cells_mesh):
    """Six closed-loop ticks over two sites, seeded arrivals, binding site
    links, retries and drops: every tick's admissions and allocations equal
    the oracle's, and no micro cell is admitted off its own grid."""
    rng = np.random.default_rng(2412)
    pools = _pools(2, seed=5)
    spec = _site_links(2, budget=8.0)
    eng = MultiCellEngine(pools, coupling=spec, max_retries=2,
                          mesh=cells_mesh if metro else None)
    micro_grid = {tuple(r) for r in make_allocation_grid(MICRO).tolist()}
    rejected = 0
    for tick in range(6):
        for c in range(len(pools)):
            for _ in range(int(rng.integers(1, 4))):
                eng.submit(_request(rng), c)
        sets, refs = _oracle(eng, pools, spec)
        decisions = eng.reslice()
        for c, (rs, ds, ref) in enumerate(zip(sets, decisions, refs)):
            assert [d.request.request_id for d in ds] == \
                [r.request_id for r in rs]
            assert [d.admitted for d in ds] == ref.admitted.tolist(), \
                (tick, c)
            got = np.array([[d.alloc["rbg"], d.alloc["gpu"]] for d in ds])
            assert np.array_equal(got, ref.alloc), (tick, c)
            if pools[c].levels is MICRO:
                assert all(tuple(a) in micro_grid
                           for a, d in zip(got.tolist(), ds) if d.admitted)
            rejected += sum(not d.admitted for d in ds)
    sess = eng.sesm._serve_session
    assert sess.dev.grids == 2 and sess.grid.shape == (320, 2)
    assert eng.sesm.session_rebuilds + 1 == eng.sesm.fresh_stacks
    assert rejected > 0 and sum(cell.drops for cell in eng.cells) > 0
    # the union has RBG 16 (micro) and odd levels (macro): both layers mask
    assert 0 < eng.sesm.masked_rows <= eng.sesm.delta_rows
    # the site budgets bind: alone, the cells admit more
    free = MultiCellEngine(pools, max_retries=2)
    for c, rs in enumerate(eng.gather()):
        for r in rs:
            free.submit(dataclasses.replace(r), c)
    n_free = sum(d.admitted for ds in free.reslice() for d in ds)
    assert n_free > sum(len(cell.tasks) for cell in eng.cells)


@pytest.mark.parametrize("metro", [False, True])
def test_one_grid_session_is_unmasked(metro, cells_mesh):
    """Every cell on one grid: the session's grid is that grid, there is no
    mask, the device rows are the pipeline's own and no row is masked."""
    pools = _pools(2, seed=1, layers=("macro",))
    eng = MultiCellEngine(pools, coupling=_site_links(2, 20.0),
                          mesh=cells_mesh if metro else None)
    rng = np.random.default_rng(7)
    for c in range(len(pools)):
        for _ in range(3):
            eng.submit(_request(rng), c)
    eng.reslice()
    sess = eng.sesm._serve_session
    assert sess.cell_mask is None and sess.dev.grids == 1
    assert np.array_equal(sess.grid, make_allocation_grid(MACRO))
    lat_ok = np.asarray(sess.dev.lat_ok)
    sdla = eng.sdla
    for c, rs in enumerate(eng.gather()):
        want = task_feasibility_rows(sdla.task_set(rs), sess.z_grid,
                                     sess.grid, sdla.lat_params).lat_ok
        row = sess.dev.padded_of[c] if metro else c
        assert np.array_equal(lat_ok[row, :len(rs)], want)
    assert eng.sesm.masked_rows == 0 and eng.sesm.delta_rows == 24


def _capture(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return {e.name: dict(e.stats)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")}


def test_spans_and_counter_record_the_union(tmp_path):
    pools = _pools(1, seed=3)
    eng = MultiCellEngine(pools, coupling=_site_links(1, 20.0))
    rng = np.random.default_rng(11)
    for c in range(len(pools)):
        eng.submit(_request(rng), c)
    eng.reslice()                                # compile outside the trace
    before = eng.sesm.masked_rows
    assert before == 4                   # neither layer's grid is the union

    def tick():
        for c in range(len(pools)):              # outgrows the Tmax bucket
            eng.submit(_request(rng), c)
            eng.submit(_request(rng), c)
        eng.reslice()

    spans = _capture(tmp_path, tick)
    for name in ("repro.sesm.build_session", "repro.solve.launch"):
        assert (spans[name]["A"], spans[name]["grids"]) == (320, 2), name
    assert spans["repro.sesm.sync_rows"]["masked"] == 12
    assert eng.sesm.masked_rows == before + 12


def test_semantic_drift_keeps_the_masks():
    """A drift recomputes the moved rows in place: they stay on their cells'
    own grids, and the decisions stay the oracle's under the drifted
    curves."""
    pools = _pools(1, seed=9)
    spec = _site_links(1, budget=8.0)
    eng = MultiCellEngine(pools, coupling=spec)
    rng = np.random.default_rng(5)
    for c in range(len(pools)):
        for _ in range(3):
            eng.submit(_request(rng), c)
    eng.reslice()
    for scale in (0.85, 1.1):
        eng.shift_semantics(scale=scale)
        sets, refs = _oracle(eng, pools, spec)
        decisions = eng.reslice()
        for ds, ref in zip(decisions, refs):
            assert [d.admitted for d in ds] == ref.admitted.tolist()
            got = np.array([[d.alloc["rbg"], d.alloc["gpu"]] for d in ds])
            assert np.array_equal(got, ref.alloc)
    assert eng.sesm.semantic_updates == 2 and eng.sesm.session_rebuilds == 0


@pytest.mark.parametrize("inner", ["jnp", "pallas"])
def test_union_stack_matches_oracle_in_both_rounds(inner):
    """The masks reach the packed feasibility bits, so the jnp round and the
    fused Pallas round both keep each cell on its own grid."""
    from repro.core import build_instance, scenarios, solve_greedy_batch
    from repro.core import stack_instances

    pools = _pools(1, seed=4)
    spec = _site_links(1, budget=6.0)
    insts = [build_instance(p, scenarios.numerical_tasks(8, "med", "high",
                                                         seed=20 + c),
                            coupling=spec.row(c))
             for c, p in enumerate(pools)]
    st = stack_instances(insts)
    assert st.grids == 2 and st.num_allocs == 320
    sols = solve_greedy_batch(st, inner=inner)
    for sol, ref in zip(sols, solve_coupled_ref(insts)):
        assert (sol.admitted == ref.admitted).all()
        assert np.array_equal(sol.alloc, ref.alloc)
    assert sum(s.num_allocated for s in sols) > 0
