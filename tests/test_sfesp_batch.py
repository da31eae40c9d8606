"""Batched sweep engine vs the per-instance numpy oracle (Alg. 1)."""
import dataclasses
from unittest import mock

import numpy as np
import pytest

from repro.core import (build_instance, device_stack, greedy, next_pow2,
                        restack, scenarios, solve_device_batch, solve_greedy,
                        solve_greedy_batch, solve_greedy_jax,
                        solve_greedy_many, stack_instances)


def _random_instances():
    """>= 8 instances with heterogeneous T, thresholds and fps, one pool."""
    pool = scenarios.numerical_pool(2)
    rng = np.random.default_rng(7)
    insts = []
    for i in range(10):
        n = int(rng.integers(1, 45))
        acc = ("low", "med", "high")[i % 3]
        lat = ("low", "high")[i % 2]
        insts.append(build_instance(pool, scenarios.numerical_tasks(
            n, acc, lat, seed=i, jobs_per_sec=float(rng.uniform(1.0, 10.0)))))
    return insts


def _oracle_rounds(inst, ref, iterations, *, semantic, flexible):
    """Rounds the batched loop spends on ``inst``, from the numpy oracle.

    MinRes drops an infeasible candidate in the round it becomes so, as
    Alg. 1 does: the oracle's own ``iterations``. The flexible round drops
    one only when its instance has nothing feasible left: one round per
    admission, plus one retiring round if a candidate was never admitted.
    """
    if not flexible:
        return iterations
    lat, z_idx = greedy._select_tables(inst, semantic)
    alive0 = (z_idx >= 0) & (lat <= inst.tasks.max_latency[:, None]).any(1)
    return int(ref.admitted.sum()) + int((alive0 & ~ref.admitted).any())


def _assert_matches_oracle(insts, *, semantic=True, flexible=True,
                           tmax=None, pad_batch_to=None):
    stacked = stack_instances(insts, tmax=tmax)
    sols = solve_greedy_batch(stacked, semantic=semantic, flexible=flexible,
                              pad_batch_to=pad_batch_to)
    assert len(sols) == len(insts)
    rounds = 0
    for inst, sol in zip(insts, sols):
        # one primal_gradient call per iteration of the oracle's loop
        with mock.patch.object(greedy, "primal_gradient",
                               wraps=greedy.primal_gradient) as pg:
            ref = solve_greedy(inst, semantic=semantic, flexible=flexible)
        assert sol.admitted.shape == (inst.num_tasks,)
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)
        assert np.allclose(sol.z, ref.z)
        assert sol.objective == pytest.approx(ref.objective)
        assert (sol.satisfied == ref.satisfied).all()
        rounds = max(rounds, _oracle_rounds(inst, ref, pg.call_count,
                                            semantic=semantic,
                                            flexible=flexible))
    # the loop runs to the slowest instance; inert pad rows add no round
    dev = device_stack(stacked, semantic=semantic, pad_batch_to=pad_batch_to)
    assert solve_device_batch(dev, flexible=flexible)["rounds"] == rounds


def _odd_tmax_instances():
    """Task counts 13 and 5: Tmax 13, not a multiple of 8."""
    pool = scenarios.numerical_pool(2)
    return [build_instance(pool, scenarios.numerical_tasks(n, "low", "high",
                                                           seed=n))
            for n in (13, 5)]


def _mass_drop_instances():
    """The middle instance's pool fits no allocation (0.5 RBG, one level
    is 1): its every candidate retires in round 1 while the others admit."""
    pool = scenarios.numerical_pool(2)
    tiny = dataclasses.replace(pool, capacity=np.array([0.5, 20.0]))
    return [build_instance(p, scenarios.numerical_tasks(9, "low", "high",
                                                        seed=s))
            for s, p in enumerate((pool, tiny, pool))]


def _no_alive_instances():
    """An instance with no candidate (accuracy out of reach) beside
    feasible ones, the device batch padded with inert rows."""
    pool = scenarios.numerical_pool(2)
    tasks = scenarios.numerical_tasks(7, "med", "high", seed=4)
    hopeless = dataclasses.replace(tasks, min_accuracy=np.full(7, 0.99))
    return [build_instance(pool, t) for t in (
        hopeless, scenarios.numerical_tasks(11, "low", "high", seed=5),
        tasks)]


@pytest.mark.parametrize("case,kw", [
    ("random", {}),
    ("odd_tmax", {}),
    ("odd_tmax", {"tmax": 21}),
    ("mass_drop", {}),
    ("no_alive", {"pad_batch_to": 8}),
])
def test_batched_matches_oracle_randomized(case, kw):
    insts = {"random": _random_instances, "odd_tmax": _odd_tmax_instances,
             "mass_drop": _mass_drop_instances,
             "no_alive": _no_alive_instances}[case]()
    _assert_matches_oracle(insts, **kw)
    if case == "mass_drop":
        alive0 = np.asarray(device_stack(stack_instances(insts)).alive0)
        assert alive0[1].any()
        assert solve_greedy_batch(insts)[1].num_allocated == 0


@pytest.mark.parametrize("semantic", [True, False])
@pytest.mark.parametrize("flexible", [True, False])
def test_batched_matches_oracle_all_quadrants(semantic, flexible):
    insts = _random_instances()[:6]
    _assert_matches_oracle(insts, semantic=semantic, flexible=flexible)


def test_batched_single_task_instances():
    pool = scenarios.numerical_pool(2)
    insts = [build_instance(pool, scenarios.numerical_tasks(1, a, l, seed=s))
             for s, (a, l) in enumerate([("low", "high"), ("med", "low"),
                                         ("high", "high")])]
    _assert_matches_oracle(insts)
    sols = solve_greedy_batch(insts)
    assert all(s.admitted.shape == (1,) for s in sols)


def test_batched_all_infeasible_instance():
    pool = scenarios.numerical_pool(2)
    # unreachable accuracy (z* = -1 for every task) → nothing admitted
    tasks = scenarios.numerical_tasks(12, "med", "high", seed=0)
    hopeless_acc = dataclasses.replace(
        tasks, min_accuracy=np.full(12, 0.99))
    # unreachable latency → lat_ok empty for every task
    hopeless_lat = dataclasses.replace(
        tasks, max_latency=np.full(12, 1e-4))
    feasible = scenarios.numerical_tasks(20, "low", "high", seed=1)
    insts = [build_instance(pool, t)
             for t in (hopeless_acc, feasible, hopeless_lat)]
    _assert_matches_oracle(insts)
    sols = solve_greedy_batch(insts)
    assert sols[0].num_allocated == 0
    assert sols[2].num_allocated == 0
    assert sols[1].num_allocated > 0


def test_batched_heterogeneous_capacities():
    """Multi-cell: same level grid, different capacities/prices per cell."""
    insts, _ = scenarios.multi_cell_trace(3, 3, seed=5)
    assert len({tuple(i.pool.capacity) for i in insts}) > 1
    _assert_matches_oracle(insts)
    _assert_matches_oracle(insts, flexible=False)


def test_batched_four_resource_pool():
    pool = scenarios.numerical_pool(4)
    insts = [build_instance(pool, scenarios.numerical_tasks(n, "med", "high",
                                                            seed=n))
             for n in (5, 15, 30)]
    _assert_matches_oracle(insts)


def test_stack_rejects_mismatched_grids():
    a = build_instance(scenarios.numerical_pool(2),
                       scenarios.numerical_tasks(5, "med", "high"))
    b = build_instance(scenarios.numerical_pool(4),
                       scenarios.numerical_tasks(5, "med", "high"))
    with pytest.raises(ValueError, match="allocation grid"):
        stack_instances([a, b])


def test_stack_padding_layout():
    insts = _random_instances()[:4]
    st = stack_instances(insts)
    tmax = max(i.num_tasks for i in insts)
    assert st.batch_size == 4 and st.max_tasks == tmax
    for b, inst in enumerate(insts):
        t = inst.num_tasks
        assert st.task_mask[b, :t].all() and not st.task_mask[b, t:].any()
        assert np.isinf(st.lat[b, t:]).all()
        assert (st.z_star_idx[b, t:] == -1).all()
    assert st.num_tasks.tolist() == [i.num_tasks for i in insts]


def test_stack_tmax_bucket_padding():
    insts = _random_instances()[:4]
    st = stack_instances(insts, tmax=64)
    assert st.max_tasks == 64
    for b, inst in enumerate(insts):
        t = inst.num_tasks
        assert st.task_mask[b, :t].all() and not st.task_mask[b, t:].any()
        assert np.isinf(st.lat[b, t:]).all()
    _assert_matches_oracle(st.instances)
    sols = solve_greedy_batch(st)
    for inst, sol in zip(insts, sols):
        ref = solve_greedy(inst)
        assert (sol.admitted == ref.admitted).all()
    with pytest.raises(ValueError, match="tmax"):
        stack_instances(insts, tmax=2)


def test_pad_batch_to_is_inert():
    insts = _random_instances()[:3]
    st = stack_instances(insts)
    plain = solve_greedy_batch(st)
    padded = solve_greedy_batch(st, pad_batch_to=8)
    assert len(padded) == len(insts)
    for a, b in zip(plain, padded):
        assert (a.admitted == b.admitted).all()
        assert np.allclose(a.alloc, b.alloc)
        assert a.objective == pytest.approx(b.objective)


def test_next_pow2():
    assert [next_pow2(n) for n in (0, 1, 2, 3, 17, 64)] == [1, 1, 2, 4, 32, 64]


# ---------------------------------------------------------------------------
# restack: buffer-reusing host fast path
# ---------------------------------------------------------------------------

def test_restack_reuses_buffers_and_matches_oracle():
    insts = _random_instances()
    first, second = insts[:5], insts[5:]
    st = stack_instances(first, tmax=64)
    st2 = restack(st, second[:5])
    assert st2.lat is st.lat and st2.task_mask is st.task_mask
    assert st2.capacity is st.capacity
    for inst, sol in zip(second[:5], solve_greedy_batch(st2)):
        ref = solve_greedy(inst)
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)
    # rows of the longest first-batch instance must have been fully cleared
    for b, inst in enumerate(second[:5]):
        t = inst.num_tasks
        assert not st2.task_mask[b, t:].any()
        assert np.isinf(st2.lat[b, t:]).all()
        assert (st2.z_star_idx[b, t:] == -1).all()


def test_restack_validates_contract():
    pool2, pool4 = scenarios.numerical_pool(2), scenarios.numerical_pool(4)
    insts = [build_instance(pool2, scenarios.numerical_tasks(6, "med", "high",
                                                             seed=s))
             for s in range(3)]
    st = stack_instances(insts)
    with pytest.raises(ValueError, match="batch size"):
        restack(st, insts[:2])
    with pytest.raises(ValueError, match="allocation grid"):
        restack(st, [build_instance(pool4, scenarios.numerical_tasks(
            6, "med", "high", seed=s)) for s in range(3)])
    with pytest.raises(ValueError, match="does not fit"):
        restack(st, [build_instance(pool2, scenarios.numerical_tasks(
            12, "med", "high", seed=s)) for s in range(3)])


# ---------------------------------------------------------------------------
# solve_greedy_many: grid-grouped dispatcher
# ---------------------------------------------------------------------------

def _mixed_grid_instances():
    """Instances over three distinct allocation grids, interleaved."""
    pools = [scenarios.numerical_pool(2), scenarios.numerical_pool(4)]
    pools += scenarios.multi_cell_pools(2, seed=3, n_grids=2)[1:]  # coarse grid
    insts = []
    for s in range(9):
        pool = pools[s % len(pools)]
        insts.append(build_instance(pool, scenarios.numerical_tasks(
            4 + 5 * (s % 3), ("low", "med", "high")[s % 3], "high", seed=s)))
    assert len({i.grid.tobytes() for i in insts}) == 3
    return insts


def test_many_mixed_grids_matches_oracle_in_order():
    insts = _mixed_grid_instances()
    sols = solve_greedy_many(insts)
    assert len(sols) == len(insts)
    for inst, sol in zip(insts, sols):
        ref = solve_greedy(inst)
        assert sol.admitted.shape == (inst.num_tasks,)
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)
        assert sol.objective == pytest.approx(ref.objective)


@pytest.mark.parametrize("semantic", [True, False])
@pytest.mark.parametrize("flexible", [True, False])
def test_many_mixed_grids_all_quadrants(semantic, flexible):
    insts = _mixed_grid_instances()[:6]
    sols = solve_greedy_many(insts, semantic=semantic, flexible=flexible)
    for inst, sol in zip(insts, sols):
        ref = solve_greedy(inst, semantic=semantic, flexible=flexible)
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)


def test_many_single_grid_degenerates_to_batch():
    insts = _random_instances()[:6]
    many = solve_greedy_many(insts)
    batch = solve_greedy_batch(insts)
    for a, b in zip(many, batch):
        assert (a.admitted == b.admitted).all()
        assert np.allclose(a.alloc, b.alloc)


def test_many_all_infeasible_instances():
    insts = _mixed_grid_instances()[:4]
    hopeless = [build_instance(
        i.pool, dataclasses.replace(i.tasks,
                                    min_accuracy=np.full(i.num_tasks, 0.99)))
        for i in insts]
    sols = solve_greedy_many(hopeless)
    assert all(s.num_allocated == 0 for s in sols)
    # mixed feasible + infeasible across grids keeps per-instance results
    combo = [insts[0], hopeless[1], insts[2], hopeless[3]]
    sols = solve_greedy_many(combo)
    for inst, sol in zip(combo, sols):
        ref = solve_greedy(inst)
        assert (sol.admitted == ref.admitted).all()


@pytest.mark.slow
def test_many_heterogeneous_multi_cell_trace():
    insts, _ = scenarios.multi_cell_trace(4, 4, seed=2, n_grids=3)
    assert len({i.grid.tobytes() for i in insts}) == 3
    for inst, sol in zip(insts, solve_greedy_many(insts)):
        ref = solve_greedy(inst)
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)


def test_many_matches_sequential_jax():
    """Grouped dispatch == the sequential JAX loop it replaces."""
    insts = _mixed_grid_instances()[:5]
    for inst, sol in zip(insts, solve_greedy_many(insts)):
        ref = solve_greedy_jax(inst)
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)


def test_batched_one_jit_call_scales_to_64():
    """The acceptance-criterion sweep: 64 Fig. 6-style instances, one batch."""
    insts, _ = scenarios.fig6_sweep(
        2, n_tasks=(10, 20, 30, 40), acc_levels=("low", "med", "high"),
        lat_levels=("low", "high"), seeds=(0, 1, 2))
    insts = insts[:64]
    assert len(insts) == 64
    _assert_matches_oracle(insts)


def test_feasibility_rows_evaluate_repeated_tasks_once_and_exactly():
    """A tick's requests repeat a few applications: each distinct task row
    is evaluated once, and every row equals the direct (T, A) table bit for
    bit, with repeats and without."""
    from repro.core import latency as lat_mod
    from repro.core.sfesp import default_z_grid, task_feasibility_rows
    from repro.core.types import TaskSet, make_allocation_grid

    pool = scenarios.numerical_pool(2)
    base = scenarios.numerical_tasks(12, "med", "high", seed=3)
    pick = np.random.default_rng(0).integers(0, 12, 200)
    repeated = TaskSet(**{f.name: getattr(base, f.name)[pick]
                          for f in dataclasses.fields(base)})
    grid = make_allocation_grid(pool.levels)
    z_grid = default_z_grid()
    for tasks in (base, repeated):
        rows = task_feasibility_rows(tasks, z_grid, grid)
        direct = lat_mod.latency_table(lat_mod.LatencyParams(), tasks,
                                       rows.z_star, grid)
        np.testing.assert_array_equal(rows.lat, direct)
        np.testing.assert_array_equal(
            rows.lat_ok, direct <= tasks.max_latency[:, None])
    with mock.patch.object(lat_mod, "latency", wraps=lat_mod.latency) as spy:
        task_feasibility_rows(repeated, z_grid, grid)
    assert spy.call_count == 1
    assert spy.call_args.args[1].shape[0] <= 12       # distinct rows only
