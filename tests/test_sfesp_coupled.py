"""Cell-coupled (shared backhaul) batched solving vs the numpy coupled oracle."""
import dataclasses

import numpy as np
import pytest

from repro.core import (CouplingSpec, build_instance, device_stack,
                        merge_coupling, scenarios, semantics,
                        solve_coupled_ref, solve_device_batch, solve_greedy,
                        solve_greedy_batch, solve_greedy_many, stack_instances,
                        restack, task_link_load)


def _coupled_instances(n_cells=4, seed=0, link_caps=(4.0, 6.0)):
    """Heterogeneous-pool cells over a random link topology (every link has
    at least one user; the last cell stays link-free/uncoupled)."""
    rng = np.random.default_rng(seed)
    pools = scenarios.multi_cell_pools(n_cells, seed=seed)
    cap = np.asarray(link_caps, float)
    L = len(cap)
    inc = np.zeros((n_cells, L), bool)
    for link in range(L):
        users = rng.choice(n_cells - 1, size=rng.integers(1, n_cells - 1),
                           replace=False)
        inc[users, link] = True
    insts = []
    for c, pool in enumerate(pools):
        tasks = scenarios.numerical_tasks(
            int(rng.integers(4, 30)), ("low", "med", "high")[c % 3], "high",
            seed=seed + 31 * c)
        insts.append(build_instance(
            pool, tasks, coupling=CouplingSpec(cap, inc[c:c + 1])))
    return insts, cap, inc


def _assert_matches_ref(insts, **kw):
    sols = solve_greedy_batch(stack_instances(insts), **kw)
    refs = solve_coupled_ref(insts, **kw)
    for b, (sol, ref) in enumerate(zip(sols, refs)):
        assert (sol.admitted == ref.admitted).all(), b
        assert np.allclose(sol.alloc, ref.alloc)
        assert np.allclose(sol.z, ref.z)
        assert sol.objective == pytest.approx(ref.objective)
    return sols


def _shared_link_instances(tiny_cell=None):
    """Cells 0-2 share one link whose budget never binds: one coupling
    group, which admits one task a round, so a cell that loses a round keeps
    its candidates for the next. Cell 3 is link-free; Tmax is 13. The pool
    of ``tiny_cell`` fits no allocation (0.5 RBG, one level is 1): its
    every candidate retires in round 1 while its group goes on admitting."""
    pools = scenarios.multi_cell_pools(4, seed=8)
    if tiny_cell is not None:
        pools[tiny_cell] = dataclasses.replace(
            pools[tiny_cell],
            capacity=np.array([0.5, pools[tiny_cell].capacity[1]]))
    cap = np.array([1e9])
    inc = np.array([[True], [True], [True], [False]])
    insts = [build_instance(pool, scenarios.numerical_tasks(
        n, "low", "high", seed=40 + c), coupling=CouplingSpec(cap,
                                                             inc[c:c + 1]))
        for c, (pool, n) in enumerate(zip(pools, (7, 13, 10, 6)))]
    return insts, cap, inc


@pytest.mark.parametrize("case", ["random", "loser_stays_alive",
                                  "mass_drop"])
def test_coupled_matches_oracle_randomized(case):
    if case == "random":
        batches = [_coupled_instances(seed=seed) for seed in range(4)]
    else:
        batches = [_shared_link_instances(
            tiny_cell=1 if case == "mass_drop" else None)]
    for insts, cap, inc in batches:
        sols = _assert_matches_ref(insts)
        # shared-link budgets hold for the admitted set
        for link in range(len(cap)):
            used = sum(
                float((task_link_load(i) * s.admitted).sum())
                for i, s, on in zip(insts, sols, inc[:, link]) if on)
            assert used <= cap[link] + 1e-6
    if case == "random":
        return
    group_admits = [s.num_allocated for s in sols[:3]]
    dev = device_stack(stack_instances(insts))
    if case == "mass_drop":
        assert np.asarray(dev.alive0)[1].any()
        assert group_admits[1] == 0 and sum(group_admits) > 0
    else:
        # two cells of one group admitted: the later one lost a round
        # with its candidates alive and contended again
        assert sum(n > 0 for n in group_admits) >= 2
    # the group admits one task a round, then at most one retiring round
    rounds = solve_device_batch(dev)["rounds"]
    assert sum(group_admits) <= rounds <= max(
        sum(group_admits), sols[3].num_allocated) + 1


@pytest.mark.parametrize("semantic", [True, False])
@pytest.mark.parametrize("flexible", [True, False])
def test_coupled_matches_oracle_all_quadrants(semantic, flexible):
    insts, _, _ = _coupled_instances(seed=2)
    _assert_matches_ref(insts, semantic=semantic, flexible=flexible)


def test_coupled_pallas_inner_matches_oracle():
    insts, _, _ = _coupled_instances(seed=1)
    sols = solve_greedy_batch(stack_instances(insts), inner="pallas")
    for sol, ref in zip(sols, solve_coupled_ref(insts)):
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)


def test_zero_budget_admits_only_link_free_cells():
    insts, _, _ = _coupled_instances(seed=3, link_caps=(0.0, 0.0))
    sols = _assert_matches_ref(insts)
    for inst, sol in zip(insts, sols):
        if inst.coupling.incidence.any():
            # every task carries positive load → nothing fits a zero link
            assert sol.num_allocated == 0
        else:
            # the link-free cell admits exactly as the uncoupled greedy
            ref = solve_greedy(inst)
            assert (sol.admitted == ref.admitted).all()
            assert sol.num_allocated > 0


def test_singleton_groups_bit_match_uncoupled_path():
    """One cell per group (private links) == the uncoupled device program."""
    insts, _, _ = _coupled_instances(seed=4)
    plain = [dataclasses.replace(i, coupling=None) for i in insts]
    # generous private link per cell → constraint never binds (one shared
    # spec: per-cell rows must reference the same capacity array)
    spec = CouplingSpec(np.full(len(insts), 1e9),
                        np.eye(len(insts), dtype=bool))
    solo = [dataclasses.replace(i, coupling=spec.row(c))
            for c, i in enumerate(insts)]
    spec = stack_instances(solo).coupling
    assert (spec.groups() == np.arange(len(insts))).all()
    for a, b in zip(solve_greedy_batch(stack_instances(solo)),
                    solve_greedy_batch(stack_instances(plain))):
        assert (a.admitted == b.admitted).all()
        assert np.allclose(a.alloc, b.alloc)
        assert a.objective == b.objective


def test_coupled_pad_batch_to_is_inert():
    insts, _, _ = _coupled_instances(seed=5, link_caps=(3.0,))
    st = stack_instances(insts)
    plain = solve_greedy_batch(st)
    padded = solve_greedy_batch(st, pad_batch_to=8)
    for a, b in zip(plain, padded):
        assert (a.admitted == b.admitted).all()
        assert np.allclose(a.alloc, b.alloc)


def test_coupling_spec_groups_transitive():
    # cells 0-1 share link 0, cells 1-2 share link 1 → {0,1,2} one group
    inc = np.array([[1, 0], [1, 1], [0, 1], [0, 0]], bool)
    spec = CouplingSpec(np.ones(2), inc)
    assert spec.groups().tolist() == [0, 0, 0, 3]


def test_merge_coupling_validates_link_set():
    insts, _, _ = _coupled_instances(seed=0)
    other = dataclasses.replace(
        insts[1], coupling=CouplingSpec(np.array([9.0]), np.ones((1, 1), bool)))
    with pytest.raises(ValueError, match="shared link set"):
        merge_coupling([insts[0], other])
    # identity, not value equality: an equal budget vector from a DIFFERENT
    # deployment must not be silently charged against the same links
    twin = dataclasses.replace(
        insts[1], coupling=CouplingSpec(
            insts[0].coupling.link_capacity.copy(),
            insts[1].coupling.incidence))
    with pytest.raises(ValueError, match="shared link set"):
        merge_coupling([insts[0], twin])
    assert merge_coupling([dataclasses.replace(i, coupling=None)
                           for i in insts]) is None


def test_many_rejects_link_across_grid_groups():
    """A link whose users sit on two grids is solved once, on their union
    grid: the decisions are the coupled oracle's, each allocation on its
    own cell's grid, and the budget binds."""
    pools = scenarios.multi_cell_pools(2, seed=3, n_grids=2)  # distinct grids
    spec = CouplingSpec(np.array([5.0]), np.ones((1, 1), bool))
    insts = [build_instance(p, scenarios.numerical_tasks(6, "med", "high",
                                                         seed=s),
                            coupling=spec)
             for s, p in enumerate(pools)]
    assert insts[0].num_allocs != insts[1].num_allocs
    sols = solve_greedy_many(insts)
    for inst, sol, ref in zip(insts, sols, solve_coupled_ref(insts)):
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)
        own = {tuple(row) for row in inst.grid.tolist()}
        assert all(tuple(a) in own for a in sol.alloc[sol.admitted].tolist())
    free = solve_greedy_many([dataclasses.replace(i, coupling=None)
                              for i in insts])
    assert sum(s.num_allocated for s in sols) \
        < sum(s.num_allocated for s in free)


def test_many_dispatches_coupled_groups():
    insts, _, _ = _coupled_instances(seed=6, link_caps=(5.0,))
    sols = solve_greedy_many(insts)
    for sol, ref in zip(sols, solve_coupled_ref(insts)):
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)


def test_restack_recomputes_coupling():
    insts, _, _ = _coupled_instances(seed=7, link_caps=(4.0,))
    plain = [dataclasses.replace(i, coupling=None) for i in insts]
    st = stack_instances(plain, tmax=32)
    assert st.coupling is None
    st2 = restack(st, insts)
    assert st2.coupling is not None and st2.lat is st.lat
    for sol, ref in zip(solve_greedy_batch(st2), solve_coupled_ref(insts)):
        assert (sol.admitted == ref.admitted).all()


# ---------------------------------------------------------------------------
# coupled scenarios: shared-backhaul traces + handover
# ---------------------------------------------------------------------------

def test_multi_cell_trace_shared_backhaul_one_group_per_step():
    insts, meta = scenarios.multi_cell_trace(3, 4, seed=2,
                                             shared_backhaul=5.0)
    st = stack_instances(insts)
    groups = st.coupling.groups()
    # cells of one step are coupled; different steps are independent
    for i, m in enumerate(meta):
        assert groups[i] == 3 * m["step"]
    sols = solve_greedy_batch(st)
    for sol, ref in zip(sols, solve_coupled_ref(insts)):
        assert (sol.admitted == ref.admitted).all()
    for step in range(4):
        used = sum(float((task_link_load(i) * s.admitted).sum())
                   for i, s, m in zip(insts, sols, meta)
                   if m["step"] == step)
        assert used <= 5.0 + 1e-6


def test_shared_backhaul_rejects_mixed_grids():
    """A shared-backhaul trace over two grids builds, and stacks whole onto
    the union grid: its coupled solve equals the oracle."""
    insts, meta = scenarios.multi_cell_trace(4, 2, n_grids=2, seed=4,
                                             shared_backhaul=5.0)
    assert len({i.num_allocs for i in insts}) == 2
    st = stack_instances(insts)
    assert st.grids == 2 and st.cell_mask.shape == (8, st.num_allocs)
    for b, inst in enumerate(insts):
        assert st.cell_mask[b].sum() == inst.num_allocs
    for sol, ref in zip(solve_greedy_batch(st), solve_coupled_ref(insts)):
        assert (sol.admitted == ref.admitted).all()
        assert np.allclose(sol.alloc, ref.alloc)
    for sol, ref in zip(solve_greedy_many(insts), solve_coupled_ref(insts)):
        assert (sol.admitted == ref.admitted).all()


def test_shared_backhaul_binds_admission():
    loose, _ = scenarios.multi_cell_trace(3, 3, seed=1)
    tight, _ = scenarios.multi_cell_trace(3, 3, seed=1, shared_backhaul=2.0)
    n_loose = sum(s.num_allocated for s in solve_greedy_batch(loose))
    n_tight = sum(s.num_allocated for s in solve_greedy_batch(tight))
    assert n_tight < n_loose
    load = sum(float((task_link_load(i) * s.admitted).sum())
               for i, s in zip(tight, solve_greedy_batch(tight)))
    assert load <= 3 * 2.0 + 1e-6          # 3 steps x one 2.0 link each


def test_closed_loop_handover_step():
    recs = scenarios.closed_loop_trace(3, 8, seed=5, arrival_rate=3.0,
                                       handover_prob=0.5)
    assert sum(r["handovers"] for r in recs) > 0
    assert all(0 <= r["admitted"] <= r["offered"] for r in recs)
    again = scenarios.closed_loop_trace(3, 8, seed=5, arrival_rate=3.0,
                                        handover_prob=0.5)
    assert recs == again
    # single cell: nowhere to hand over to
    solo = scenarios.closed_loop_trace(1, 4, seed=5, handover_prob=1.0)
    assert all(r["handovers"] == 0 for r in solo)


def test_closed_loop_coupled_backhaul_runs():
    recs = scenarios.closed_loop_trace(2, 5, seed=4, arrival_rate=4.0,
                                       shared_backhaul=3.0,
                                       handover_prob=0.25)
    assert len(recs) == 10
    assert all(0 <= r["admitted"] <= r["offered"] for r in recs)
    # the tight shared link caps admission below the uncoupled run
    free = scenarios.closed_loop_trace(2, 5, seed=4, arrival_rate=4.0,
                                       handover_prob=0.25)
    assert sum(r["admitted"] for r in recs) <= sum(r["admitted"] for r in free)


def test_handover_warm_start_pins_compression():
    """Re-deriving z from the accuracy achieved at the admitted z never
    forces a re-upload at a higher rate (the warm-start contract)."""
    z_grid = np.geomspace(0.02, 1.0, 64)
    for app in range(len(semantics.PAPER_APPS)):
        idx = np.full(z_grid.shape, app)
        acc_at = semantics.accuracy(idx, z_grid)
        zi = semantics.min_z_for_accuracy(idx, acc_at, z_grid)
        assert (zi >= 0).all()
        assert (z_grid[zi] <= z_grid + 1e-12).all()
        assert (semantics.accuracy(idx, z_grid[zi]) >= acc_at - 1e-9).all()


def test_link_budget_binds_as_in_float64():
    """Sixty one-task cells on one link, admitted in cell order; the budget
    leaves the last task 3e-6 short. A float32 running sum of the first 59
    loads (about 61.8) errs by more than that and would admit it; the
    device's float32-pair accounting gives float64's verdict."""
    from repro.core.greedy import _greedy_jax_batch_coupled
    from repro.core.sfesp import split_f32

    loads = np.random.default_rng(9).uniform(0.5, 1.5, 60)
    budget = loads[:-1].sum() + loads[-1] - 3e-6
    used = np.float32(0)
    for load in loads[:-1]:
        used = np.float32(used + np.float32(load))
    assert np.float32(loads[-1]) <= np.float32(budget) - used  # the premise
    B = len(loads)
    grid = np.array([[1.0, 1.0]])
    admitted, _, _ = _greedy_jax_batch_coupled(
        np.ones((B, 1, 1), bool), grid, np.ones((B, 2)),
        np.full((B, 2), 4.0), np.ones((B, 1), bool), np.zeros(1),
        split_f32(loads[:, None]), split_f32([budget]),
        np.ones((B, 1), bool), np.zeros(B, np.int32))
    admitted = np.asarray(admitted)[:, 0]
    assert admitted[:-1].all() and not admitted[-1]
