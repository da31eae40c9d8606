"""Deployments from ``bench/deploy.py`` and the reference's coupled
solve over them (``bench/check.py``), on three test deployments of ``data/``:

* ``metro``: one pool, 32 cells in 4 contiguous domains of 8, one link per
  domain: the harness's rehearsal deployment, which must build the arrays
  it built when each cell had one grid and one domain;
* ``nested``: 6 cells on one grid, 2 sites of 3 behind site links and one
  link over all 6, so each cell lies on two links;
* ``two_pool``: 3 cells of the full grid and 3 of a coarser one, each run
  behind its own site link. The program's engine takes one grid for all its
  cells, so this one is checked against the program's numpy oracle only.
"""

import types

import numpy as np
import pytest

from bench import check, deploy
from bench import reference as ref
from cells import _load, run_tiny

FIXTURES = ["metro", "nested", "two_pool"]


def test_metro_fixture_builds_the_same_arrays():
    """Capacities drawn in cell order from ``pool_seed``, prices 1 /
    capacity, one link per domain of 8 cells with 1.2 per cell: what the
    deployment read when it was stated as 32 cells in 4 domains."""
    cfg = _load("metro")
    dep = deploy.metro(cfg)
    n, d = 32, 4
    pool = cfg["pools"]["pool"]
    base = np.asarray(pool["capacity"], np.float64)
    rng = np.random.default_rng(1)
    cap = np.stack([np.maximum(np.round(base * rng.uniform(
        1 - 0.4, 1 + 0.4, size=len(base))), 2.0) for _ in range(n)])
    domain = (np.arange(n) * d) // n
    np.testing.assert_array_equal(np.stack(dep.capacity), cap)
    np.testing.assert_array_equal(np.stack(dep.price), 1.0 / cap)
    np.testing.assert_array_equal(dep.link_budget,
                                  np.bincount(domain, minlength=d) * 1.2)
    np.testing.assert_array_equal(dep.incidence, np.eye(d, dtype=bool)[domain])
    assert len(dep.pools) == 1 and (dep.pool_of == 0).all()
    np.testing.assert_array_equal(dep.pools[0].grid, ref.allocation_grid(
        deploy.pool_levels(pool)))
    assert [list(g) for g in dep.groups()] == [
        list(np.flatnonzero(domain == k)) for k in range(d)]


def test_link_tiers_and_pools():
    nested = deploy.metro(_load("nested"))
    assert nested.incidence.shape == (6, 3)
    assert nested.incidence.sum(axis=1).tolist() == [2] * 6
    np.testing.assert_allclose(nested.link_budget, [4.8, 4.8, 7.2])
    assert [list(g) for g in nested.groups()] == [list(range(6))]
    two = deploy.metro(_load("two_pool"))
    assert two.pool_of.tolist() == [0, 0, 0, 1, 1, 1]
    assert two.pool(4).grid.shape == (20, 2)
    assert all((two.capacity[c] <= np.array([10.0, 12.5])).all()
               for c in range(3, 6))
    assert [list(g) for g in two.groups()] == [[0, 1, 2], [3, 4, 5]]


def _candidates(dep, seed: int):
    """Seeded candidate lists: 6-12 requests per cell, apps drawn from the
    deployment's, as the decision lists ``reference_tick`` reads."""
    rng = np.random.default_rng(seed)
    return [[types.SimpleNamespace(request=deploy.request(
                dep, int(rng.integers(len(dep.apps)))))
             for _ in range(int(rng.integers(6, 13)))]
            for _ in range(dep.n_cells)]


def _oracle(dep, decisions):
    """The program's numpy oracle of the coupled greedy on the same lists,
    as (admitted, allocation index) per cell."""
    from repro.core import (CouplingSpec, ResourcePool, TaskSet,
                            build_instance, semantics, solve_coupled_ref)

    insts = []
    for c, ds in enumerate(decisions):
        p = dep.pool(c)
        pool = ResourcePool(names=p.names, capacity=dep.capacity[c],
                            price=dep.price[c], levels=p.levels)
        reqs = [d.request for d in ds]
        services = [ref.APPS[r.app_class][0] for r in reqs]
        insts.append(build_instance(pool, TaskSet(
            app_idx=np.array([semantics.APP_INDEX[r.app_class]
                              for r in reqs]),
            min_accuracy=np.array([r.min_accuracy for r in reqs]),
            max_latency=np.array([r.max_latency_s for r in reqs]),
            bits_per_job=np.array([ref.SERVICE_BITS[s] for s in services]),
            jobs_per_sec=np.array([r.jobs_per_sec for r in reqs]),
            gpu_time_per_job=np.array([ref.SERVICE_GPU_S[s]
                                       for s in services]),
            n_ues=np.array([r.n_ues for r in reqs]))))
    sols = solve_coupled_ref(insts, CouplingSpec(dep.link_budget,
                                                 dep.incidence))
    out = []
    for c, sol in enumerate(sols):
        index = {tuple(row): i
                 for i, row in enumerate(dep.pool(c).grid.tolist())}
        out.append((sol.admitted, np.array(
            [index[tuple(a)] if ok else -1
             for ok, a in zip(sol.admitted, sol.alloc.tolist())], np.int64)))
    return out


@pytest.mark.parametrize("seed", [5, 2 ** 35 + 1])
@pytest.mark.parametrize("name", FIXTURES)
def test_reference_equals_program_oracle(name, seed):
    dep = deploy.metro(_load(name))
    decisions = _candidates(dep, seed)
    got, ties = check.reference_tick(dep, decisions)
    want = _oracle(dep, decisions)
    assert check.mismatches(got, want) == 0
    assert ties == {"ties_followed": 0, "tie_gap_max": 0.0}
    # the links bind: without them the decisions differ
    loose, _ = check.reference_tick(dep, decisions, coupled=False)
    assert check.mismatches(loose, want) > 0


def test_engine_matches_on_nested_links():
    out = run_tiny("nested.churn")
    assert out["correct"], out["checks"]
    assert out["checks"]["ticks_compared"]["value"] >= 3
