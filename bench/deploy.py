"""Deployments built from a configuration file, for the program and for the
reference alike.

A serving deployment is cells, pools and links:

* ``pools``: named pools, each with ``names``, ``capacity`` and ``levels``
  (its allocation grid is every combination of the levels);
* ``cells``: contiguous runs ``[pool name, count]``, in cell order;
* ``links``: tiers of shared links, each ``{"name", "cells_per_link",
  "budget_per_cell"}``: cell c lies on link c // ``cells_per_link`` of the
  tier, and a link's budget is ``budget_per_cell`` x its cell count. A cell
  lies on one link of every tier, so on as many links as there are tiers.

Each cell's capacities scatter its pool's by +-``capacity_spread``, drawn
in cell order from ``pool_seed`` (fixed by the configuration, not by the
run's seed: the deployment stays put while the traffic varies); prices are
1 / capacity.

A sweep deployment is one pool and a grid of what-if instances drawn from
the run's seed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference as ref


@dataclasses.dataclass
class Pool:
    """A pool's resources and its enumerated allocation grid."""

    names: tuple[str, ...]
    levels: tuple[np.ndarray, ...]
    grid: np.ndarray                 # (A, m)


@dataclasses.dataclass
class Metro:
    """The deployment as plain arrays (what the reference reads)."""

    pools: tuple[Pool, ...]
    pool_of: np.ndarray              # (C,) index into ``pools``
    capacity: list[np.ndarray]       # (m,) per cell
    price: list[np.ndarray]          # (m,) per cell
    incidence: np.ndarray            # (C, L) bool: the links of each cell
    link_budget: np.ndarray          # (L,)
    apps: list[tuple[str, float, float]]
    max_latency_s: float
    max_retries: int

    @property
    def n_cells(self) -> int:
        return len(self.pool_of)

    def pool(self, c: int) -> Pool:
        return self.pools[self.pool_of[c]]

    def groups(self) -> list[np.ndarray]:
        """The coupling groups: connected components of the cell-link
        graph, each as its cells in ascending order, ordered by their first
        cell."""
        parent = np.arange(self.n_cells)

        def find(a: int) -> int:
            while parent[a] != a:
                a = parent[a]
            return a

        for users in (np.flatnonzero(col) for col in self.incidence.T):
            for other in users[1:]:
                ra, rb = find(int(users[0])), find(int(other))
                parent[max(ra, rb)] = min(ra, rb)
        roots = np.array([find(c) for c in range(self.n_cells)])
        return [np.flatnonzero(roots == r) for r in np.unique(roots)]


def pool_levels(pool: dict) -> tuple[np.ndarray, ...]:
    return tuple(np.asarray(lv, np.float64) for lv in pool["levels"])


def metro(cfg: dict) -> Metro:
    names = list(cfg["pools"])
    pools = []
    for p in cfg["pools"].values():
        levels = pool_levels(p)
        pools.append(Pool(tuple(p["names"]), levels,
                          ref.allocation_grid(levels)))
    pool_of = np.concatenate([np.full(int(n), names.index(name))
                              for name, n in cfg["cells"]])
    n = len(pool_of)
    spread = float(cfg["capacity_spread"])
    rng = np.random.default_rng(int(cfg["pool_seed"]))
    cap = []
    for c in range(n):
        base = np.asarray(cfg["pools"][names[pool_of[c]]]["capacity"],
                          np.float64)
        scale = rng.uniform(1.0 - spread, 1.0 + spread, size=len(base))
        cap.append(np.maximum(np.round(base * scale), 2.0))
    columns, budgets = [], []
    for tier in cfg["links"]:
        k = int(tier["cells_per_link"])
        link, n_links = np.arange(n) // k, -(-n // k)
        for j in range(n_links):
            columns.append(link == j)
            budgets.append(float(tier["budget_per_cell"])
                           * int(columns[-1].sum()))
    return Metro(pools=tuple(pools), pool_of=pool_of, capacity=cap,
                 price=[1.0 / c for c in cap],
                 incidence=np.stack(columns, axis=1),
                 link_budget=np.asarray(budgets, np.float64),
                 apps=[tuple(a) for a in cfg["apps"]],
                 max_latency_s=float(cfg["max_latency_s"]),
                 max_retries=int(cfg["max_retries"]))


def engine(dep: Metro, chips: int):
    """The program under test: a ``MultiCellEngine`` over the deployment on
    a "cells" mesh of ``chips`` devices."""
    from repro.core import CouplingSpec, ResourcePool
    from repro.launch.mesh import make_cells_mesh
    from repro.serving import MultiCellEngine

    pools = [ResourcePool(names=dep.pool(c).names,
                          capacity=dep.capacity[c].copy(),
                          price=dep.price[c].copy(),
                          levels=dep.pool(c).levels)
             for c in range(dep.n_cells)]
    spec = CouplingSpec(dep.link_budget.copy(), dep.incidence.copy())
    return MultiCellEngine(pools, coupling=spec, mesh=make_cells_mesh(chips),
                           max_retries=dep.max_retries)


def request(dep: Metro, app: int):
    from repro.serving import SliceRequest

    name, acc, fps = dep.apps[app]
    return SliceRequest("object-recognition", "yolox", name,
                        max_latency_s=dep.max_latency_s, min_accuracy=acc,
                        jobs_per_sec=fps)


@dataclasses.dataclass
class SweepTasks:
    """One what-if instance: the paper's Fig. 6 task draw."""

    apps: list[str]
    min_acc: np.ndarray
    max_lat: np.ndarray
    rate: np.ndarray


def sweep_tasks(n: int, acc: str, lat: str, seed: int,
                mix: dict) -> SweepTasks:
    """``n`` tasks spread evenly over the ten Tab. II applications, in an
    order shuffled by ``seed``; thresholds by service (paper §V-B)."""
    rng = np.random.default_rng(seed)
    idx = np.arange(n) % len(ref.PAPER_APPS)
    rng.shuffle(idx)
    apps = [ref.PAPER_APPS[i] for i in idx]
    min_acc = np.array([mix["acc_thresholds"][acc][ref.APPS[a][0]]
                        for a in apps])
    return SweepTasks(apps=apps, min_acc=min_acc,
                      max_lat=np.full(n, float(mix["lat_thresholds"][lat])),
                      rate=np.full(n, float(mix["jobs_per_sec"])))


def sweep_batch(mix: dict, seeds) -> list[SweepTasks]:
    """The Fig. 6 grid (task counts x accuracy x latency levels), once per
    seed of ``seeds``: ``len(seeds)`` x the grid's cells instances."""
    return [sweep_tasks(n, acc, lat, int(s), mix)
            for acc in mix["acc"] for lat in mix["lat"]
            for n in mix["n_tasks"] for s in seeds]


def program_instance(pool_cfg: dict, tasks: SweepTasks):
    """The program's own instance of one what-if (its SDLA-free path)."""
    from repro.core import ResourcePool, TaskSet, build_instance, semantics

    cap = np.asarray(pool_cfg["capacity"], np.float64)
    pool = ResourcePool(names=tuple(pool_cfg["names"]), capacity=cap,
                        price=1.0 / cap, levels=pool_levels(pool_cfg))
    n = len(tasks.apps)
    services = [ref.APPS[a][0] for a in tasks.apps]
    ts = TaskSet(
        app_idx=np.array([semantics.APP_INDEX[a] for a in tasks.apps]),
        min_accuracy=tasks.min_acc, max_latency=tasks.max_lat,
        bits_per_job=np.array([ref.SERVICE_BITS[s] for s in services]),
        jobs_per_sec=tasks.rate,
        gpu_time_per_job=np.array([ref.SERVICE_GPU_S[s] for s in services]),
        n_ues=np.ones(n, np.int64))
    return build_instance(pool, ts)
