"""Deployments built from a configuration file, for the program and for the
reference alike.

A metro deployment is ``n_cells`` cells in ``n_domains`` contiguous backhaul
domains, one shared aggregation link per domain with a budget of
``link_budget_per_cell`` x the domain's cell count. Cell pools scatter the
paper's numerical pool by +-``capacity_spread`` from ``pool_seed`` (fixed by
the configuration, not by the run's seed: the deployment stays put while the
traffic varies). A sweep deployment is one pool and a grid of what-if
instances drawn from the run's seed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference as ref


@dataclasses.dataclass
class Metro:
    """The deployment as plain arrays (what the reference reads)."""

    names: tuple[str, ...]
    levels: tuple[np.ndarray, ...]
    grid: np.ndarray                 # (A, m)
    capacity: np.ndarray             # (C, m)
    price: np.ndarray                # (C, m)
    domain: np.ndarray               # (C,) link of each cell
    link_budget: np.ndarray          # (L,)
    apps: list[tuple[str, float, float]]
    max_latency_s: float
    max_retries: int

    @property
    def n_cells(self) -> int:
        return len(self.domain)


def pool_levels(pool: dict) -> tuple[np.ndarray, ...]:
    return tuple(np.asarray(lv, np.float64) for lv in pool["levels"])


def metro(cfg: dict) -> Metro:
    n, d = int(cfg["n_cells"]), int(cfg["n_domains"])
    pool = cfg["pool"]
    base = np.asarray(pool["capacity"], np.float64)
    spread = float(cfg["capacity_spread"])
    rng = np.random.default_rng(int(cfg["pool_seed"]))
    cap = np.empty((n, len(base)))
    for c in range(n):
        scale = rng.uniform(1.0 - spread, 1.0 + spread, size=len(base))
        cap[c] = np.maximum(np.round(base * scale), 2.0)
    domain = (np.arange(n) * d) // n
    budget = np.bincount(domain, minlength=d) * float(
        cfg["link_budget_per_cell"])
    levels = pool_levels(pool)
    return Metro(names=tuple(pool["names"]), levels=levels,
                 grid=ref.allocation_grid(levels), capacity=cap,
                 price=1.0 / cap, domain=domain, link_budget=budget,
                 apps=[tuple(a) for a in cfg["apps"]],
                 max_latency_s=float(cfg["max_latency_s"]),
                 max_retries=int(cfg["max_retries"]))


def engine(dep: Metro, chips: int):
    """The program under test: a ``MultiCellEngine`` over the deployment on
    a "cells" mesh of ``chips`` devices."""
    from repro.core import CouplingSpec, ResourcePool
    from repro.launch.mesh import make_cells_mesh
    from repro.serving import MultiCellEngine

    pools = [ResourcePool(names=dep.names, capacity=dep.capacity[c].copy(),
                          price=dep.price[c].copy(), levels=dep.levels)
             for c in range(dep.n_cells)]
    inc = np.zeros((dep.n_cells, len(dep.link_budget)), bool)
    inc[np.arange(dep.n_cells), dep.domain] = True
    spec = CouplingSpec(dep.link_budget.copy(), inc)
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
