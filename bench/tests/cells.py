"""Tiny copies of the benchmark's cells (sweeps cut to a few instances,
serving cells to the window ``run_tiny`` gives), and of the serving loops on
the test deployments of ``data/``, for CPU rehearsal."""

import json
import pathlib
import time

from bench import harness

DATA = pathlib.Path(__file__).resolve().parent / "data"

SWEEP_TINY = dict(n_tasks=[10, 30], acc=["low", "high"], lat=["high"],
                  instances_per_cell=3)
SERVING_E2E = {"churn": ["tick_ms", "tick_p50_ms", "tick_p99_ms", "setup_s"],
               "open": ["decision_p95_ms", "setup_s"]}
TINY = ["paper4res.sweep", "paper2res.sweep", "imt_du_macro.full_buffer",
        "metro.churn", "metro.open"]


def _load(name: str) -> dict:
    return json.loads((DATA / f"{name}.json").read_text())


def tiny_cell(name: str) -> harness.Cell:
    """A benchmark cell, a sweep cut to a few instances, or a serving loop
    (``<deployment>.<mix>``) over a test deployment of ``data/``, such as
    the 32-cell rehearsal deployment ``metro``."""
    config, mix = name.split(".")
    if not (DATA / f"{config}.json").exists():
        cell = harness.load_cell(name)
        if cell.traffic["loop"] == "sweep":
            cell.traffic = dict(cell.traffic, **SWEEP_TINY)
        return cell
    e2e = [{"name": m, "unit": "ms" if m.endswith("_ms") else "s"}
           for m in SERVING_E2E[mix]]
    return harness.Cell(name=name, chips=1, config=_load(config),
                        traffic=_load(mix), end_to_end=e2e, per_layer=[])


def run_tiny(name: str, seed: int = 2 ** 33 + 7, seconds: float = 1.0,
             control: bool = False) -> dict:
    """One run with the chip check skipped (no devices), as run.py makes
    it otherwise."""
    return harness.run(tiny_cell(name), seed, seconds, False,
                       time.perf_counter(), None, lambda msg: None,
                       control=control)
