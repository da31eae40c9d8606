"""Tiny copies of the benchmark's cells, and of the serving loops on a
rehearsal deployment (``data/metro.json``), for CPU rehearsal."""

import json
import pathlib
import time

from bench import harness

DATA = pathlib.Path(__file__).resolve().parent / "data"

SWEEP_TINY = dict(n_tasks=[10, 30], acc=["low", "high"], lat=["high"],
                  instances_per_cell=3)
SERVING_E2E = {"churn": ["tick_ms", "tick_p95_ms", "setup_s"],
               "open": ["decision_p95_ms", "setup_s"]}
TINY = ["paper4res.sweep", "paper2res.sweep", "metro.churn", "metro.open"]


def _load(name: str) -> dict:
    return json.loads((DATA / f"{name}.json").read_text())


def tiny_cell(name: str) -> harness.Cell:
    """A benchmark cell with its sweep cut to a few instances, or a serving
    loop (``metro.<mix>``) over the 32-cell rehearsal deployment."""
    config, mix = name.split(".")
    if config != "metro":
        cell = harness.load_cell(name)
        cell.traffic = dict(cell.traffic, **SWEEP_TINY)
        return cell
    e2e = [{"name": m, "unit": "ms" if m.endswith("_ms") else "s"}
           for m in SERVING_E2E[mix]]
    return harness.Cell(name=name, chips=1, config=_load("metro"),
                        traffic=_load(mix), end_to_end=e2e, per_layer=[])


def run_tiny(name: str, seed: int = 2 ** 33 + 7, seconds: float = 1.0,
             control: bool = False) -> dict:
    """One run with the chip check skipped (no devices), as run.py makes
    it otherwise."""
    return harness.run(tiny_cell(name), seed, seconds, False,
                       time.perf_counter(), None, lambda msg: None,
                       control=control)
