"""Each cell's whole run at a tiny size on the CPU: control flow, the
result line's keys and the comparison with the reference."""

import json

import numpy as np
import pytest

from bench import generator, harness
from cells import TINY, run_tiny, tiny_cell


@pytest.mark.parametrize("name", TINY)
def test_run_is_correct_and_complete(name):
    out = run_tiny(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    cell = tiny_cell(name)
    want = {m["name"] for m in cell.end_to_end}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["info"]["compiles_in_window"] >= 0
    json.dumps(out)


def test_every_cell_names_its_metrics_readers():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))


def test_same_seed_same_traffic():
    cell = tiny_cell("metro.open")
    from bench import deploy

    dep = deploy.metro(cell.config)

    def draw(seed):
        gen = generator.Open(cell.traffic, dep, seed)
        gen.high_water()
        evs, arr, gone = gen.due(2.0)
        return [(a[1], a[2]) for a in arr], len(gone)

    assert draw(2 ** 40 + 3) == draw(2 ** 40 + 3)
    assert draw(2 ** 40 + 3) != draw(2 ** 40 + 4)
    seeds = generator.sweep_seeds({"instances_per_cell": 4, "batches": 2}, 5)
    assert len(set(seeds[0]) | set(seeds[1])) == 8


def test_reservoir_is_uniform_and_seeded():
    def pick(seed):
        r = harness.Reservoir(3, np.random.default_rng(seed))
        for i in range(100):
            r.offer(lambda: i)  # noqa: B023
        return sorted(r.items)

    assert pick(1) == pick(1) and len(pick(1)) == 3
