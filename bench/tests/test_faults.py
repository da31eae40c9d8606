"""The timed path broken underneath: each fault the cells can have must turn
``correct`` false — an answer altered where it is produced, and half of the
batch left out."""

import numpy as np
import pytest

from cells import run_tiny, tiny_cell


def _flip_one(res):
    adm = res["admitted"]
    b, t = np.argwhere(adm)[0]
    adm[b, t] = False
    return res


def _half(res):
    res["admitted"][res["admitted"].shape[0] // 2:] = False
    return res


def _serving(monkeypatch, alter):
    import repro.serving.admission as adm

    orig = adm.unpack_sharded_batch
    monkeypatch.setattr(adm, "unpack_sharded_batch",
                        lambda d: alter(orig(d)))


def _sweep(monkeypatch, alter):
    import repro.core.greedy as greedy

    orig = greedy.solve_device_batch
    monkeypatch.setattr(greedy, "solve_device_batch",
                        lambda dev, **kw: alter(orig(dev, **kw)))


@pytest.mark.parametrize("name", ["imt_du_macro.full_buffer", "metro.churn",
                                  "metro.open", "paper4res.sweep",
                                  "paper2res.sweep"])
@pytest.mark.parametrize("fault", [_flip_one, _half])
def test_fault_turns_correct_false(monkeypatch, name, fault):
    serving = tiny_cell(name).traffic["loop"] != "sweep"
    patch = _serving if serving else _sweep
    patch(monkeypatch, fault)
    out = run_tiny(name)
    assert not out["correct"]
    assert out["checks"]["mismatched_decisions"]["value"] > 0

