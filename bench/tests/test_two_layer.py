"""The two-layer Dense Urban cell at a tiny size on the CPU: macro and micro
cells on different grids, coupled behind their site's link, compared with
the reference (each cell on its own grid); a fault in the timed path and
the control with the link budgets left out must each fail the comparison."""

import pytest

from cells import run_tiny

CELL = "imt_du_2layer.full_buffer"


@pytest.fixture(scope="module")
def controlled():
    return run_tiny(CELL, control=True)


def test_two_layer_is_correct_and_links_bind(controlled):
    out = controlled
    assert out["correct"], out["checks"]
    assert out["checks"]["mismatched_decisions"]["value"] == 0
    assert out["info"]["links_bound_groups"] > 0
    assert out["info"]["ticks"] > 0 and out["info"]["delta_rows"] > 0


def test_two_layer_fault_turns_correct_false(monkeypatch):
    import repro.serving.admission as adm
    from test_faults import _flip_one

    orig = adm.unpack_sharded_batch
    monkeypatch.setattr(adm, "unpack_sharded_batch",
                        lambda d: _flip_one(orig(d)))
    out = run_tiny(CELL)
    assert not out["correct"]
    assert out["checks"]["mismatched_decisions"]["value"] > 0


def test_two_layer_control_fails_where_program_passes(controlled):
    out = controlled
    assert out["control"]["budgets_left_out"] > \
        out["checks"]["mismatched_decisions"]["limit"]
    assert out["control"]["bfloat16"] >= 0
