"""The controls put in the program's place must fail the comparison that
the program passes (limit 0 mismatches): the reference with a guarantee of
the configuration broken. The bfloat16 reading is recorded, not asserted:
the greedy's decisions hardly depend on the gradient's last bits."""

import pytest

from cells import run_tiny


@pytest.mark.parametrize("name,key", [
    ("metro.churn", "budgets_left_out"),
    ("metro.open", "budgets_left_out"),
    ("paper4res.sweep", "capacity_not_charged"),
    ("paper2res.sweep", "capacity_not_charged"),
])
def test_control_fails_where_program_passes(name, key):
    out = run_tiny(name, control=True)
    assert out["checks"]["mismatched_decisions"]["value"] == 0
    assert out["control"][key] > out["checks"]["mismatched_decisions"][
        "limit"]
    assert out["control"]["bfloat16"] >= 0
