"""The control, the reference one precision lower in the program's place,
comes out as not correct. PERF.md has its readings at the cells' own sizes,
taken on the chip."""
import pytest

import control


@pytest.mark.parametrize("cell,coflows", [
    ("fb2010-n150-k4.oneshot-m60", 4), ("paper-n16-k3.oneshot-m100", 30)])
def test_float32_control_is_not_correct(cell, coflows):
    rows = control.readings(cell, [3, 2**32 + 1], 2, "all",
                            {"coflows": coflows, "shapes": 2})
    assert all(r["correct"] is False for r in rows), rows
    assert all(r["referee_violations"] > 0 for r in rows)


def test_float32_assignment_fails_at_the_cells_own_size():
    """One request of the FB2010 cell, as its window serves it: float32
    assignment state tips a near-tie between two cores' bounds."""
    rows = control.readings("fb2010-n150-k4.oneshot-m60", [6], 1,
                            "assignment")
    assert rows[0]["correct"] is False, rows
    assert rows[0]["choices_differing"] > 0
