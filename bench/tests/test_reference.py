"""The plain reference: near ties follow the decisions under test, nothing
else does."""

import numpy as np

from bench import reference as ref

GRID = ref.allocation_grid([np.arange(1.0, 16.0), np.arange(1.0, 21.0)])
CAP = np.array([15.0, 20.0])


def _group(hint0, hint1, app1="coco_bags"):
    t0 = ref.Task("coco_bags", 0.35, 0.7, 8.0, GRID)
    t1 = ref.Task(app1, 0.35, 0.7, 8.0, GRID)
    cells = [ref.Cell([t0], 1 / CAP, CAP, GRID, hint=hint0),
             ref.Cell([t1], 1 / CAP, CAP, GRID, hint=hint1)]
    # one link, room for one task only
    link = np.array([max(t0.load, t1.load) * 1.01])
    ref.solve_group(cells, link, np.ones((2, 1), bool))
    return cells


def test_exact_tie_follows_the_decisions():
    plain = _group(frozenset(), frozenset())
    assert plain[0].admitted.tolist() == [True]
    assert plain[1].admitted.tolist() == [False]
    a = int(plain[0].alloc[0])
    cells = _group(frozenset(), frozenset({(0, a)}))
    assert cells[0].admitted.tolist() == [False]
    assert cells[1].admitted.tolist() == [True]
    assert cells[1].ties_followed == 1
    assert 0.0 <= cells[1].gap_max <= ref.TIE


def test_no_tie_is_not_followed():
    plain = _group(frozenset(), frozenset(), app1="cityscapes_flat")
    w = 0 if plain[0].admitted[0] else 1
    loser = 1 - w
    # a decision for the loser at its feasible allocation of least gradient
    probe = ref.Cell([ref.Task(("coco_bags", "cityscapes_flat")[loser], 0.35,
                               0.7, 8.0, GRID)], 1 / CAP, CAP, GRID)
    probe.best(np.inf)
    a = int(np.where(probe._feas[0], probe._pg, np.inf).argmin())
    hint = [frozenset(), frozenset()]
    hint[loser] = frozenset({(0, a)})
    cells = _group(*hint, app1="cityscapes_flat")
    assert cells[w].admitted.tolist() == [True]
    assert cells[loser].admitted.tolist() == [False]
    assert cells[loser].ties_followed == 0
    assert cells[loser].gap_max > ref.TIE
