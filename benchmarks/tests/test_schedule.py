"""The traffic generator: training rows from the seed."""

import numpy as np

import schedule


def test_train_rows_differ_and_repeat_from_the_seed():
    succ = schedule.successor_table(9, 50)
    rows = schedule.train_round(9, succ, 0, (1, 2, 4, 16))
    assert rows.shape == (1, 2, 4, 16) and rows.dtype == np.int32
    flat = rows.reshape(-1, 16)
    assert len({tuple(r) for r in flat.tolist()}) == len(flat)
    assert (schedule.train_round(9, succ, 0, (1, 2, 4, 16)) == rows).all()
    assert not (schedule.train_round(9, succ, 1, (1, 2, 4, 16)) == rows).all()


def test_rows_are_a_function_of_seed_and_round_and_walk_the_successor_table():
    succ = schedule.successor_table(3_000_000_007, 50)  # seeds past 2**31 are fine
    assert succ.shape == (50, 4) and (schedule.successor_table(3_000_000_007, 50) == succ).all()
    rows = schedule.train_round(3_000_000_007, succ, 2, (2, 2, 3, 12))
    assert rows.shape == (2, 2, 3, 12)
    flat = rows.reshape(-1, 12)
    assert all(b in succ[a] for r in flat for a, b in zip(r[:-1], r[1:]))
    assert not (schedule.train_round(5, succ, 2, (2, 2, 3, 12)) == rows).all()
