"""MR-Grid's fold of the 2^d grid cells onto the partitions, through the
served worker of the ``anticorr4d-grid`` configuration: a skyline row in
grid cell 8 (v4 >= 5000) reaches a queried partition and is answered. With
the reference's unfolded cell id as the partition key the same row is
never triggered and drops out of the answer, so the guarantee rests on the
fold."""

import numpy as np
import pytest

from skybench import reference, system
from skybench.run import load_json
from skyline_tpu.stream import engine

HIDDEN = (0, 0, 0, 6000)  # grid cell 8: only dim 4 is >= 5000
ROWS = np.array(
    [
        HIDDEN,
        (1, 2, 3, 6001),  # cell 8, dominated by HIDDEN
        (50, 60, 70, 9000),  # cell 8, dominated
        (0, 0, 1, 6000),  # cell 8, dominated
        (6000, 1, 1, 7000),  # cell 9, dominated
        (1000, 1000, 1000, 100),  # cell 0, a skyline row beside HIDDEN
        (2000, 10, 4000, 50),  # cell 0, a skyline row
        (3000, 3000, 3000, 3000),  # cell 0, dominated
    ],
    np.int64,
)


def _unfolded(x, algo, num_partitions, domain_max):
    """The reference's GridPartitioner key: the raw cell id, no modulo."""
    bits = (np.asarray(x, np.float32) >= domain_max / 2.0).astype(np.int64)
    return (bits @ (1 << np.arange(bits.shape[1], dtype=np.int64))).astype(np.int32)


def _answer(rows):
    sut = system.WorkerSystem(load_json("skybench", "configs", "anticorr4d-grid.json"))
    try:
        sut.produce([f"{i}," + ",".join(map(str, r)) for i, r in enumerate(rows)])
        sut.trigger(1)
        answers = []
        for _ in range(50):
            answers += sut.step()[1]
            if answers:
                break
        served = sut.served_rows()
    finally:
        sut.close()
    (a,) = answers
    return a, served


def _has_row(points, row) -> bool:
    return bool((np.asarray(points) == np.asarray(row, np.float64)).all(axis=1).any())


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("SKYLINE_PALLAS_INTERPRET", "1")


def test_fold_answers_a_skyline_row_of_a_high_cell():
    want = reference.skyline(ROWS)
    assert _has_row(want, HIDDEN) and want.shape[0] == 3
    a, served = _answer(ROWS)
    assert a.size == want.shape[0]
    assert reference.multiset_gap(a.points, want) == 0
    assert reference.multiset_gap(served, want) == 0


def test_unfolded_cell_id_loses_the_row(monkeypatch):
    monkeypatch.setattr(engine, "partition_ids_np", _unfolded)
    a, served = _answer(ROWS)
    assert not _has_row(a.points, HIDDEN) and not _has_row(served, HIDDEN)
    low = ROWS[_unfolded(ROWS, "mr-grid", 8, 10000.0) < 8]
    assert reference.multiset_gap(a.points, reference.skyline(low)) == 0
