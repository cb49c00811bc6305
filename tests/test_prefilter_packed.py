"""Grid prefilter host test (flush cascade stage 1): the binary-search
codes and the packed-field certificate must keep exactly the rows the
broadcast formula keeps — over dims on both sides of the packed limit,
ladder-boundary and non-finite coordinates, validation-disabled ladders,
masked and few representatives, and chunk edges."""

import numpy as np
import pytest

from skyline_tpu.stream.batched import (
    _PACKED_MAX_DIMS,
    _PREFILTER_CHUNK,
    PartitionSet,
    _grid_certifiers,
    _packed_drop,
)
from skyline_tpu.stream.window import GRID_BINS, GRID_REPS
from skyline_tpu.telemetry.profiler import FlightRecorder


def _oracle_keep(bounds, ux, rows):
    """The broadcast formula the packed test replaces: validate each
    ladder, code every row by ``#(b <= y) - 1`` and drop it when some
    representative's codes are strictly below it in every dim."""
    ux = ux.copy()
    with np.errstate(invalid="ignore"):
        bad = ~np.all(np.diff(bounds, axis=1) > 0, axis=1)
    ux[np.broadcast_to(bad[:, None, :], ux.shape)] = GRID_BINS + 1
    keep = []
    for p, r in enumerate(rows):
        b, u = bounds[p], ux[p]
        c = np.asarray(r, np.float32)
        vy = (b[None, :, :] <= c[:, None, :]).sum(axis=1, dtype=np.int32) - 1
        drop = np.any(np.all(u[None, :, :] < vy[:, None, :], axis=2), axis=1)
        keep.append(~drop)
    return keep


def _ladder(rng, d, lo=None):
    """(GRID_BINS+1, d) f32 ladder ``lo + i*step``, as the device builds."""
    lo = rng.random(d).astype(np.float32) * 4 - 2 if lo is None else lo
    step = (rng.random(d).astype(np.float32) + 0.1) / 8
    i = np.arange(GRID_BINS + 1, dtype=np.float32)
    return (lo[None, :] + i[:, None] * step[None, :]).astype(np.float32)


def _rep_codes(rng, b, n_reps, masked=0):
    """Codes ``#(b < x)`` of ``n_reps`` representatives drawn over the
    ladder's low half; the last ``masked`` are out-of-count (GRID_BINS+1)."""
    lo, hi = b[0], b[GRID_BINS // 2]
    x = lo + rng.random((n_reps, b.shape[1])).astype(np.float32) * (hi - lo)
    ux = (b[None, :, :] < x[:, None, :]).sum(axis=1).astype(np.int32)
    if masked:
        ux[-masked:] = GRID_BINS + 1
    return ux


def _rows(rng, b, n):
    """Rows over the ladder's range and past both ends, a third of them
    sitting exactly on a ladder bound or one f32 ulp either side."""
    d = b.shape[1]
    span = b[-1] - b[0]
    y = b[0] - span * 0.1 + rng.random((n, d)).astype(np.float32) * span * 1.2
    on = rng.random((n, d)) < 0.33
    k = rng.integers(0, GRID_BINS + 1, (n, d))
    exact = b[k, np.arange(d)[None, :]]
    nudge = rng.integers(-1, 2, (n, d))
    exact = np.where(
        nudge < 0, np.nextafter(exact, np.float32(-np.inf)),
        np.where(nudge > 0, np.nextafter(exact, np.float32(np.inf)), exact),
    )
    return np.where(on, exact, y).astype(np.float32)


def _sprinkle_nonfinite(rng, y):
    y = y.copy()
    n, d = y.shape
    for v in (np.nan, np.inf, -np.inf, -0.0, 0.0):
        idx = rng.integers(0, n, max(1, n // 20))
        y[idx, rng.integers(0, d, idx.shape[0])] = v
    return y


def _pset(monkeypatch, P, d, bounds, ux):
    """A set whose launched grid summary is exactly (bounds, ux)."""
    monkeypatch.setenv("SKYLINE_FLUSH_PREFILTER", "1")
    pset = PartitionSet(P, d)
    flight = FlightRecorder(64)
    pset.attach_observability(flight=flight)
    pset._grid_dev = (bounds, ux)
    pset._grid_host = None
    return pset, flight


def _assert_same_keep(pset, bounds, ux, rows):
    want = _oracle_keep(bounds, ux, rows)
    got = pset._prefilter_rows([r.copy() for r in rows])
    for p, r in enumerate(rows):
        assert got[p].tobytes() == r[want[p]].tobytes(), f"partition {p}"
    return sum(int((~k).sum()) for k in want)


@pytest.mark.parametrize("d", [3, 4, 8, 9, 10])
def test_keep_mask_matches_broadcast(monkeypatch, d):
    """Random ladders and representatives, rows on and around the
    bounds, NaN / +-inf / -0.0 coordinates: the keep mask (and so the
    survivors and their order) is the broadcast formula's, row for row."""
    rng = np.random.default_rng(100 + d)
    P = 3
    bounds = np.stack([_ladder(rng, d) for _ in range(P)])
    ux = np.stack(
        [_rep_codes(rng, bounds[p], GRID_REPS, masked=5 * p) for p in range(P)]
    )
    rows = [_sprinkle_nonfinite(rng, _rows(rng, bounds[p], 3000)) for p in range(P)]
    pset, _ = _pset(monkeypatch, P, d, bounds, ux)
    dropped = _assert_same_keep(pset, bounds, ux, rows)
    assert dropped > 0  # the certificate is live, not vacuously equal
    assert pset.prefilter_dropped == dropped


def test_zero_ladder_signed_zero(monkeypatch):
    """A ladder starting at 0.0: -0.0 and 0.0 coordinates code alike."""
    rng = np.random.default_rng(7)
    d = 4
    b = _ladder(rng, d, lo=np.zeros(d, np.float32))
    ux = _rep_codes(rng, b, 16)[None]
    y = _rows(rng, b, 2000)
    y[::3, 1] = -0.0
    y[1::3, 2] = 0.0
    pset, _ = _pset(monkeypatch, 1, d, b[None], ux)
    _assert_same_keep(pset, b[None], ux, [y])


def test_disabled_dim_and_empty_partition(monkeypatch):
    """A ladder whose column f32 rounding left non-increasing is disabled
    by validation (no drops in that partition); an empty partition's NaN
    ladder drops nothing; a healthy partition beside them still drops."""
    rng = np.random.default_rng(9)
    d = 4
    good = _ladder(rng, d)
    flat = _ladder(rng, d)
    flat[10:12, 2] = flat[10, 2]  # one repeated bound: column 2 disabled
    nan = np.full((GRID_BINS + 1, d), np.nan, np.float32)
    bounds = np.stack([good, flat, nan])
    ux = np.stack([
        _rep_codes(rng, good, GRID_REPS),
        _rep_codes(rng, flat, GRID_REPS),
        np.full((GRID_REPS, d), GRID_BINS + 1, np.int32),
    ])
    rows = [_rows(rng, good, 1500), _rows(rng, flat, 1500),
            rng.random((700, d)).astype(np.float32)]
    pset, _ = _pset(monkeypatch, 3, d, bounds, ux)
    want = _oracle_keep(bounds, ux, rows)
    assert not want[0].all() and want[1].all() and want[2].all()
    _assert_same_keep(pset, bounds, ux, rows)


@pytest.mark.parametrize("n_reps, masked", [(1, 0), (5, 0), (12, 4), (64, 64)])
def test_few_and_masked_representatives(monkeypatch, n_reps, masked):
    """Fewer than GRID_REPS representatives, some or all masked to
    GRID_BINS+1 (out of count): all-masked partitions drop nothing."""
    rng = np.random.default_rng(n_reps * 31 + masked)
    d = 5
    b = _ladder(rng, d)
    ux = _rep_codes(rng, b, n_reps, masked=masked)[None]
    rows = [_rows(rng, b, 2500)]
    pset, _ = _pset(monkeypatch, 1, d, b[None], ux)
    dropped = _assert_same_keep(pset, b[None], ux, rows)
    if masked == n_reps:
        assert dropped == 0


@pytest.mark.parametrize(
    "n", [_PREFILTER_CHUNK - 1, _PREFILTER_CHUNK, _PREFILTER_CHUNK + 1]
)
@pytest.mark.parametrize("d", [4, 10])
def test_chunk_edges(monkeypatch, n, d):
    """Partitions one row short of, at and past a chunk: the chunk seam
    neither drops nor keeps a row the whole-partition formula would not."""
    rng = np.random.default_rng(n + d)
    b = _ladder(rng, d)
    ux = _rep_codes(rng, b, GRID_REPS)[None]
    rows = [_sprinkle_nonfinite(rng, _rows(rng, b, n))]
    pset, _ = _pset(monkeypatch, 1, d, b[None], ux)
    _assert_same_keep(pset, b[None], ux, rows)


@pytest.mark.parametrize("d, path", [(4, "packed"), (10, "broadcast")])
def test_path_counter_and_flight_note(monkeypatch, d, path):
    """The path is chosen from ``dims``: ``prefilter_packed_seen`` counts
    the rows that went through the packed test, and the flight note names
    the path taken."""
    rng = np.random.default_rng(d)
    b = _ladder(rng, d)
    ux = np.stack([_rep_codes(rng, b, GRID_REPS),
                   np.full((GRID_REPS, d), GRID_BINS + 1, np.int32)])
    rows = [_rows(rng, b, 900), _rows(rng, b, 300)]
    pset, flight = _pset(monkeypatch, 2, d, np.stack([b, b]), ux)
    _assert_same_keep(pset, np.stack([b, b]), ux, rows)
    cs = pset.flush_cascade_stats()
    assert cs["prefilter_seen"] == 1200
    # partition 1 has no certifying representative, so no test runs there
    assert cs["prefilter_packed_seen"] == (900 if path == "packed" else 0)
    (note,) = [e for e in flight.snapshot() if e["kind"] == "flush.prefilter"]
    assert note["path"] == path and note["seen"] == 1200
    assert (d <= _PACKED_MAX_DIMS) == (path == "packed")


def test_packed_fields_never_borrow():
    """Every code pair at the field extremes, in all nine fields at once:
    bit 6 of each field of W - U is set iff the representative's code is
    strictly below the row's — the packed drop equals the direct compare."""
    d = _PACKED_MAX_DIMS
    rng = np.random.default_rng(3)
    ends_v = np.array([-1, 0, 1, GRID_BINS - 1, GRID_BINS])
    ends_u = np.array([0, 1, GRID_BINS - 1, GRID_BINS])
    vy = ends_v[rng.integers(0, ends_v.size, (4000, d))]
    vy[:, 0] = np.resize(ends_v, 4000)
    u = ends_u[rng.integers(0, ends_u.size, (40, d))]
    u = np.concatenate([u, np.zeros((1, d), np.int64)])
    # a low row dominated by the all-zero code in every field but one
    vy[-1] = 1
    vy[-1, 4] = 0
    for k in range(u.shape[0]):
        U = _grid_certifiers(u[k : k + 1].astype(np.int32), packed=True)
        want = np.all(u[k][None, :] < vy, axis=1)
        assert (_packed_drop(vy, U) == want).all(), k
    assert not _packed_drop(vy[-1:], _grid_certifiers(
        np.zeros((1, d), np.int32), packed=True))[0]


def test_certifiers_drop_covered_codes():
    """Masked, duplicate and covered representatives are left out; what
    stays certifies the same rows as the full set."""
    u = np.array([
        [1, 2, 3], [1, 2, 3], [2, 2, 3], [0, 5, 1],
        [GRID_BINS + 1, 0, 0], [3, 0, 9],
    ], np.int32)
    kept = _grid_certifiers(u, packed=False)
    assert sorted(map(tuple, kept)) == [(0, 5, 1), (1, 2, 3), (3, 0, 9)]
    vy = np.stack(np.meshgrid(*[np.arange(-1, 11)] * 3), -1).reshape(-1, 3)
    full = u[(u <= GRID_BINS).all(axis=1)]
    want = np.any(np.all(full[None] < vy[:, None], axis=2), axis=1)
    got = _packed_drop(vy, _grid_certifiers(u, packed=True))
    assert (got == want).all()
