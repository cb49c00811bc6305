"""The SFS append rounds on duplicate-heavy rows: each round takes the
followers of every run of adjacent equal rows out of its dominators
(``adjacent_duplicates``), never out of its victims, and hands the
rectangular kernel the victims' validity. The survivors must stay the
multiset skyline (every copy kept) and the appended rows and their order
the host sorted cascade's, on the XLA path and the Pallas kernels
(interpret mode, f32 and mixed precision). The tile counts the rounds
report are the kernels' own skip predicate, counted in numpy."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skybench import reference
from skyline_tpu.ops.pallas_dominance import tile_pairs, tiles_run
from skyline_tpu.ops.sfs import adjacent_duplicates, sfs_round_core
from skyline_tpu.ops.sorted_sfs import sorted_sfs_keep
from skyline_tpu.utils.buckets import next_pow2

B = 1024  # two row tiles of the triangular pass, one column tile


def _plane(rng, n, d, total):
    """``n`` integer rows of coordinate sum ``total``: none dominates
    another, so every one survives."""
    x = rng.integers(0, total // d, (n, d))
    x[:, -1] = total - x[:, :-1].sum(axis=1)
    return x


def _case(name, rng):
    if name == "origin_copies":
        # the anticorr8d hot partition: the origin, clipped to, many times,
        # among rows it dominates
        x = rng.integers(0, 50, (2600, 8))
        x[rng.random(2600) < 0.6] = 0
    elif name == "repeated_runs":
        # distinct non-origin survivors, each repeated in place, and
        # dominated copies behind them
        x = np.repeat(_plane(rng, 300, 8, 400), rng.integers(1, 9, 300), axis=0)
        x = np.concatenate([x, x[::7] + 1])
    elif name == "non_adjacent":
        # copies interleaved with other rows of the same sum, so the sum
        # sort leaves them apart: the mask finds fewer runs, never a wrong one
        x = np.tile(_plane(rng, 400, 8, 400), (3, 1))
        x = np.concatenate([x, _plane(rng, 900, 8, 480)])
    elif name == "nan_rows":
        x = rng.integers(0, 30, (2200, 8)).astype(np.float64)
        x[rng.random(2200) < 0.4] = 0
        nan = np.repeat(rng.integers(0, 30, (40, 8)).astype(np.float64), 3, axis=0)
        nan[:, 2] = np.nan  # copies of rows that hold a NaN
        x = np.concatenate([x, nan])
    elif name == "signed_zero":
        # origin copies that differ only in the sign of their zeros
        x = np.zeros((1800, 4))
        x = np.where(rng.random(x.shape) < 0.5, -x, x)
        x = np.concatenate([x, rng.integers(0, 9, (700, 4))])
    elif name == "partial_block":
        # one valid row in the last block
        x = np.concatenate([np.zeros((B, 8)), rng.integers(1, 9, (1, 8))])
    else:
        raise ValueError(name)
    return x.astype(np.float32)


CASES = [
    "origin_copies", "repeated_runs", "non_adjacent", "nan_rows",
    "signed_zero", "partial_block",
]


def _rounds(xs, use_pallas, mp):
    """The SFS rounds over sum-sorted ``xs`` in B-row blocks against a
    fixed prefix: (appended rows, tile pairs run, tile pairs launched)."""
    n, d = xs.shape
    cap = next_pow2(n + B)
    rnd = jax.jit(
        functools.partial(
            sfs_round_core, active=cap, use_pallas=use_pallas, interp=True, mp=mp
        )
    )
    sky = jnp.full((cap, d), jnp.inf, jnp.float32)
    count = jnp.zeros((), jnp.int32)
    run = launched = 0
    for lo in range(0, n, B):
        blk = np.full((B, d), np.inf, np.float32)
        w = min(B, n - lo)
        blk[:w] = xs[lo : lo + w]
        sky, count, _, tiles = rnd(sky, count, jnp.asarray(blk), jnp.arange(B) < w)
        run += int(tiles)
        launched += tile_pairs(cap, B) + tile_pairs(B, B, triangular=True)
    return np.asarray(sky)[: int(count)], run, launched


@pytest.mark.parametrize(
    "use_pallas, mp", [(False, False), (True, False), (True, True)],
    ids=["xla", "pallas", "pallas_mp"],
)
@pytest.mark.parametrize("case", CASES)
def test_rounds_keep_every_copy_in_order(case, use_pallas, mp):
    rng = np.random.default_rng(CASES.index(case))
    x = _case(case, rng)
    xs = x[np.argsort(x.sum(axis=1), kind="stable")]
    got, run, launched = _rounds(xs, use_pallas, mp)
    # the appended rows and their order: the host cascade's, byte for byte
    assert got.tobytes() == xs[sorted_sfs_keep(xs)].tobytes()
    # the multiset skyline; a row holding a NaN is never dominated
    nan = np.isnan(x).any(axis=1)
    got_nan = np.isnan(got).any(axis=1)
    assert got_nan.sum() == nan.sum()
    assert reference.multiset_gap(got[~got_nan], reference.skyline(x[~nan])) == 0
    assert 0 <= run <= launched
    if case in ("origin_copies", "partial_block"):
        assert run < launched // 2  # the copies' tiles skip


@pytest.mark.parametrize("seed", range(4))
def test_mask_marks_the_followers_of_adjacent_equal_valid_rows(seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 3, (300, 2)).astype(np.float32)
    x[rng.random(300) < 0.05, 1] = np.nan
    x[rng.random(300) < 0.05, 0] = -0.0
    valid = rng.random(300) < 0.8
    got = np.asarray(adjacent_duplicates(jnp.asarray(x), jnp.asarray(valid)))
    want = np.zeros(300, bool)
    for i in range(1, 300):
        want[i] = valid[i] and valid[i - 1] and all(x[i] == x[i - 1])
    np.testing.assert_array_equal(got, want)
    assert got.any() and not got[0]
    assert not (got & ~valid).any()
    assert not got[np.isnan(x).any(axis=1)].any()


def test_mask_hand_cases():
    x = jnp.asarray(
        [[0, 0], [0, 0], [-0.0, 0], [1, 2], [1, 2], [np.nan, 1], [np.nan, 1],
         [5, 5], [5, 5], [5, 5]],
        jnp.float32,
    )
    valid = jnp.asarray([1, 1, 1, 1, 1, 1, 1, 1, 0, 1], bool)
    got = np.asarray(adjacent_duplicates(x, valid))
    # ±0 are equal; NaN rows never; an invalid row is never marked and
    # breaks its run, so the row after it heads a new one
    assert got.tolist() == [0, 1, 1, 0, 1, 0, 0, 0, 0, 0]


def _tiles_np(x, xv, y, yv, rt, ct, triangular):
    """The kernels' predicate per (row tile i, col tile j), in numpy: the
    body runs unless min valid dominator sum > max victim sum (valid ones,
    when ``yv`` is given), and, triangular, only where i*rt <= j*ct+ct-1."""
    sx, sy = x[:, 0].copy(), y[:, 0].copy()
    for k in range(1, x.shape[1]):
        sx = sx + x[:, k]
        sy = sy + y[:, k]
    sx = np.where(xv, sx, np.float32(np.inf))
    if yv is not None:
        sy = np.where(yv, sy, np.float32(-np.inf))
    lo, hi = sx.reshape(-1, rt).min(axis=1), sy.reshape(-1, ct).max(axis=1)
    n = 0
    for i in range(lo.size):
        for j in range(hi.size):
            if triangular and i * rt > j * ct + ct - 1:
                continue
            n += not lo[i] > hi[j]
    return n


@pytest.mark.parametrize("rt, ct", [(128, 256), (256, 128), (256, 256)])
@pytest.mark.parametrize("triangular", [False, True], ids=["rect", "tri"])
def test_tiles_run_is_the_kernel_predicate(rt, ct, triangular):
    rng = np.random.default_rng(rt + ct + triangular)
    n, d = 1024, 8
    x = rng.integers(0, 20, (n, d)).astype(np.float32)
    x[rng.random(n) < 0.6] = 0
    x = x[np.argsort(x.sum(axis=1), kind="stable")]
    xv = np.ones(n, bool)
    xv[n - 300 :] = False
    x[n - 300 :] = np.inf
    dom = xv & ~np.asarray(adjacent_duplicates(jnp.asarray(x), jnp.asarray(xv)))
    xt = jnp.asarray(x.T)
    if triangular:
        got = int(tiles_run(xt, jnp.asarray(dom), xt, row_tile=rt, col_tile=ct,
                            triangular=True))
        want = _tiles_np(x, dom, x, None, rt, ct, True)
        grid = sum(i * rt <= j * ct + ct - 1
                   for i in range(n // rt) for j in range(n // ct))
    else:
        y = rng.integers(0, 40, (n, d)).astype(np.float32)
        yv = rng.random(n) < 0.7
        got = int(tiles_run(xt, jnp.asarray(dom), jnp.asarray(y.T),
                            jnp.asarray(yv), row_tile=rt, col_tile=ct))
        want = _tiles_np(x, dom, y, yv, rt, ct, False)
        grid = (n // rt) * (n // ct)
    assert got == want
    assert tile_pairs(n, n, rt, ct, triangular=triangular) == grid
    assert 0 < got < grid  # row tiles of masked copies or invalid rows skip


@pytest.mark.parametrize("skewed", [True, False], ids=["sequential", "vmapped"])
def test_lazy_flush_counts_its_sfs_tiles(monkeypatch, tmp_path, skewed):
    """A traced lazy flush over host rows counts the tile pairs its SFS
    rounds launch and, read at the global merge harvest, those that run;
    the hot partition's origin copies all survive and their tiles skip."""
    from skyline_tpu.metrics import tracing
    from skyline_tpu.stream.batched import PartitionSet

    # the chip never lists the host cascade; off, the CPU runs the rounds
    monkeypatch.setenv("SKYLINE_SORTED_SFS", "off")
    rng = np.random.default_rng(28)
    P, d = 4, 8
    hot = rng.integers(0, 50, (6000, d))
    hot[rng.random(6000) < 0.7] = 0
    parts = [hot] + [rng.integers(0, 50, (300 if skewed else 6000, d)) for _ in range(P - 1)]
    pset = PartitionSet(P, d, buffer_size=1024, flush_policy="lazy")
    tracing.CAPTURED.reset()
    try:
        with jax.profiler.trace(str(tmp_path)):
            for p, rows in enumerate(parts):
                pset.add_batch(p, rows.astype(np.float32), max_id=0, now_ms=0.0)
            pset.flush_all()
            pset.global_merge_stats()
        counts = tracing.CAPTURED.counts()
    finally:
        tracing.CAPTURED.reset()
    assert pset._sfs_tiles_run_dev is None  # read at the harvest
    assert 0 < counts["flush/sfs_tiles_run"] < counts["flush/sfs_tiles"]
    for p, rows in enumerate(parts):
        got = pset.snapshot(p)
        assert reference.multiset_gap(got, reference.skyline(rows)) == 0
    assert pset.snapshot(0).shape[0] == int((hot == 0).all(axis=1).sum())
