"""Property tests for the dominance/skyline kernels vs the numpy oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from skyline_tpu.ops import (
    skyline_mask_scan,
    PAD_VALUE,
    dominance_mask,
    dominates,
    pad_window,
    skyline_mask,
    skyline_mask_blocked,
    skyline_large,
    skyline_np,
)
from skyline_tpu.ops.dominance import compact
from skyline_tpu.ops.block_skyline import dominated_by_blocked

from conftest import assert_same_set


def test_dominates_pairs():
    assert bool(dominates(jnp.array([1.0, 1.0]), jnp.array([2.0, 2.0])))
    assert bool(dominates(jnp.array([1.0, 2.0]), jnp.array([1.0, 3.0])))
    # equal points do not dominate each other (ServiceTuple.java:67-77)
    assert not bool(dominates(jnp.array([1.0, 1.0]), jnp.array([1.0, 1.0])))
    # incomparable
    assert not bool(dominates(jnp.array([1.0, 3.0]), jnp.array([3.0, 1.0])))
    assert not bool(dominates(jnp.array([2.0, 2.0]), jnp.array([1.0, 1.0])))


def test_dominance_mask_matches_pairwise(rng):
    x = rng.uniform(0, 100, size=(50, 3))
    dom = np.asarray(dominance_mask(jnp.asarray(x), jnp.asarray(x)))
    for i in range(50):
        for j in range(50):
            expect = np.all(x[i] <= x[j]) and np.any(x[i] < x[j])
            assert dom[i, j] == expect


@pytest.mark.parametrize("d", [1, 2, 4, 8])
@pytest.mark.parametrize("n", [1, 17, 300])
def test_skyline_mask_vs_oracle(rng, n, d):
    x = rng.uniform(0, 1000, size=(n, d)).astype(np.float32)
    keep = np.asarray(skyline_mask(jnp.asarray(x)))
    assert_same_set(x[keep], skyline_np(x))


def test_skyline_with_duplicates():
    # All duplicates of a skyline point survive (reference behavior:
    # 1,716 copies of [0,0] in the 2D correlated run, SURVEY.md §4).
    x = np.array([[0.0, 0.0], [0.0, 0.0], [1.0, 1.0], [0.0, 0.0]])
    keep = np.asarray(skyline_mask(jnp.asarray(x)))
    assert list(keep) == [True, True, False, True]


def test_padding_is_dominance_neutral(rng):
    x = rng.uniform(0, 1000, size=(33, 4)).astype(np.float32)
    vals, valid = pad_window(x, 64)
    keep = np.asarray(skyline_mask(vals, valid))
    assert not keep[33:].any()
    assert_same_set(np.asarray(vals)[keep], skyline_np(x))


@pytest.mark.parametrize("n,block", [(100, 32), (1000, 128), (4096, 1024)])
def test_skyline_mask_blocked_matches_dense(rng, n, block):
    for d in (2, 5):
        x = rng.uniform(0, 1000, size=(n, d)).astype(np.float32)
        dense = np.asarray(skyline_mask(jnp.asarray(x)))
        blocked = np.asarray(skyline_mask_blocked(jnp.asarray(x), block=block))
        np.testing.assert_array_equal(dense, blocked)


def test_skyline_mask_blocked_with_padding(rng):
    x = rng.uniform(0, 1000, size=(70, 3)).astype(np.float32)
    vals, valid = pad_window(x, 128)
    keep = np.asarray(skyline_mask_blocked(vals, valid, block=32))
    assert not keep[70:].any()
    assert_same_set(np.asarray(vals)[keep], skyline_np(x))


def test_dominated_by_blocked_matches_dense(rng):
    y = rng.uniform(0, 1000, size=(64, 3)).astype(np.float32)
    x = rng.uniform(0, 1000, size=(200, 3)).astype(np.float32)
    xv = rng.random(200) < 0.7
    from skyline_tpu.ops.dominance import dominated_by

    dense = np.asarray(dominated_by(jnp.asarray(y), jnp.asarray(x), jnp.asarray(xv)))
    blocked = np.asarray(
        dominated_by_blocked(jnp.asarray(y), jnp.asarray(x), jnp.asarray(xv), block=64)
    )
    np.testing.assert_array_equal(dense, blocked)


@pytest.mark.parametrize("dist", ["uniform", "anti"])
def test_skyline_large_vs_oracle(rng, dist):
    n, d = 30_000, 4
    if dist == "uniform":
        x = rng.uniform(0, 10000, size=(n, d)).astype(np.float32)
    else:
        base = rng.uniform(0, 10000, size=(n, 1))
        x = np.clip(
            10000 - base + rng.normal(0, 300, size=(n, d)), 0, 10000
        ).astype(np.float32)
    got = skyline_large(x, block=4096, dense_threshold=2048)
    # oracle on a pre-reduced set to keep the n^2 python loop tractable:
    # skyline(x) == skyline over the union of chunked skylines (merge law)
    chunks = [skyline_np(c) for c in np.array_split(x, 10)]
    expect = skyline_np(np.concatenate(chunks, axis=0))
    assert_same_set(got, expect)


def test_merge_law(rng):
    # skyline(skyline(X) U skyline(Y)) == skyline(X U Y)  (SURVEY.md §4)
    x = rng.uniform(0, 100, size=(200, 3)).astype(np.float32)
    y = rng.uniform(0, 100, size=(150, 3)).astype(np.float32)
    xs = skyline_np(x)
    ys = skyline_np(y)
    # the union-merge is expressed with the primitives the engine's merge
    # steps are built from: concat -> skyline_mask -> compact
    a, av = pad_window(xs.astype(np.float32), 256)
    b, bv = pad_window(ys.astype(np.float32), 256)
    u = jnp.concatenate([a, b], axis=0)
    uv = jnp.concatenate([av, bv], axis=0)
    vals, valid, count = compact(u, skyline_mask(u, uv), 512)
    merged = np.asarray(vals)[np.asarray(valid)]
    assert merged.shape[0] == int(count)
    assert_same_set(merged, skyline_np(np.concatenate([x, y], axis=0)))


def test_compact_packs_and_pads():
    x = jnp.array([[1.0, 1], [2, 2], [3, 3], [4, 4]])
    keep = jnp.array([False, True, False, True])
    vals, valid, count = compact(x, keep, 3)
    assert int(count) == 2
    np.testing.assert_allclose(np.asarray(vals)[:2], [[2, 2], [4, 4]])
    assert list(np.asarray(valid)) == [True, True, False]
    assert np.isinf(np.asarray(vals)[2]).all()


@pytest.mark.parametrize("n,chunk", [(100, 32), (1000, 0), (5000, 512)])
def test_skyline_mask_scan_matches_dense(rng, n, chunk):
    for d in (2, 6):
        x = rng.uniform(0, 1000, size=(n, d)).astype(np.float32)
        dense = np.asarray(skyline_mask(jnp.asarray(x)))
        scan = np.asarray(skyline_mask_scan(jnp.asarray(x), chunk=chunk))
        np.testing.assert_array_equal(dense, scan)


def test_skyline_mask_scan_with_padding(rng):
    from skyline_tpu.ops import skyline_mask_scan as sms
    x = rng.uniform(0, 1000, size=(77, 3)).astype(np.float32)
    vals, valid = pad_window(x, 128)
    keep = np.asarray(sms(vals, valid, chunk=32))
    assert not keep[77:].any()
    assert_same_set(np.asarray(vals)[keep], skyline_np(x))


@pytest.mark.parametrize("mp", [False, True])
def test_skyline_mask_pallas_interpret_matches_dense(rng, mp):
    # Pallas kernels run in interpret mode on CPU: validates kernel logic
    # (incl. the triangular skip + sum-sort wrapper) without TPU hardware.
    # mp=True runs the bf16 margin pass block by block with the f32 recheck
    # on undecided blocks; integer duplicates force the recheck.
    from skyline_tpu.ops.pallas_dominance import (
        dominated_by_pallas,
        skyline_mask_pallas,
    )
    from skyline_tpu.ops.dominance import dominated_by

    x = rng.uniform(0, 1000, size=(1500, 4)).astype(np.float32)
    dense = np.asarray(skyline_mask(jnp.asarray(x)))
    pallas = np.asarray(skyline_mask_pallas(jnp.asarray(x), interpret=True, mp=mp))
    np.testing.assert_array_equal(dense, pallas)

    xi = np.floor(x[:, :3] / 50).astype(np.float32)  # ties in every dim
    dense = np.asarray(skyline_mask(jnp.asarray(xi)))
    pallas = np.asarray(skyline_mask_pallas(jnp.asarray(xi), interpret=True, mp=mp))
    np.testing.assert_array_equal(dense, pallas)

    xd = rng.uniform(0, 1000, size=(512, 4)).astype(np.float32)
    xv = rng.random(512) < 0.7
    yv = rng.uniform(0, 1000, size=(1024, 4)).astype(np.float32)
    a = np.asarray(dominated_by(jnp.asarray(yv), jnp.asarray(xd), jnp.asarray(xv)))
    b = np.asarray(
        dominated_by_pallas(
            jnp.asarray(xd.T), jnp.asarray(xv), jnp.asarray(yv.T),
            interpret=True, mp=mp,
        )
    )
    np.testing.assert_array_equal(a, b)
