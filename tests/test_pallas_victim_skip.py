"""Victim validity in the rectangular Pallas dominance kernel's tile skip
(interpret mode on CPU).

``dominated_by_pallas(..., y_valid=...)`` lets ``_tile_sum_skip`` leave
invalid victims out of its max, so a tile of padded victims, or of invalid
dominators against finite victims, skips its body. The verdict of every
valid victim must stay what the oracle and the ``y_valid=None`` kernel give;
invalid victims' verdicts are unspecified (callers mask them).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skyline_tpu.ops.pallas_dominance import (
    dominated_by_pallas,
    tile_pairs,
    tiles_run,
)

D = 4
NX, NY = 256, 1024  # two dominator tiles x two victim tiles
RT, CT = 128, 512


def _oracle(x, xv, y):
    """dominated[j] = some valid x_i is <= y_j everywhere and < somewhere."""
    le = (x[:, None, :] <= y[None, :, :]).all(-1)
    lt = (x[:, None, :] < y[None, :, :]).any(-1)
    return (le & lt & xv[:, None]).any(0)


def _tiles_np(x, xv, y, yv):
    """The skip predicate in numpy: per (row tile, col tile), the body runs
    unless min valid dominator sum > max valid victim sum (f32, summed in
    dimension order)."""
    sx, sy = x[:, 0].copy(), y[:, 0].copy()
    for k in range(1, x.shape[1]):
        sx = sx + x[:, k]
        sy = sy + y[:, k]
    sx = np.where(xv, sx, np.float32(np.inf))
    sy = np.where(yv, sy, np.float32(-np.inf))
    lo = sx.reshape(-1, RT).min(axis=1)
    hi = sy.reshape(-1, CT).max(axis=1)
    return int((~(lo[:, None] > hi[None, :])).sum())


def _case(name, rng):
    x = rng.integers(0, 20, (NX, D)).astype(np.float32)
    y = rng.integers(0, 30, (NY, D)).astype(np.float32)
    xv = np.ones(NX, bool)
    yv = np.ones(NY, bool)
    if name == "pad_victim_tile":
        # the second victim tile is all padding
        yv[CT:] = False
        y[CT:] = np.inf
    elif name == "invalid_dominator_tile":
        # the second dominator tile is invalid, with coordinates that would
        # dominate every victim if they counted
        xv[RT:] = False
        x[RT:] = 0.0
        y = y + 1.0
    elif name == "mixed":
        xv = rng.random(NX) < 0.6
        yv = rng.random(NY) < 0.5
        x[~xv & (rng.random(NX) < 0.5)] = np.inf
        y[~yv & (rng.random(NY) < 0.5)] = np.inf
    elif name == "inf_victim":
        # a valid victim with an +inf coordinate, dominated, in a tile
        # otherwise of padding: "+inf" must not read as "pad"
        yv[CT:] = False
        y[CT:] = np.inf
        yv[CT + 7] = True
        y[CT + 7] = [np.inf, 25.0, 25.0, 25.0]
        x[3] = [1.0, 2.0, 3.0, 4.0]
    else:
        raise ValueError(name)
    return x, xv, y, yv


CASES = ["pad_victim_tile", "invalid_dominator_tile", "mixed", "inf_victim"]


def _run(x, xv, y, yv, mp, vmapped):
    kw = dict(interpret=True, row_tile=RT, col_tile=CT, mp=mp)
    xt, yt = jnp.asarray(x.T), jnp.asarray(y.T)
    xvj, yvj = jnp.asarray(xv), jnp.asarray(yv)
    if not vmapped:
        return (
            np.asarray(dominated_by_pallas(xt, xvj, yt, y_valid=yvj, **kw)),
            np.asarray(dominated_by_pallas(xt, xvj, yt, **kw)),
        )
    # two partitions: the case, and the same case with the roles of the
    # victim tiles' contents swapped
    swap = np.concatenate([np.arange(CT, NY), np.arange(CT)])
    stack = lambda a, b: jnp.stack([a, b])  # noqa: E731
    xs, xvs = stack(xt, xt), stack(xvj, xvj)
    ys, yvs = stack(yt, yt[:, swap]), stack(yvj, yvj[swap])
    with_v = jax.vmap(
        lambda a, av, b, bv: dominated_by_pallas(a, av, b, y_valid=bv, **kw)
    )(xs, xvs, ys, yvs)
    without = jax.vmap(lambda a, av, b: dominated_by_pallas(a, av, b, **kw))(
        xs, xvs, ys
    )
    inv = np.argsort(swap)
    w, wo = np.asarray(with_v), np.asarray(without)
    # each partition's verdicts back in the case's victim order; both must
    # agree, so the second is checked against the first below
    assert (w[1][inv][yv] == w[0][yv]).all()
    assert (wo[1][inv] == wo[0]).all()
    return w[0], wo[0]


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmapped"])
@pytest.mark.parametrize("mp", [False, True], ids=["f32", "mp"])
@pytest.mark.parametrize("case", CASES)
def test_valid_victims_keep_their_verdicts(case, mp, vmapped):
    rng = np.random.default_rng(CASES.index(case))
    x, xv, y, yv = _case(case, rng)
    want = _oracle(x, xv, y)
    with_v, without = _run(x, xv, y, yv, mp, vmapped)
    assert (without == want).all()
    assert (with_v[yv] == want[yv]).all()
    if case == "inf_victim":
        assert with_v[CT + 7]
    if case == "invalid_dominator_tile":
        assert want.any()  # the valid tile still finds dominated victims


@pytest.mark.parametrize("case", CASES)
def test_tiles_run_is_the_kernel_predicate(case):
    rng = np.random.default_rng(100 + CASES.index(case))
    x, xv, y, yv = _case(case, rng)
    got = int(tiles_run(
        jnp.asarray(x.T), jnp.asarray(xv), jnp.asarray(y.T), jnp.asarray(yv),
        row_tile=RT, col_tile=CT,
    ))
    assert got == _tiles_np(x, xv, y, yv)
    assert tile_pairs(NX, NY, RT, CT) == 4
    # a whole victim tile of padding, or a whole dominator tile invalid,
    # takes two of the four pairs off
    assert got == (2 if case in ("pad_victim_tile", "invalid_dominator_tile") else 4)


def test_victim_validity_engages_the_skip():
    """A tile of invalid victims that valid dominators WOULD dominate: with
    ``y_valid`` its body is skipped (verdicts stay at their zero init);
    without, the +inf-free tile is computed."""
    x = np.full((NX, D), 5.0, np.float32)
    y = np.full((NY, D), 9.0, np.float32)
    xv = np.ones(NX, bool)
    yv = np.zeros(NY, bool)
    yv[:CT] = True
    y[:CT] = 1.0  # the valid tile: nothing dominates it
    kw = dict(interpret=True, row_tile=RT, col_tile=CT)
    args = (jnp.asarray(x.T), jnp.asarray(xv), jnp.asarray(y.T))
    with_v = np.asarray(dominated_by_pallas(*args, y_valid=jnp.asarray(yv), **kw))
    without = np.asarray(dominated_by_pallas(*args, **kw))
    assert without[CT:].all() and not without[:CT].any()
    assert not with_v.any()
    assert int(tiles_run(*args, jnp.asarray(yv), row_tile=RT, col_tile=CT)) == 0
