"""The capture clock's counts, and the incremental merge's pair and tile
counts under one hot partition: each vmapped round runs every partition
against the prefix sized to the largest one's live count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skybench import reference
from skyline_tpu.metrics import tracing
from skyline_tpu.ops import dispatch
from skyline_tpu.ops.pallas_dominance import COL_TILE, ROW_TILE, tile_pairs
from skyline_tpu.stream import batched, window
from skyline_tpu.stream.batched import PartitionSet


@pytest.fixture
def clock():
    tracing.CAPTURED.reset()
    yield tracing.CAPTURED
    tracing.CAPTURED.reset()


def test_counts_only_while_a_capture_is_on(clock, tmp_path):
    clock.count("flush/merge_pairs", 7)
    assert clock.counts() == {}
    with jax.profiler.trace(str(tmp_path)):
        clock.count("flush/merge_pairs", 7)
        clock.count("flush/merge_pairs", 5)
        clock.count("flush/merge_live_pairs", 3)
    clock.count("flush/merge_pairs", 100)
    assert clock.counts() == {"flush/merge_live_pairs": 3, "flush/merge_pairs": 12}
    clock.reset()
    assert clock.counts() == {}


def _hot_batches(rng, n_flushes):
    """Per flush, 4 partitions' integer rows: partition 0 takes 3,000
    anti-correlated rows, a third of them copies of the origin (its skyline
    grows by ~1,000 rows a flush); the others take 150 uniform rows."""
    out = []
    for _ in range(n_flushes):
        hot = rng.integers(0, 100, (3000, 4))
        hot[:, 3] = 300 - hot[:, :3].sum(axis=1)
        hot[rng.random(3000) < 1 / 3] = 0
        cold = [rng.integers(1, 1000, (150, 4)) for _ in range(3)]
        out.append([hot.astype(np.float32)] + [c.astype(np.float32) for c in cold])
    return out


def test_hot_partition_merge_counts(clock, tmp_path, monkeypatch):
    rng = np.random.default_rng(25)
    P, d = 4, 4
    pset = PartitionSet(P, d, buffer_size=1024)
    rounds = []
    merge = batched.merge_step_active

    def recording(sky, sky_valid, batch, bvalid, active, out_active, mp=False):
        widths = np.asarray(bvalid).sum(axis=1)
        live = np.asarray(sky_valid)[:, :active].sum(axis=1)
        rounds.append((batch.shape[0], active, batch.shape[1], widths, live,
                       pset._count_ub.copy()))
        return merge(sky, sky_valid, batch, bvalid, active, out_active, mp)

    monkeypatch.setattr(batched, "merge_step_active", recording)
    seen = [[] for _ in range(P)]
    with jax.profiler.trace(str(tmp_path)):
        for k, flush in enumerate(_hot_batches(rng, 5)):
            for p, rows in enumerate(flush):
                pset.add_batch(p, rows, max_id=k, now_ms=0.0)
                seen[p].append(rows)
            pset.flush_all()
    counts = clock.counts()

    assert len(rounds) > 5  # the hot partition takes extra rounds
    assert max(r[1] for r in rounds) >= 4096  # its prefix outgrew the cold ones
    assert counts["flush/merge_pairs"] == sum(n * a * b for n, a, b, *_ in rounds)
    assert counts["flush/merge_live_pairs"] == sum(
        int((np.minimum(ub, a) * w).sum()) for _, a, _, w, _, ub in rounds
    )
    exact_live = sum(int((np.minimum(lv, a) * w).sum()) for _, a, _, w, lv, _ in rounds)
    assert exact_live <= counts["flush/merge_live_pairs"] <= counts["flush/merge_pairs"]
    # the cold partitions' ~100 rows against the hot one's prefix: mostly padding
    assert counts["flush/merge_live_pairs"] < counts["flush/merge_pairs"] / 2

    for p in range(P):
        want = reference.skyline(np.concatenate(seen[p]))
        assert reference.multiset_gap(pset.snapshot(p), want) == 0, p
    assert pset.snapshot(0).shape[0] > 4096


def test_no_counts_off_a_capture(clock):
    rng = np.random.default_rng(26)
    pset = PartitionSet(4, 4, buffer_size=1024)
    for p, rows in enumerate(_hot_batches(rng, 1)[0]):
        pset.add_batch(p, rows, max_id=0, now_ms=0.0)
    pset.flush_all()
    assert pset._tiles_run_dev is None  # nothing kept on the device either
    pset.global_merge_stats()
    assert clock.counts() == {}


def _dominated_np(x, xv, y):
    """dominated[j] = some valid x_i dominates y_j, in dominator chunks."""
    out = np.zeros(y.shape[0], bool)
    for lo in range(0, x.shape[0], 1024):
        xs, vs = x[lo : lo + 1024], xv[lo : lo + 1024]
        le = (xs[:, None, :] <= y[None]).all(-1)
        lt = (xs[:, None, :] < y[None]).any(-1)
        out |= (le & lt & vs[:, None]).any(0)
    return out


def _tiles_np(x, xv, y, yv):
    """Tile pairs of one rectangular kernel launch whose body runs: the
    skip predicate (min valid dominator sum > max valid victim sum, f32
    sums in dimension order) per (row tile, col tile)."""
    rt, ct = min(ROW_TILE, x.shape[0]), min(COL_TILE, y.shape[0])
    sx, sy = x[:, 0].copy(), y[:, 0].copy()
    for k in range(1, x.shape[1]):
        sx = sx + x[:, k]
        sy = sy + y[:, k]
    lo = np.where(xv, sx, np.float32(np.inf)).reshape(-1, rt).min(axis=1)
    hi = np.where(yv, sy, np.float32(-np.inf)).reshape(-1, ct).max(axis=1)
    return int((~(lo[:, None] > hi[None, :])).sum())


def test_pallas_core_matches_xla_and_counts_its_tiles(clock, tmp_path, monkeypatch):
    """Each round of the hot-partition flush through the Pallas core
    (interpret mode) gives the XLA core's values, validity and counts, and
    the tile pairs whose body runs are the skip predicate's, in numpy."""
    rng = np.random.default_rng(27)
    P, d = 4, 4
    pset = PartitionSet(P, d, buffer_size=1024)
    rounds = []
    merge = batched.merge_step_active

    def recording(sky, sky_valid, batch, bvalid, active, out_active, mp=False):
        args = [np.asarray(a) for a in (sky, sky_valid, batch, bvalid)]
        out = merge(sky, sky_valid, batch, bvalid, active, out_active, mp)
        rounds.append((args, active, out_active, mp, [np.asarray(o) for o in out]))
        return out

    monkeypatch.setattr(batched, "merge_step_active", recording)
    with jax.profiler.trace(str(tmp_path)):
        for k, flush in enumerate(_hot_batches(rng, 3)):
            for p, rows in enumerate(flush):
                pset.add_batch(p, rows, max_id=k, now_ms=0.0)
            pset.flush_all()
        pset.global_merge_stats()
    counts = clock.counts()
    assert pset._tiles_run_dev is None  # read at the harvest

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setenv("SKYLINE_PALLAS_INTERPRET", "1")
    # a fresh function, so no trace of the XLA core is reused
    pallas = jax.jit(
        lambda s, sv, b, bv, active, out_active, mp: (
            window.merge_step_active.__wrapped__(s, sv, b, bv, active, out_active, mp)
        ),
        static_argnames=("active", "out_active", "mp"),
    )
    launched = run = 0
    for (sky, sv, batch, bv), active, out_active, mp, want in rounds:
        assert not mp  # the CPU default: batch validity reaches the passes as given
        got = pallas(*map(jnp.asarray, (sky, sv, batch, bv)), active, out_active, mp)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)
        B = batch.shape[1]
        launched += P * (tile_pairs(B, B) + tile_pairs(active, B) + tile_pairs(B, active))
        for p in range(P):
            s, v = sky[p, :active], sv[p, :active]
            local = bv[p] & ~_dominated_np(batch[p], bv[p], batch[p])
            keep_b = local & ~_dominated_np(s, v, batch[p])
            run += (
                _tiles_np(batch[p], bv[p], batch[p], bv[p])
                + _tiles_np(s, v, batch[p], bv[p])
                + _tiles_np(batch[p], keep_b, s, v)
            )
        assert int(want[4]) <= launched

    assert max(r[1] for r in rounds) >= 4096  # the hot prefix outgrew the cold
    assert counts["flush/merge_tiles"] == launched
    assert counts["flush/merge_tiles_run"] == run
    # the cold partitions' padded tiles skip
    assert run < launched
