"""Flush dominance cascade (ISSUE 5): quantized grid prefilter + bf16
margin pass must never change a single output byte — property grid over
workload shapes / dims / flush policies / mesh, the edge cases that broke
naive designs (all-dropped batches, NaN/inf rows, bf16-ambiguous ties),
and direct soundness checks of the certified-margin and grid-code
schemes."""

import jax.numpy as jnp
import numpy as np
import pytest

from skyline_tpu.parallel.mesh import make_mesh
from skyline_tpu.stream import EngineConfig, SkylineEngine
from skyline_tpu.stream.batched import PartitionSet
# workload generator shared via conftest.py (satellite of ISSUE 10)
from conftest import assert_same_set, gen_points as _gen


def _run_rounds(pset, rng, x, P, rounds=2):
    """Feed ``x`` in ``rounds`` chunks with a flush after each — round 1's
    flush tail publishes the grid summaries round 2's prefilter uses."""
    pids = rng.integers(0, P, x.shape[0])
    step = -(-x.shape[0] // rounds)
    for lo in range(0, x.shape[0], step):
        hi = min(lo + step, x.shape[0])
        for p in range(P):
            rows = np.ascontiguousarray(x[lo:hi][pids[lo:hi] == p])
            if rows.shape[0]:
                pset.add_batch(p, rows, max_id=x.shape[0], now_ms=0.0)
        pset.flush_all()


def _state(pset, P):
    """Exact per-partition skylines (order included) + global digest."""
    snaps = [pset.snapshot(p) for p in range(P)]
    counts, surv, g, pts = pset.global_merge_stats(emit_points=True)
    return snaps, (np.asarray(counts), np.asarray(surv), int(g), pts)


def _assert_identical(a, b, ctx=""):
    sa, ga = a
    sb, gb = b
    for p, (ra, rb) in enumerate(zip(sa, sb)):
        assert ra.shape == rb.shape and ra.tobytes() == rb.tobytes(), (
            f"partition {p} skyline diverges {ctx}"
        )
    assert (ga[0] == gb[0]).all(), f"counts diverge {ctx}"
    assert (ga[1] == gb[1]).all(), f"survivors diverge {ctx}"
    assert ga[2] == gb[2], f"global count diverges {ctx}"
    assert ga[3].tobytes() == gb[3].tobytes(), f"points diverge {ctx}"


def _cascade_env(monkeypatch, on: bool):
    v = "1" if on else "0"
    monkeypatch.setenv("SKYLINE_FLUSH_PREFILTER", v)
    monkeypatch.setenv("SKYLINE_MIXED_PRECISION", v)


@pytest.mark.parametrize("kind", ["uniform", "correlated", "anti"])
@pytest.mark.parametrize("d", [4, 8])
@pytest.mark.parametrize("policy", ["incremental", "lazy", "overlap"])
def test_cascade_byte_identity(monkeypatch, kind, d, policy):
    """Property grid: cascade on vs off is byte-identical — per-partition
    skylines (including row order) and the global merge digest."""
    monkeypatch.setenv("SKYLINE_MERGE_CACHE", "0")
    P = 3
    results = {}
    for on in (True, False):
        _cascade_env(monkeypatch, on)
        rng = np.random.default_rng(29)
        pset = PartitionSet(P, d, flush_policy=policy)
        _run_rounds(pset, rng, _gen(rng, 900, d, kind), P)
        results[on] = _state(pset, P)
        if on:
            cs = pset.flush_cascade_stats()
            assert cs["prefilter_enabled"] and cs["mixed_precision"]
            assert cs["prefilter_seen"] > 0
            assert 0 <= cs["prefilter_dropped"] <= cs["prefilter_seen"]
            assert cs["bf16_resolved"] >= 0
        else:
            cs = pset.flush_cascade_stats()
            assert cs["prefilter_dropped"] == 0 and cs["bf16_resolved"] == 0
    _assert_identical(
        results[True], results[False], f"(kind={kind} d={d} policy={policy})"
    )


def test_cascade_actually_drops(monkeypatch):
    """The grid prefilter is live, not vacuously passing: on clustered
    correlated data a later flush round drops a solid fraction."""
    monkeypatch.setenv("SKYLINE_MERGE_CACHE", "0")
    _cascade_env(monkeypatch, True)
    rng = np.random.default_rng(5)
    pset = PartitionSet(4, 4)
    _run_rounds(pset, rng, _gen(rng, 4000, 4, "uniform"), 4)
    cs = pset.flush_cascade_stats()
    assert cs["prefilter_dropped"] > 0, cs
    assert cs["prefilter_drop_fraction"] == pytest.approx(
        cs["prefilter_dropped"] / cs["prefilter_seen"]
    )


def test_all_dropped_batch(monkeypatch):
    """A whole batch certified-dropped by the grid: the flush degenerates
    to a no-op for that partition and state matches the exact path."""
    monkeypatch.setenv("SKYLINE_MERGE_CACHE", "0")

    def run(on):
        _cascade_env(monkeypatch, on)
        rng = np.random.default_rng(11)
        pset = PartitionSet(2, 4)
        strong = (rng.random((64, 4)) * 0.01).astype(np.float32)
        weak = (0.5 + rng.random((300, 4)) * 0.5).astype(np.float32)
        pset.add_batch(0, strong, max_id=64, now_ms=0.0)
        pset.flush_all()  # publishes the grid over the strong skyline
        pset.add_batch(0, weak, max_id=364, now_ms=0.0)
        pset.flush_all()
        return pset, _state(pset, 2)

    pset_on, state_on = run(True)
    _, state_off = run(False)
    _assert_identical(state_on, state_off, "(all-dropped batch)")
    cs = pset_on.flush_cascade_stats()
    assert cs["prefilter_dropped"] == 300, cs  # every weak row certified


def test_nan_inf_rows(monkeypatch):
    """NaN coordinates are dominance-neutral and must never be prefiltered
    (their grid code is -1 on the victim side); +inf rows are droppable.
    Cascade on/off must agree byte for byte either way."""
    monkeypatch.setenv("SKYLINE_MERGE_CACHE", "0")

    def run(on):
        _cascade_env(monkeypatch, on)
        rng = np.random.default_rng(13)
        pset = PartitionSet(2, 4)
        base = rng.random((400, 4)).astype(np.float32)
        pset.add_batch(0, base, max_id=400, now_ms=0.0)
        pset.flush_all()
        odd = rng.random((200, 4)).astype(np.float32)
        odd[:40, 1] = np.nan  # never droppable
        odd[40:80, 2] = np.inf  # droppable when the other dims certify
        pset.add_batch(0, odd, max_id=600, now_ms=0.0)
        pset.flush_all()
        return pset, _state(pset, 2)

    pset_on, state_on = run(True)
    _, state_off = run(False)
    _assert_identical(state_on, state_off, "(NaN/inf rows)")
    # NaN rows are neither dominated nor dominating: all 40 must survive
    sky0 = state_on[0][0]
    assert np.isnan(sky0).any(axis=1).sum() == 40


def test_bf16_ambiguous_ties(monkeypatch):
    """Duplicates and sub-bf16-resolution near-ties sit inside the margin:
    the bf16 pass must defer them to f32, keeping exact semantics
    (duplicates never dominate each other)."""
    monkeypatch.setenv("SKYLINE_MERGE_CACHE", "0")

    def run(on):
        _cascade_env(monkeypatch, on)
        rng = np.random.default_rng(17)
        pset = PartitionSet(2, 4)
        base = rng.random((300, 4)).astype(np.float32)
        pset.add_batch(0, base, max_id=300, now_ms=0.0)
        pset.flush_all()
        # exact duplicates of skyline rows + rows nudged by one f32 ulp
        # (far inside the bf16 margin) in a strictly-worse direction
        dup = base[:50].copy()
        nudged = np.nextafter(base[50:100], np.float32(2.0), dtype=np.float32)
        pset.add_batch(
            0, np.concatenate([dup, nudged]), max_id=400, now_ms=0.0
        )
        pset.flush_all()
        return _state(pset, 2)

    _assert_identical(run(True), run(False), "(bf16-ambiguous ties)")


@pytest.mark.parametrize("policy", ["incremental", "lazy"])
def test_meshed_engine_cascade(monkeypatch, policy):
    """Under a mesh the grid prefilter self-disables (host rows feed a
    sharded flush) but the bf16 pass runs inside the shard_map kernels —
    results must match the cascade-off meshed run exactly."""

    def run(on):
        _cascade_env(monkeypatch, on)
        rng = np.random.default_rng(19)
        eng = SkylineEngine(
            EngineConfig(
                parallelism=2, dims=4, domain_max=1.0, buffer_size=256,
                emit_skyline_points=True, flush_policy=policy,
            ),
            mesh=make_mesh(2),
        )
        x = rng.random((3000, 4)).astype(np.float32)
        eng.process_records(np.arange(1500), x[:1500])
        eng.process_trigger("q0,0")
        eng.poll_results()
        eng.process_records(np.arange(1500, 3000), x[1500:])
        eng.process_trigger("q1,0")
        (r,) = eng.poll_results()
        return r, eng.stats()["flush_cascade"]

    r_on, cs_on = run(True)
    r_off, _ = run(False)
    assert r_on["skyline_size"] == r_off["skyline_size"]
    assert_same_set(r_on["skyline_points"], r_off["skyline_points"])
    assert cs_on["prefilter_seen"] == 0  # grid prefilter inert under mesh


def test_sfs_large_skyline_mixed_precision(monkeypatch):
    """The sequential large-skyline path (skyline_large / SFS rounds) with
    the bf16 pass matches the exact path bit for bit, env-gated and via
    the explicit argument."""
    from skyline_tpu.ops.block_skyline import skyline_large

    rng = np.random.default_rng(23)
    x = jnp.asarray(_gen(rng, 6000, 8, "anti"))
    exact = np.asarray(skyline_large(x, block=1024, mp=False))
    fast = np.asarray(skyline_large(x, block=1024, mp=True))
    assert exact.tobytes() == fast.tobytes()
    monkeypatch.setenv("SKYLINE_MIXED_PRECISION", "1")
    gated = np.asarray(skyline_large(x, block=1024))
    assert gated.tobytes() == exact.tobytes()


def test_mask_scan_and_blocked_mixed_precision():
    """Direct mp on/off equality for the jnp fallbacks the global merge
    and multihost paths share."""
    from skyline_tpu.ops.block_skyline import (
        dominated_by_blocked,
        skyline_mask_scan,
    )

    rng = np.random.default_rng(31)
    x = jnp.asarray(_gen(rng, 1500, 8, "uniform"))
    a = np.asarray(skyline_mask_scan(x, chunk=512, mp=False))
    b = np.asarray(skyline_mask_scan(x, chunk=512, mp=True))
    assert (a == b).all()
    y = jnp.asarray(_gen(rng, 700, 8, "correlated"))
    xv = jnp.asarray(rng.random(1500) < 0.9)
    da = np.asarray(dominated_by_blocked(y, x, x_valid=xv, block=256))
    db = np.asarray(
        dominated_by_blocked(y, x, x_valid=xv, block=256, mp=True)
    )
    assert (da == db).all()


def test_strictly_dominated_bf16_sound(rng):
    """Certification soundness: every row the bf16 margin pass flags has a
    genuine strict dominator in exact f32; ties and duplicates are never
    certified."""
    from skyline_tpu.ops.dominance import strictly_dominated_bf16

    x = rng.random((400, 6)).astype(np.float32)
    y = rng.random((500, 6)).astype(np.float32)
    xv = rng.random(400) < 0.8
    got = np.asarray(
        strictly_dominated_bf16(jnp.asarray(y), jnp.asarray(x), jnp.asarray(xv))
    )
    strict = (
        (x[xv][:, None, :] < y[None, :, :]).all(axis=2).any(axis=0)
    )
    assert not (got & ~strict).any(), "certified a non-dominated row"
    assert got.sum() > 0  # the pass is live on easy data
    # self-vs-self: a certified row still needs a strict dominator; the
    # diagonal (each row vs itself) can never certify
    self_got = np.asarray(
        strictly_dominated_bf16(jnp.asarray(x), jnp.asarray(x))
    )
    self_strict = (
        (x[:, None, :] < x[None, :, :]).all(axis=2).any(axis=0)
    )
    assert not (self_got & ~self_strict).any()
    # a pure tie pair (shared coordinate) is never certified
    pair = np.array([[1.0, 2.0, 3.0], [1.0, 30.0, 40.0]], dtype=np.float32)
    assert not np.asarray(
        strictly_dominated_bf16(jnp.asarray(pair), jnp.asarray(pair))
    ).any()


def test_grid_summary_codes_sound(rng):
    """Stage-1 soundness: whenever every dim has rep-code < victim-code,
    the rep row strictly dominates the victim in exact f32 (the inequality
    chain x <= b[ux] < b[vy] <= y the prefilter relies on)."""
    from skyline_tpu.stream.window import (
        GRID_BINS,
        GRID_REPS,
        grid_summary_device,
    )

    d, cap, count = 5, 1024, 200
    sky = np.full((1, cap, d), np.inf, dtype=np.float32)
    rows = rng.random((count, d)).astype(np.float32)
    sky[0, :count] = rows
    counts = jnp.asarray(np.array([count], dtype=np.int32))
    bounds, ux = grid_summary_device(jnp.asarray(sky), counts, cap)
    bounds = np.asarray(bounds)[0]  # (K+1, d)
    ux = np.asarray(ux)[0]  # (R, d)
    assert np.all(np.diff(bounds, axis=0) > 0)
    r = min(cap, GRID_REPS)
    assert ux.shape == (r, d) and (ux[:count] <= GRID_BINS).all()
    assert (ux[count:] == GRID_BINS + 1).all()  # padding reps masked out
    y = rng.random((800, d)).astype(np.float32) * 1.5
    vy = (bounds[None, :, :] <= y[:, None, :]).sum(axis=1) - 1
    dominated = np.any(
        np.all(ux[None, :, :] < vy[:, None, :], axis=2), axis=1
    )
    strict = (rows[:r][None, :, :] < y[:, None, :]).all(axis=2).any(axis=1)
    assert not (dominated & ~strict).any(), "grid certified a false drop"
    assert dominated.sum() > 0  # and it certifies real ones


def test_engine_stats_and_telemetry_counters(monkeypatch):
    """The flush_cascade block rides engine.stats() and the counters reach
    the telemetry hub under their Prometheus names."""
    from skyline_tpu.telemetry import Telemetry

    _cascade_env(monkeypatch, True)
    hub = Telemetry()
    eng = SkylineEngine(
        EngineConfig(parallelism=2, dims=4, domain_max=1.0, buffer_size=128),
        telemetry=hub,
    )
    rng = np.random.default_rng(37)
    x = rng.random((2000, 4)).astype(np.float32)
    eng.process_records(np.arange(1000), x[:1000])
    eng.process_trigger("q0,0")
    eng.poll_results()
    eng.process_records(np.arange(1000, 2000), x[1000:])
    eng.process_trigger("q1,0")
    eng.poll_results()
    st = eng.stats()
    cs = st["flush_cascade"]
    for key in (
        "prefilter_enabled",
        "mixed_precision",
        "prefilter_seen",
        "prefilter_dropped",
        "prefilter_drop_fraction",
        "bf16_resolved",
    ):
        assert key in cs, cs
    assert cs["prefilter_seen"] > 0
    body = hub.render_prometheus()
    assert "skyline_flush_prefilter_dropped_total" in body
    assert "skyline_flush_bf16_resolved_total" in body
    # telemetry totals agree with the stats block (stats() synced them)
    assert hub.counters.get("flush.prefilter_dropped") == cs[
        "prefilter_dropped"
    ]
    assert hub.counters.get("flush.bf16_resolved") == cs["bf16_resolved"]


def test_restore_invalidates_grid(monkeypatch, tmp_path):
    """A restored checkpoint must invalidate the device grid summaries —
    stale cells over pre-restore state could otherwise certify drops
    against a skyline that no longer exists."""
    from skyline_tpu.utils.checkpoint import load_engine, save_engine

    _cascade_env(monkeypatch, True)
    rng = np.random.default_rng(41)
    eng = SkylineEngine(
        EngineConfig(parallelism=2, dims=4, domain_max=1.0, buffer_size=128)
    )
    x = rng.random((1500, 4)).astype(np.float32)
    eng.process_records(np.arange(1500), x)
    eng.process_trigger("q0,0")
    eng.poll_results()
    path = str(tmp_path / "ck.npz")
    save_engine(eng, path)
    eng2 = load_engine(path)
    assert eng2.pset._grid_dev is None
    assert eng2.pset._grid_host is None
    assert eng2.pset._grid_epoch is None
    # and the restored engine still answers identically with the cascade on
    eng2.process_trigger("q1,0")
    (r2,) = eng2.poll_results()
    eng.process_trigger("q1,0")
    (r1,) = eng.poll_results()
    assert r1["skyline_size"] == r2["skyline_size"]
