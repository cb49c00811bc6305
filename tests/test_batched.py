"""PartitionSet (stacked, single-launch flush) semantics.

The batched path must be result-identical to per-partition incremental
merging: the merge law (SURVEY.md §4) makes the incremental skyline
batching-invariant, so these tests pin the exact-set equality against the
numpy oracle under uneven routing, heavy skew (multi-round flushes), and
cache invalidation across flush/snapshot interleavings.
"""

import numpy as np

from skyline_tpu.ops.dominance import skyline_np
from skyline_tpu.stream.batched import PartitionSet
from conftest import assert_same_set


def test_uneven_partitions_match_oracle(rng):
    ps = PartitionSet(num_partitions=4, dims=3, buffer_size=64)
    data = [rng.uniform(0, 100, size=(n, 3)).astype(np.float32)
            for n in (5, 700, 33, 0)]
    for p, x in enumerate(data):
        if x.shape[0]:
            ps.add_batch(p, x, max_id=p, now_ms=0.0)
    ps.maybe_flush()
    for p, x in enumerate(data):
        assert_same_set(ps.snapshot(p), skyline_np(x) if x.shape[0] else
                        np.empty((0, 3)))


def test_heavy_skew_multi_round_flush(rng):
    """One partition holding many times buffer_size pending rows exercises
    the multi-round loop inside flush_all."""
    ps = PartitionSet(num_partitions=2, dims=2, buffer_size=1024)
    x = rng.uniform(0, 1000, size=(5000, 2)).astype(np.float32)
    ps.add_batch(0, x, max_id=0, now_ms=0.0)
    ps.add_batch(1, x[:10], max_id=1, now_ms=0.0)
    ps.flush_all()
    assert_same_set(ps.snapshot(0), skyline_np(x))
    assert_same_set(ps.snapshot(1), skyline_np(x[:10]))


def test_snapshot_caches_invalidate_on_new_data(rng):
    ps = PartitionSet(num_partitions=2, dims=2, buffer_size=16)
    a = rng.uniform(0, 100, size=(50, 2)).astype(np.float32)
    ps.add_batch(0, a, max_id=0, now_ms=0.0)
    s1 = ps.snapshot(0)
    assert_same_set(s1, skyline_np(a))
    # a strictly better point must show up in the next snapshot
    better = np.zeros((1, 2), dtype=np.float32)
    ps.add_batch(0, better, max_id=1, now_ms=0.0)
    s2 = ps.snapshot(0)
    assert_same_set(s2, np.zeros((1, 2)))
    # snapshot copies: mutating the returned array must not corrupt state
    s2[:] = 123.0
    assert_same_set(ps.snapshot(0), np.zeros((1, 2)))


def test_incremental_equals_one_shot(rng):
    """Stream in many small chunks == one big batch (batching invariance)."""
    x = rng.uniform(0, 1000, size=(3000, 4)).astype(np.float32)
    ps_stream = PartitionSet(num_partitions=1, dims=4, buffer_size=128)
    for i in range(0, 3000, 77):
        ps_stream.add_batch(0, x[i : i + 77], max_id=i, now_ms=0.0)
        ps_stream.maybe_flush()
    ps_one = PartitionSet(num_partitions=1, dims=4, buffer_size=4096)
    ps_one.add_batch(0, x, max_id=0, now_ms=0.0)
    assert_same_set(ps_stream.snapshot(0), ps_one.snapshot(0))
    assert_same_set(ps_stream.snapshot(0), skyline_np(x))


def test_counts_and_bookkeeping(rng):
    ps = PartitionSet(num_partitions=3, dims=2, buffer_size=32)
    x = rng.uniform(0, 100, size=(100, 2)).astype(np.float32)
    ps.add_batch(1, x, max_id=41, now_ms=7.5)
    assert ps.max_seen_id.tolist() == [-1, 41, -1]
    assert ps.start_time_ms == [None, 7.5, None]
    assert int(ps.records_seen[1]) == 100
    ps.flush_all()
    counts = ps.sky_counts()
    assert counts[0] == 0 and counts[2] == 0
    assert counts[1] == skyline_np(x).shape[0]


def test_initial_capacity_presizing(rng):
    """Pre-sized buffers skip growth and still produce exact skylines."""
    x = rng.uniform(0, 1000, size=(2000, 3)).astype(np.float32)
    ps = PartitionSet(num_partitions=2, dims=3, buffer_size=256,
                      initial_capacity=4096)
    assert ps._cap == 4096
    ps.add_batch(0, x, max_id=0, now_ms=0.0)
    ps.flush_all()
    assert ps._cap == 4096  # no growth happened
    assert_same_set(ps.snapshot(0), skyline_np(x))


def test_meshed_partition_set_matches_oracle(rng):
    """Meshed flushes go through shard_map(vmap(merge)) — result-identical
    to the unmeshed path and to the oracle."""
    from skyline_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4)
    ps = PartitionSet(num_partitions=8, dims=3, buffer_size=128, mesh=mesh)
    data = [rng.uniform(0, 100, size=(n, 3)).astype(np.float32)
            for n in (5, 700, 33, 0, 257, 64, 1, 900)]
    for p, x in enumerate(data):
        if x.shape[0]:
            ps.add_batch(p, x, max_id=p, now_ms=0.0)
    ps.flush_all()
    for p, x in enumerate(data):
        assert_same_set(ps.snapshot(p), skyline_np(x) if x.shape[0] else
                        np.empty((0, 3)))


def test_meshed_merge_pallas_interpret(rng, monkeypatch):
    """The TPU flush combination — shard_map over vmap over pallas_call —
    lowers and partitions correctly (interpret mode stands in for Mosaic on
    CPU; the hardware path is checked by dryrun_multichip/kernel bench)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from skyline_tpu.ops.dominance import skyline_np as oracle
    from skyline_tpu.parallel.mesh import make_mesh
    from skyline_tpu.stream.window import _MIN_CAP, meshed_merge_step

    monkeypatch.setenv("SKYLINE_PALLAS_INTERPRET", "1")
    mesh = make_mesh(4)
    p_parts, cap, d = 4, _MIN_CAP, 3
    sh = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    sky = jax.device_put(
        np.full((p_parts, cap, d), np.inf, dtype=np.float32), sh)
    sky_valid = jax.device_put(np.zeros((p_parts, cap), dtype=bool), sh)
    batch = np.full((p_parts, cap, d), np.inf, dtype=np.float32)
    bvalid = np.zeros((p_parts, cap), dtype=bool)
    parts = [rng.uniform(0, 100, size=(50, d)).astype(np.float32)
             for _ in range(p_parts)]
    for p, x in enumerate(parts):
        batch[p, :50] = x
        bvalid[p, :50] = True
    merge = meshed_merge_step(mesh, mesh.axis_names[0], True, cap)
    out_sky, out_valid, out_count, _ = merge(
        sky, sky_valid, jax.device_put(batch, sh), jax.device_put(bvalid, sh))
    out_sky = np.asarray(out_sky)
    counts = np.asarray(out_count)
    for p, x in enumerate(parts):
        assert_same_set(out_sky[p, :counts[p]], oracle(x))


def test_lazy_flush_path_choice(rng, monkeypatch):
    """The lazy flush picks per-partition sequential SFS under routing skew
    (P * max_rows > 2 * total_rows) and the one-launch-per-round vmapped SFS
    for balanced loads — and both produce the oracle skyline either way.
    (Device-path heuristic only: the sorted host cascade is pinned off so
    the chooser can't route around both variants — its own engagement is
    covered by tests/test_sorted_sfs.py.)"""
    monkeypatch.setenv("SKYLINE_SORTED_SFS", "off")
    calls = []
    orig_seq = PartitionSet._sfs_sequential
    orig_vm = PartitionSet._sfs_vmapped
    monkeypatch.setattr(
        PartitionSet, "_sfs_sequential",
        lambda self, rows: calls.append("seq") or orig_seq(self, rows))
    monkeypatch.setattr(
        PartitionSet, "_sfs_vmapped",
        lambda self, rows, m: calls.append("vmap") or orig_vm(self, rows, m))

    # skewed: one of 4 partitions holds ~all rows
    ps = PartitionSet(num_partitions=4, dims=3, flush_policy="lazy")
    heavy = rng.uniform(0, 100, size=(4000, 3)).astype(np.float32)
    light = rng.uniform(0, 100, size=(5, 3)).astype(np.float32)
    ps.add_batch(0, heavy, max_id=0, now_ms=0.0)
    ps.add_batch(1, light, max_id=1, now_ms=0.0)
    ps.flush_all()
    assert calls == ["seq"]
    assert_same_set(ps.snapshot(0), skyline_np(heavy))
    assert_same_set(ps.snapshot(1), skyline_np(light))

    # balanced: every partition carries the same load
    calls.clear()
    ps2 = PartitionSet(num_partitions=4, dims=3, flush_policy="lazy")
    parts = [rng.uniform(0, 100, size=(1000, 3)).astype(np.float32)
             for _ in range(4)]
    for p, x in enumerate(parts):
        ps2.add_batch(p, x, max_id=p, now_ms=0.0)
    ps2.flush_all()
    assert calls == ["vmap"]
    for p, x in enumerate(parts):
        assert_same_set(ps2.snapshot(p), skyline_np(x))


def test_sfs_round_single_matches_vmapped(rng):
    """sfs_round_single (skew path) is lane-for-lane identical to the
    vmapped sfs_round on the same sum-sorted blocks."""
    import jax.numpy as jnp

    from skyline_tpu.stream.window import _MIN_CAP, sfs_round, sfs_round_single

    P, B, d, cap = 3, 256, 4, _MIN_CAP
    sky0 = np.full((P, cap, d), np.inf, dtype=np.float32)
    counts0 = np.zeros((P,), dtype=np.int32)
    parts = [rng.uniform(0, 100, size=(2 * B, d)).astype(np.float32)
             for _ in range(P)]
    parts = [x[np.argsort(x.sum(axis=1), kind="stable")] for x in parts]

    sky_v = jnp.asarray(sky0)
    cnt_v = jnp.asarray(counts0)
    singles = [(jnp.asarray(sky0[p]), jnp.asarray(counts0[p]))
               for p in range(P)]
    for rnd in range(2):
        batch = np.stack([x[rnd * B:(rnd + 1) * B] for x in parts])
        bvalid = np.ones((P, B), dtype=bool)
        sky_v, cnt_v = sfs_round(
            sky_v, cnt_v, jnp.asarray(batch), jnp.asarray(bvalid), cap)[:2]
        singles = [
            sfs_round_single(s, c, jnp.asarray(batch[p]),
                             jnp.asarray(bvalid[p]), cap)[:2]
            for p, (s, c) in enumerate(singles)]
    cnt_v = np.asarray(cnt_v)
    for p, (s, c) in enumerate(singles):
        assert int(c) == int(cnt_v[p])
        assert_same_set(np.asarray(s)[:int(c)],
                        np.asarray(sky_v)[p, :int(c)])
        # SFS invariant: the appended prefix IS the partition's skyline
        assert_same_set(np.asarray(s)[:int(c)], skyline_np(parts[p]))


def test_global_merge_stats_matches_host_oracle(rng):
    """Device-side union merge (one small stats transfer) returns the same
    per-partition counts, survivor counts, global size, and points as
    merging the pulled snapshots on host — including under skew."""
    ps = PartitionSet(num_partitions=4, dims=3, flush_policy="lazy")
    sizes = (3000, 40, 0, 800)
    parts = [rng.uniform(0, 100, size=(n, 3)).astype(np.float32)
             for n in sizes]
    for p, x in enumerate(parts):
        if x.shape[0]:
            ps.add_batch(p, x, max_id=p, now_ms=0.0)
    ps.flush_all()
    counts, surv, g, pts = ps.global_merge_stats(emit_points=True)

    locals_ = [skyline_np(x) if x.shape[0] else np.empty((0, 3))
               for x in parts]
    union = np.concatenate(locals_, axis=0)
    glob = skyline_np(union)
    assert list(counts) == [s.shape[0] for s in locals_]
    assert g == glob.shape[0]
    assert_same_set(pts, glob)
    # survivors per partition sum to the global count
    assert int(surv.sum()) == g
    for p, loc in enumerate(locals_):
        keep = np.array([any(np.array_equal(r, gr) for gr in glob)
                         for r in loc]) if loc.shape[0] else np.empty(0)
        assert surv[p] == int(keep.sum()) if loc.shape[0] else surv[p] == 0


def test_active_bucket_ladder_invariants():
    """The quarter-pow2 active ladder: always covers n, never exceeds the
    pow2 bucket, stays pow2 while the pow2 bucket is below 16384 (Pallas
    column-tile divisibility), and is a 2048-multiple otherwise."""
    from skyline_tpu.stream.window import _active_bucket, _next_pow2

    for n in [1, 2, 100, 1024, 4097, 16384, 16385, 20480, 20481,
              57000, 100000, 437252, 500001, 1 << 20]:
        b = _active_bucket(n)
        p = _next_pow2(n)
        assert b >= n
        assert b <= p
        if p < 16384:
            assert b == p
        else:
            assert b % 2048 == 0
    # the ladder actually tightens: a survivor count just over a pow2
    # boundary lands on the next quarter step, not the next octave
    assert _active_bucket(262145) == 327680  # 1.25 * 2^18, not 2^19


def test_sequential_sfs_capacity_tracks_survivors_not_rows(rng):
    """The skew-path SFS must size its buffers by actual survivor counts,
    not worst-case streamed rows: a 400k-row skewed stream whose skyline is
    tiny stays in a small capacity bucket (the worst-case pre-grow put a
    10M-row QoS stream into a 16M-row bucket, whose executables crashed
    the remote-compile helper)."""
    ps = PartitionSet(num_partitions=4, dims=3, buffer_size=8192,
                      flush_policy="lazy")
    n = 400_000
    # heavy skew: ~97% of rows to partition 0; uniform data -> tiny skyline
    x = rng.uniform(100, 10000, size=(n, 3)).astype(np.float32)
    ps.add_batch(0, x[: int(n * 0.97)], max_id=0, now_ms=0.0)
    for p in (1, 2, 3):
        ps.add_batch(p, x[int(n * 0.97) + (p - 1) * 4000:
                          int(n * 0.97) + p * 4000], max_id=p, now_ms=0.0)
    ps.flush_all()
    counts = ps.sky_counts()
    assert int(counts.sum()) < 4096  # uniform data: small local skylines
    # capacity stayed near the survivor scale, nowhere near pow2(rows)
    assert ps._cap <= 65536 * 2, ps._cap
    # and the result is still exact
    local0 = np.asarray(ps.sky[0])[: int(counts[0])]
    assert_same_set(local0, skyline_np(x[: int(n * 0.97)]))
