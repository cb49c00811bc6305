"""Sharded streaming engine (skyline_tpu/distributed): byte-identity of
the two-level tournament against the single-device engine, chip-level
witness pruning, chip WAL barriers, and chip-crash replay equivalence.

The grid is the PR's acceptance bar: for every distribution shape x
dimensionality x chip count x flush policy, the sharded engine's
published skyline must be byte-identical (rows AND order) to the
single-device engine's — including after an injected chip crash plus
WAL replay, with the audit plane at full sample reporting zero
divergence.
"""

from __future__ import annotations

import json
from dataclasses import replace

import numpy as np
import pytest

from skyline_tpu.bridge import MemoryBus, SkylineWorker
from skyline_tpu.bridge.wire import format_trigger, format_tuple_line
from skyline_tpu.distributed import ShardedEngine, ShardedPartitionSet
from skyline_tpu.parallel.chips import chip_devices, chip_of
from skyline_tpu.resilience import ResilienceConfig
from skyline_tpu.resilience.chip_wal import (
    ChipWalPlane,
    discover_chips,
    read_chip_records,
    verify_chip_barriers,
)
from skyline_tpu.resilience.faults import (
    FaultPlan,
    active_plan,
    clear,
    install_plan,
)
from skyline_tpu.resilience.supervisor import Supervisor
from skyline_tpu.resilience.wal import WalReplayError
from skyline_tpu.stream import EngineConfig, SkylineEngine
from skyline_tpu.stream.batched import PartitionSet
from skyline_tpu.telemetry import Telemetry
from skyline_tpu.workload.generators import uniform

from conftest import assert_same_merge, gen_points, merge_state

P = 4  # divisible by every chip count in the grid


@pytest.fixture(autouse=True)
def _no_leaked_fault_plan():
    clear()
    yield
    clear()


def _feed_pset(pset, x: np.ndarray, chunk: int = 97) -> None:
    """Identical ingest sequence for both engines: deterministic routing,
    chunked adds, the engine's own flush cadence after every chunk — so
    a sharded/single pair sees byte-identical flush points."""
    n = x.shape[0]
    pids = np.arange(n) % P
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        for p in range(P):
            rows = np.ascontiguousarray(x[lo:hi][pids[lo:hi] == p])
            if rows.shape[0]:
                pset.add_batch(p, rows, max_id=hi, now_ms=0.0)
        pset.maybe_flush()
    pset.flush_all()


# --------------------------------------------------------------------------
# the acceptance grid: distribution x d x chips x flush policy
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["uniform", "correlated", "anti"])
@pytest.mark.parametrize("d", [2, 4, 8])
def test_sharded_matches_single_device_grid(rng, kind, d):
    x = gen_points(rng, 450, d, kind)
    for policy in ("incremental", "lazy"):
        single = PartitionSet(P, d, buffer_size=64, flush_policy=policy)
        _feed_pset(single, x)
        base = merge_state(single)
        for chips in (1, 2, 4):
            sp = ShardedPartitionSet(
                P, d, 64, chips=chips, flush_policy=policy
            )
            _feed_pset(sp, x)
            assert_same_merge(
                base, merge_state(sp),
                ctx=f"kind={kind} d={d} chips={chips} policy={policy}",
            )


def test_sharded_incremental_merge_across_batches(rng):
    """Identity must hold at every intermediate query, not just the end
    state (flush cadence + facade merge cache both in play)."""
    d = 4
    x = gen_points(rng, 600, d, "uniform")
    single = PartitionSet(P, d, buffer_size=64)
    sp = ShardedPartitionSet(P, d, 64, chips=2)
    n = x.shape[0]
    pids = np.arange(n) % P
    for lo in range(0, n, 150):
        hi = min(lo + 150, n)
        for ps in (single, sp):
            for p in range(P):
                rows = np.ascontiguousarray(x[lo:hi][pids[lo:hi] == p])
                if rows.shape[0]:
                    ps.add_batch(p, rows, max_id=hi, now_ms=0.0)
            ps.flush_all()
        assert_same_merge(
            merge_state(single), merge_state(sp), ctx=f"after {hi} rows"
        )
    # a repeated query against unchanged state is a facade cache hit and
    # must return the same bytes
    again = merge_state(sp)
    assert_same_merge(merge_state(single), again, ctx="cache-hit query")
    assert sp.merge_cache_hits >= 1


# --------------------------------------------------------------------------
# chip-level witness pruning
# --------------------------------------------------------------------------


def test_chip_prune_fires_and_preserves_identity(rng):
    """Skewed routing: partition 0 (chip 0 when chips == P) receives a
    cluster near the origin while every other partition receives points
    in the dominated upper quadrant — chip 0's witness strictly dominates
    the other chips' min-corners, so whole chips skip the cross-chip
    merge."""
    d = 2
    x = rng.random((448, d)).astype(np.float32) * 0.4 + 0.55
    x[::P] = rng.random((112, d)).astype(np.float32) * 0.05 + 0.01
    single = PartitionSet(P, d, buffer_size=64)
    _feed_pset(single, x)
    sp = ShardedPartitionSet(P, d, 64, chips=4)
    _feed_pset(sp, x)
    assert_same_merge(merge_state(single), merge_state(sp), ctx="pruned")
    stats = sp.sharded_stats()
    assert stats["chips"] == 4
    assert stats["chips_pruned"] > 0
    assert 0.0 < stats["pruned_chip_fraction"] <= 0.75
    info = stats["last"]
    assert info is not None
    pruned_ids = {e["chip"] for e in info["pruned"]}
    assert pruned_ids
    for e in info["pruned"]:
        assert e["witness"] not in pruned_ids, "witness chain must end alive"
    assert len(info["per_chip"]) == 4
    assert len(info["survivors"]) >= 1
    assert not (set(info["survivors"]) & pruned_ids)


def test_chip_prune_knob_disables(rng, monkeypatch):
    monkeypatch.setenv("SKYLINE_CHIP_PRUNE", "0")
    d = 2
    x = rng.random((448, d)).astype(np.float32) * 0.4 + 0.55
    x[::P] = rng.random((112, d)).astype(np.float32) * 0.05 + 0.01
    single = PartitionSet(P, d, buffer_size=64)
    _feed_pset(single, x)
    sp = ShardedPartitionSet(P, d, 64, chips=4)
    _feed_pset(sp, x)
    assert_same_merge(merge_state(single), merge_state(sp), ctx="no-prune")
    assert sp.sharded_stats()["chips_pruned"] == 0


# --------------------------------------------------------------------------
# engine level: full query path, audit plane at full sample, EXPLAIN
# --------------------------------------------------------------------------


def _run_engine(engine, x, trigger=True):
    n = x.shape[0]
    ids = np.arange(n, dtype=np.int64)
    for lo in range(0, n, 128):
        hi = min(lo + 128, n)
        engine.process_records(ids[lo:hi], x[lo:hi])
    if trigger:
        engine.process_trigger("0,0")
    out = []
    for _ in range(200):
        out.extend(engine.poll_results())
        if out:
            break
    return out


def test_sharded_engine_end_to_end_with_full_audit(rng, monkeypatch):
    monkeypatch.setenv("SKYLINE_AUDIT_SAMPLE", "1.0")
    d = 4
    cfg = EngineConfig(parallelism=2, dims=d, buffer_size=64,
                       domain_max=1.0, emit_skyline_points=True)
    x = gen_points(rng, 500, d, "uniform")
    base = _run_engine(SkylineEngine(cfg, telemetry=Telemetry()), x)
    sharded_telem = Telemetry()
    eng = ShardedEngine(cfg, chips=2, telemetry=sharded_telem)
    # the audit plane shadow-verifies PUBLISHED snapshots; attach a store
    # so the sharded answer actually reaches the auditor
    from skyline_tpu.serve import SnapshotStore

    eng.attach_snapshots(SnapshotStore(history=4))
    got = _run_engine(eng, x)
    assert len(base) == len(got) == 1
    assert got[0]["skyline_size"] == base[0]["skyline_size"]
    np.testing.assert_array_equal(
        np.asarray(got[0]["skyline_points"], dtype=np.float32),
        np.asarray(base[0]["skyline_points"], dtype=np.float32),
    )
    stats = eng.stats()
    assert stats["sharded"]["chips"] == 2
    assert stats["sharded"]["merges"] >= 1
    # the audit plane runs the sharded answer against the host oracle at
    # full sample — distributed execution must not change a single byte
    assert stats["audit"]["checks_total"] >= 1
    assert stats["audit"]["divergence_total"] == 0


def test_sharded_explain_carries_chip_attribution(rng):
    from skyline_tpu.telemetry.explain import format_plan

    d = 2
    cfg = EngineConfig(parallelism=2, dims=d, buffer_size=64,
                       domain_max=1.0, emit_skyline_points=True)
    telem = Telemetry()
    eng = ShardedEngine(cfg, chips=4, telemetry=telem)
    _run_engine(eng, gen_points(rng, 450, d, "correlated"))
    doc = telem.explain.latest()
    assert doc is not None
    ch = doc.get("chips")
    assert ch is not None
    assert ch["chips"] == 4
    assert len(ch["per_chip"]) == 4
    assert len(ch["survivors"]) >= 1
    assert doc["merge"]["path"] == "sharded_tree"
    rendered = format_plan(doc)
    assert "chips n=4" in rendered
    for e in ch["pruned"]:
        assert f"chip {e['chip']} pruned by witness of chip" in rendered


# --------------------------------------------------------------------------
# checkpoint topology portability
# --------------------------------------------------------------------------


def test_checkpoint_roundtrips_across_topologies(rng, tmp_path):
    from skyline_tpu.utils.checkpoint import load_engine, save_engine

    d = 4
    cfg = EngineConfig(parallelism=2, dims=d, buffer_size=64,
                       domain_max=1.0, emit_skyline_points=True)
    x = gen_points(rng, 400, d, "uniform")
    eng = ShardedEngine(cfg, chips=2)
    _run_engine(eng, x, trigger=False)
    eng.pset.flush_all()
    base = merge_state(eng.pset)
    path = str(tmp_path / "ckpt.npz")
    save_engine(eng, path)
    # sharded checkpoint -> single-device engine
    flat = load_engine(path)
    assert isinstance(flat, SkylineEngine)
    assert not isinstance(flat, ShardedEngine)
    assert flat.config.mesh_chips == 0
    assert_same_merge(base, merge_state(flat.pset), ctx="sharded->flat")
    # sharded checkpoint -> different chip count
    wide = load_engine(path, mesh_chips=4)
    assert isinstance(wide, ShardedEngine)
    assert wide.mesh_chips == wide.config.mesh_chips == 4
    assert_same_merge(base, merge_state(wide.pset), ctx="sharded->4chips")
    assert flat.records_in == wide.records_in == eng.records_in


def test_checkpoint_roundtrips_across_cluster_topologies(rng, tmp_path):
    """ISSUE 16: a checkpoint taken under one host/chip layout restores
    into any other — including flat -> cluster and a cluster saved at
    hosts=2 restored at hosts=4 with a DIFFERENT per-host chip count —
    with a byte-identical next answer."""
    from skyline_tpu.cluster import ClusterEngine
    from skyline_tpu.utils.checkpoint import load_engine, save_engine

    d = 4
    cfg = EngineConfig(parallelism=2, dims=d, buffer_size=64,
                       domain_max=1.0, emit_skyline_points=True)
    x = gen_points(rng, 400, d, "uniform")
    eng = ClusterEngine(cfg, hosts=2, chips_per_host=2)
    _run_engine(eng, x, trigger=False)
    eng.pset.flush_all()
    base = merge_state(eng.pset)
    path = str(tmp_path / "ckpt.npz")
    save_engine(eng, path)
    # cluster checkpoint -> flat single-host engine
    flat = load_engine(path)
    assert not isinstance(flat, ClusterEngine)
    assert_same_merge(base, merge_state(flat.pset), ctx="cluster->flat")
    # cluster checkpoint -> more hosts, different per-host chip count
    wide = load_engine(path, cluster_hosts=4, mesh_chips=1)
    assert isinstance(wide, ClusterEngine)
    assert wide.cluster_hosts == 4 and wide.chips_per_host == 1
    assert_same_merge(base, merge_state(wide.pset), ctx="cluster->4hosts")
    assert flat.records_in == wide.records_in == eng.records_in
    # and the reverse direction: a FLAT checkpoint boots a cluster
    flat_path = str(tmp_path / "flat.npz")
    save_engine(flat, flat_path)
    clustered = load_engine(flat_path, cluster_hosts=2, mesh_chips=2)
    assert isinstance(clustered, ClusterEngine)
    assert_same_merge(
        base, merge_state(clustered.pset), ctx="flat->cluster"
    )


# --------------------------------------------------------------------------
# chip WAL plane
# --------------------------------------------------------------------------


def test_chip_wal_barrier_fanout_and_verify(tmp_path):
    d = str(tmp_path)
    plane = ChipWalPlane(d, chips=3, fsync="off")
    plane.note_flush(0, 10, "e0")
    plane.merge_barrier(1, "g1", ["a", "b", "c"], [5, 0, 2])
    plane.merge_barrier(2, "g2", ["a", "b", "c"], [5, 1, 2])
    plane.close()
    assert discover_chips(d) == 3
    v = verify_chip_barriers(d)
    assert v == {"chips": 3, "common_seq": 2, "epoch": "g2", "agree": True}
    recs = read_chip_records(d, 3)
    assert [r["type"] for r in recs[0]] == [
        "flush", "chip-barrier", "chip-barrier",
    ]
    assert all(r[-1]["seq"] == 2 for r in recs)


def test_chip_wal_torn_fanout_is_ignored(tmp_path):
    """A crash mid-fan-out leaves the barrier on SOME journals only; that
    seq is not common to all, so verification ignores it rather than
    reporting divergence."""
    d = str(tmp_path)
    plane = ChipWalPlane(d, chips=2, fsync="off")
    plane.merge_barrier(1, "g1", ["a", "b"], [1, 1])
    plane.close()
    # simulate a torn seq-2 fan-out: only chip 0's journal gets it
    torn = ChipWalPlane(d, chips=2, fsync="off")
    torn._writers[0].append({
        "type": "chip-barrier", "seq": 2, "chip": 0, "chips": 2,
        "epoch": "g2", "chip_epoch": "a2", "g": 1,
    })
    torn.close()
    v = verify_chip_barriers(d)
    assert v["common_seq"] == 1 and v["epoch"] == "g1" and v["agree"]


def test_chip_wal_divergence_raises(tmp_path):
    d = str(tmp_path)
    plane = ChipWalPlane(d, chips=2, fsync="off")
    plane._writers[0].append({
        "type": "chip-barrier", "seq": 1, "chip": 0, "chips": 2,
        "epoch": "gX", "chip_epoch": "a", "g": 1,
    })
    plane._writers[1].append({
        "type": "chip-barrier", "seq": 1, "chip": 1, "chips": 2,
        "epoch": "gY", "chip_epoch": "b", "g": 1,
    })
    plane.close()
    with pytest.raises(WalReplayError, match="divergence"):
        verify_chip_barriers(d)


def test_chip_wal_empty_layout_trivially_agrees(tmp_path):
    v = verify_chip_barriers(str(tmp_path))
    assert v == {"chips": 0, "common_seq": None, "epoch": None,
                 "agree": True}


# --------------------------------------------------------------------------
# chip-crash chaos: injected crash at the per-chip merge + WAL replay
# must reproduce the uninterrupted single-device answer byte-for-byte
# --------------------------------------------------------------------------


def _feed(bus, rows, start_id=0):
    bus.produce_many(
        "input-tuples",
        [format_tuple_line(start_id + i, row) for i, row in enumerate(rows)],
    )


def _sharded_worker(bus, tmp_path, d, chips, telem=None):
    res = ResilienceConfig(checkpoint_dir=str(tmp_path), wal_fsync="batch")
    return SkylineWorker(
        bus,
        EngineConfig(parallelism=2, dims=d, domain_max=10000.0,
                     buffer_size=128, emit_skyline_points=True,
                     mesh_chips=chips),
        resilience=res,
        telemetry=telem,
    )


def _drive_to_result(worker, bus, out, shared, chunk=64):
    idle = 0
    while True:
        if worker.step(max_records=chunk):
            idle = 0
            continue
        if not shared["trigger_sent"]:
            bus.produce("queries", format_trigger(0, 0))
            shared["trigger_sent"] = True
            continue
        shared["lines"].extend(out.poll())
        if shared["lines"]:
            return json.loads(shared["lines"][-1])
        idle += 1
        assert idle < 500, "worker went idle without producing a result"


def _run_sharded_stream(tmp_path, rows, d, chips, plan_spec):
    bus = MemoryBus()
    _feed(bus, rows)
    out = bus.consumer("output-skyline", from_beginning=True)
    telem = Telemetry()
    shared = {"trigger_sent": False, "lines": []}
    holder = {}
    if plan_spec:
        install_plan(FaultPlan.parse(plan_spec))

    def incarnation(attempt):
        w = _sharded_worker(bus, tmp_path, d, chips, telem=telem)
        holder["w"] = w
        return _drive_to_result(w, bus, out, shared)

    sup = Supervisor(incarnation, max_restarts=8, backoff_base_s=0.0,
                     backoff_cap_s=0.0, telemetry=telem, sleep=lambda s: None)
    stats_doc = None
    try:
        doc = sup.run()
        stats_doc = holder["w"].stats()  # before close() drops the planes
    finally:
        clear()
        if holder.get("w") is not None:
            holder["w"].close()
    return doc, holder["w"], sup, stats_doc


@pytest.mark.parametrize("chips,plan", [
    (2, "crash@sharded.chip_merge:1"),
    (4, "crash@sharded.chip_merge:3,crash@kafka.poll:7"),
])
def test_chaos_chip_crash_replay_equals_single_device(rng, tmp_path, chips,
                                                      plan):
    n = 400
    d = 4
    rows = uniform(rng, n, d, 0, 10000)
    # the reference answer comes from an UNSHARDED uninterrupted worker:
    # equality across both the crash schedule and the topology
    base_bus = MemoryBus()
    _feed(base_bus, rows)
    base_out = base_bus.consumer("output-skyline", from_beginning=True)
    base_w = SkylineWorker(
        base_bus,
        EngineConfig(parallelism=2, dims=d, domain_max=10000.0,
                     buffer_size=128, emit_skyline_points=True),
    )
    base_doc = _drive_to_result(
        base_w, base_bus, base_out, {"trigger_sent": False, "lines": []}
    )
    base_w.close()

    doc, w, sup, stats = _run_sharded_stream(tmp_path, rows, d, chips, plan)
    assert sup.restarts >= 1, "the fault plan never fired"
    assert active_plan() is None
    assert w.engine.records_in == n
    assert doc["skyline_size"] == base_doc["skyline_size"]
    np.testing.assert_array_equal(
        np.asarray(doc["skyline_points"], dtype=np.float32),
        np.asarray(base_doc["skyline_points"], dtype=np.float32),
    )
    # the survivor's chip journals hold a consistent barrier history
    cw = stats["resilience"].get("chip_wal")
    assert cw is not None and cw["chips"] == chips
    assert cw["barriers_written"] >= 1
    v = verify_chip_barriers(w._wal_dir, chips)
    assert v["agree"] and v["common_seq"] is not None
    rec = w._recovered
    assert rec is not None and rec["wal_records"] > 0


# --------------------------------------------------------------------------
# construction + config validation
# --------------------------------------------------------------------------


def test_chip_devices_round_robin_and_ownership():
    devs = chip_devices(4)
    assert len(devs) == 4
    assert chip_of(0, 2) == 0 and chip_of(1, 2) == 0
    assert chip_of(2, 2) == 1 and chip_of(3, 2) == 1
    with pytest.raises(ValueError):
        chip_devices(0)


def test_sharded_pset_validates_divisibility():
    with pytest.raises(ValueError):
        ShardedPartitionSet(4, 2, 64, chips=3)
    with pytest.raises(ValueError):
        ShardedPartitionSet(4, 2, 64, chips=0)


def test_sharded_engine_rejects_device_ingest():
    with pytest.raises(ValueError, match="ingest"):
        ShardedEngine(
            EngineConfig(parallelism=2, dims=2, ingest="device"), chips=2
        )


def test_job_config_validates_mesh_chips():
    from skyline_tpu.utils.config import JobConfig

    assert JobConfig(parallelism=2, mesh_chips=2).mesh_chips == 2
    with pytest.raises(ValueError, match="mutually exclusive"):
        JobConfig(parallelism=2, mesh=2, mesh_chips=2)
    with pytest.raises(ValueError, match="divisible"):
        JobConfig(parallelism=2, mesh_chips=3)
    with pytest.raises(ValueError, match="mesh-chips|mesh_chips"):
        JobConfig(parallelism=2, mesh_chips=2, window_size=64, slide=32)
    with pytest.raises(ValueError):
        JobConfig(parallelism=2, mesh_chips=-1)


def test_worker_rejects_mesh_chips_with_window():
    with pytest.raises(ValueError):
        SkylineWorker(
            MemoryBus(),
            EngineConfig(parallelism=2, dims=2, mesh_chips=2),
            window_size=64,
            slide=32,
        )


# --------------------------------------------------------------------------
# the chip count as an engine config field
# --------------------------------------------------------------------------


def _answer_of(config, lines):
    """A served worker's published answer to one immediate trigger."""
    bus = MemoryBus()
    worker = SkylineWorker(bus, config, serve_port=0)
    try:
        bus.produce_many("input-tuples", lines)
        bus.produce("queries", format_trigger(1, 0))
        while worker.step() > 0:
            pass
        out = [json.loads(m) for m in bus._topics["output-skyline"]]
        assert [r["query_id"] for r in out] == ["1"]
        pts = np.asarray(worker.engine.snapshots.latest().points)
        return type(worker.engine), worker.engine.pset, out[0], pts
    finally:
        worker.close()


def test_worker_runs_the_sharded_engine_from_the_config_field(monkeypatch):
    """``EngineConfig(mesh_chips=4)`` at the four-chip cell's engine
    settings: the worker builds the sharded engine on four devices and
    publishes the flat engine's answer byte for byte, which is the plain
    reference's skyline."""
    from skybench import gen, reference

    monkeypatch.setenv("SKYLINE_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("SKYLINE_SORTED_SFS", "off")
    rows = gen.generate(
        {"distribution": "anti_correlated", "dims": 8, "d_min": 0, "d_max": 10000},
        gen.rng_for(2900000029, 0), 4000,
    )
    lines = gen.wire_lines(np.arange(rows.shape[0]), rows)
    cell = dict(parallelism=4, algo="mr-angle", dims=8, domain_max=10000.0,
                buffer_size=8192, initial_capacity=65536, flush_policy="lazy",
                window_capacity=4000)
    kind, pset, res, got = _answer_of(EngineConfig(**cell, mesh_chips=4), lines)
    assert kind is ShardedEngine
    assert pset.chips == 4 and len({d.id for d in pset._devices}) == 4
    flat_kind, _, flat_res, want = _answer_of(EngineConfig(**cell), lines)
    assert flat_kind is SkylineEngine
    assert res["skyline_size"] == flat_res["skyline_size"] == got.shape[0]
    assert got.tobytes() == want.tobytes()
    assert reference.multiset_gap(got, reference.skyline(rows)) == 0


def test_job_cli_mesh_chips_reaches_the_engine_config():
    from skyline_tpu.utils.config import parse_job_args

    cfg = parse_job_args(["--parallelism", "4", "--mesh-chips", "4"])
    assert cfg.engine_config().mesh_chips == 4
    assert parse_job_args(["--parallelism", "4"]).engine_config().mesh_chips == 0
    clustered = parse_job_args(
        ["--parallelism", "4", "--cluster-hosts", "2", "--mesh-chips", "2"]
    )
    # under --cluster-hosts the field is the chip count per host
    assert clustered.engine_config().mesh_chips == 2


@pytest.mark.parametrize("saved, running", [(2, 4), (2, 0), (0, 2)])
def test_worker_restore_takes_its_own_chip_count(rng, tmp_path, saved, running):
    """A checkpoint is placement-free: the restoring worker's
    ``config.mesh_chips`` decides the engine, whatever the saving worker
    ran."""
    d = 4
    rows = uniform(rng, 300, d, 0, 10000)
    base = EngineConfig(parallelism=2, dims=d, domain_max=10000.0,
                        buffer_size=128, emit_skyline_points=True)
    res = ResilienceConfig(checkpoint_dir=str(tmp_path), wal_fsync="batch")
    bus = MemoryBus()
    _feed(bus, rows)
    w = SkylineWorker(bus, replace(base, mesh_chips=saved), resilience=res)
    try:
        while w.step(max_records=64):
            pass
        assert w.checkpoint_now() is not None
        want = w.engine.pset.global_merge_stats(emit_points=True)[3]
    finally:
        w.close()
    w2 = SkylineWorker(MemoryBus(), replace(base, mesh_chips=running),
                       resilience=res)
    try:
        assert w2._recovered is not None
        assert w2.engine.config.mesh_chips == running
        assert isinstance(w2.engine, ShardedEngine) == bool(running)
        if running:
            assert w2.engine.pset.chips == running
        got = w2.engine.pset.global_merge_stats(emit_points=True)[3]
        assert got.tobytes() == want.tobytes()
    finally:
        w2.close()


def test_two_level_tournament_lands_on_the_capture_clock(rng, tmp_path):
    """Under a profiler capture the tournament's phases count to the
    ``cross_chip`` layer, the per-chip flush and the prune are counted,
    and ``/trace`` keeps its ``chip_merge`` / ``cross_chip_merge`` spans."""
    import jax

    from skyline_tpu.metrics import tracing
    from skyline_tpu.metrics.tracing import Tracer

    d = 4
    cfg = EngineConfig(parallelism=4, dims=d, buffer_size=64,
                       domain_max=1.0, emit_skyline_points=True)
    x = gen_points(rng, 800, d, "anti")
    hub = Telemetry()
    eng = ShardedEngine(cfg, chips=4, tracer=Tracer(), telemetry=hub)
    tracing.CAPTURED.reset()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            (res,) = _run_engine(eng, x)
        counts = tracing.CAPTURED.counts()
        self_ms = tracing.CAPTURED.self_ms()
    finally:
        tracing.CAPTURED.reset()
    assert self_ms["cross_chip"] > 0
    assert counts["flush/chip_rows"] == x.shape[0]
    assert x.shape[0] / 4 <= counts["flush/chip_rows_max"] <= x.shape[0]
    info = eng.pset.last_chip_info
    assert counts["cross_chip/chips_considered"] == info["alive"]
    assert counts["cross_chip/chips_pruned"] == len(info["pruned"])
    assert counts["cross_chip/rows_shipped"] >= 0
    report = eng.tracer.report()
    assert report["cross_chip/level1"]["count"] == 4
    assert report["cross_chip/level2"]["count"] == 1
    spans = hub.spans.snapshot()
    level1 = sorted((s for s in spans if s["name"] == "chip_merge"), key=lambda s: s["tid"])
    assert [s["tid"] for s in level1] == [1, 2, 3, 4]
    assert [s["args"]["chip"] for s in level1] == [0, 1, 2, 3]
    assert all(set(s["args"]) == {"chip", "level", "skyline"} for s in level1)
    (level2,) = [s for s in spans if s["name"] == "cross_chip_merge"]
    assert level2["tid"] == 0
    assert set(level2["args"]) == {"level", "survivors", "pruned", "levels"}
    assert level2["args"]["survivors"] == len(info["survivors"])
    assert res["skyline_size"] > 0
