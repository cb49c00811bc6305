"""Headline benchmark: skyline tuples/sec on 8-D anti-correlated 1M-tuple windows.

The BASELINE.json north-star config: anti-correlated synthetic stream,
d=8, 1M-tuple windows, single TPU chip, scored as end-to-end window
throughput (tuples/s) and p50 per-window latency through the full streaming
engine (routing -> per-partition incremental local skylines -> barrier ->
global merge -> result JSON).

Baseline anchor (BASELINE.md): the reference Flink job never completed a d=8
run; its closest measured point is 4-D/1M at ~692 s per window (~1.4k
tuples/s end-to-end, graph_paper_figures.py:28-32) — d=8 would be strictly
slower for it (skyline fraction grows with d), so vs_baseline computed
against 1,400 tuples/s is conservative.

Runs in one process on the accelerator JAX finds; it exits non-zero when
JAX finds none, and on any failure.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tuples/s", "vs_baseline": N, ...}

Env knobs: BENCH_N (window size, default 1_000_000), BENCH_D (default 8),
BENCH_ALGO (partitioner, default mr-angle), BENCH_WINDOWS (measured windows,
default 5), BENCH_PARALLELISM (default 4),
BENCH_BUFFER (flush threshold, default 8192), BENCH_INITIAL_CAP (skyline
buffer pre-size per partition, default 65536 — lower it on small devices).
The compile cache is JAX_COMPILATION_CACHE_DIR, else ./.jax_cache.

Defaults are measured-best (round-3 A/Bs on hardware, p50 at the north-star
window): BENCH_ALGO mr-dim ties mr-angle (6.97 s vs
7.03 s); mr-angle kept for parity with the reference's documented best for
anti-correlated data. BENCH_BUFFER 8192 (131072: 7.9 s — block self-prune
work grows faster than round count shrinks). BENCH_INITIAL_CAP 65536
(524288: 8.5 s — bigger buffers + fresh executable shapes). flush_policy
lazy (incremental at buffer 262144: ~3x the dominance work; measured in
benchmarks/e2e_transport.py's docstring).
"""

from __future__ import annotations

import json
import os
import time

from skyline_tpu.analysis.registry import env_bool, env_float, env_int, env_str

import numpy as np


REFERENCE_TUPLES_PER_SEC = 1400.0  # 4-D/1M anchor, see module docstring


def rank_cascade_stamp() -> bool:
    """Artifact provenance for the rank-cascade dispatch decision — read
    from the single source of truth (``ops.dispatch.rank_cascade``) instead
    of re-reading SKYLINE_RANK_CASCADE with a duplicated default that can
    silently drift from the dispatcher's (ADVICE.md round 5)."""
    from skyline_tpu.ops.dispatch import rank_cascade

    return rank_cascade()  # lint: allow-raw-gate


def analysis_stamp() -> dict:
    """Provenance of the static-analysis gate for the bench artifact: the
    knob-registry size, per-rule finding counts over the product tree, and
    the jaxpr audit matrix this run's dispatch variants were checked
    against (RUNBOOK 2h). A non-empty ``rule_counts`` means the gate would
    fail CI — perf numbers from such a tree carry an asterisk."""
    from skyline_tpu.analysis.__main__ import default_roots, repo_root, run_passes
    from skyline_tpu.analysis.registry import KNOBS
    from skyline_tpu.utils.compile_cache import compile_cache_stats

    base = repo_root()
    findings, summary = run_passes(("knobs", "locks", "jaxpr"), base)
    rule_counts: dict[str, int] = {}
    for f in findings:
        rule_counts[f.rule] = rule_counts.get(f.rule, 0) + 1
    jaxpr = summary.get("jaxpr", {})
    return {
        "registry_size": len(KNOBS),
        # persistent-cache effectiveness this process: nonzero misses on a
        # warm BENCH_COMPILE_CACHE dir is a retrace/cache-key regression
        "compile_cache": compile_cache_stats(),
        "lint_roots": [os.path.relpath(r, base) for r in default_roots(base)],
        "rule_counts": rule_counts,  # empty == gate clean
        "findings_total": len(findings),
        "jaxpr_configs_traced": jaxpr.get("configs_traced", 0),
        "jaxpr_dims": jaxpr.get("dims", []),
        "jaxpr_backend": jaxpr.get("backend"),
    }


def resilience_stamp() -> dict:
    """Crash-safety provenance for the bench artifact: the fault hook's
    disabled cost (it sits on the flush/poll hot paths — must stay a
    global-load + None check), raw WAL append throughput with fsync off,
    and the effective durability knobs. See benchmarks/resilience.py for
    the full A/B."""
    import shutil
    import tempfile

    from skyline_tpu.resilience.faults import active_plan, fault_point
    from skyline_tpu.resilience.wal import WalWriter

    assert active_plan() is None  # measure the disabled path
    calls = 200_000
    t0 = time.perf_counter()
    for _ in range(calls):
        fault_point("kafka.poll")
    hook_ns = (time.perf_counter() - t0) / calls * 1e9
    tmp = tempfile.mkdtemp(prefix="skyline-bench-wal-")
    try:
        w = WalWriter(tmp, fsync="off")
        rec = {"type": "commit", "data_off": 123456, "query_off": 7}
        appends = 2000
        t0 = time.perf_counter()
        for _ in range(appends):
            w.append(rec)
        append_us = (time.perf_counter() - t0) / appends * 1e6
        w.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "fault_hook_disabled_ns": round(hook_ns, 1),
        "wal_append_us_fsync_off": round(append_us, 2),
        "wal_fsync_policy": env_str("SKYLINE_WAL_FSYNC", "batch"),
        "checkpoint_interval_s": env_float("SKYLINE_CHECKPOINT_INTERVAL_S", 30.0),
        "supervisor_max_restarts": env_int("SKYLINE_SUPERVISOR_MAX_RESTARTS", 5),
    }


def failover_stamp() -> dict:
    """Chip fault-tolerance truth for the bench artifact (RUNBOOK §2p):
    a miniature drill — chip-scoped crash under a merge deadline ->
    honest degraded answer -> quarantine -> online failover -> post-heal
    merge byte-identical to a single-device run. Stamps the drill
    outcome plus the effective §2p knobs; a healthy bench run must show
    zero degraded answers (scripts/bench_compare.py gates on it). The
    full latency A/B lives in benchmarks/failover.py
    (artifacts/failover_ab.json)."""
    import jax

    if jax.device_count() < 2:
        return {"skipped": True, "reason": "single device"}
    from skyline_tpu.distributed import ShardedPartitionSet
    from skyline_tpu.resilience.faults import FaultPlan, clear, install_plan
    from skyline_tpu.resilience.health import ChipHealth
    from skyline_tpu.stream.batched import PartitionSet

    d, P, n = 4, 4, 2000
    rng = np.random.default_rng(11)
    x = (rng.random((n, d)) * 10000.0).astype(np.float32)
    pids = np.arange(n) % P
    single = PartitionSet(P, d, buffer_size=4096)
    sp = ShardedPartitionSet(P, d, 4096, chips=2)
    health = ChipHealth(2)
    sp.attach_health(health)
    for ps in (single, sp):
        for p in range(P):
            ps.add_batch(p, np.ascontiguousarray(x[pids == p]),
                         max_id=n, now_ms=0.0)
        ps.flush_all()
    truth = np.asarray(single.global_merge_stats(emit_points=True)[3])
    warm = np.asarray(sp.global_merge_stats(emit_points=True)[3])
    assert warm.tobytes() == truth.tobytes()
    try:
        os.environ["SKYLINE_CHIP_MERGE_DEADLINE_MS"] = "500"
        os.environ["SKYLINE_CHIP_MERGE_RETRIES"] = "0"
        install_plan(FaultPlan.parse("crash@sharded.chip_merge#1:1"))
        sp._gm_cache = None  # same epoch: force the level-1 rerun
        t0 = time.perf_counter()
        sp.global_merge_stats(emit_points=True)
        degraded_wall_ms = (time.perf_counter() - t0) * 1000.0
        partial = sp.last_partial
        assert partial is not None and partial["excluded_chips"] == [1]
        assert health.quarantined() == [1]
    finally:
        clear()
        os.environ.pop("SKYLINE_CHIP_MERGE_DEADLINE_MS", None)
        os.environ.pop("SKYLINE_CHIP_MERGE_RETRIES", None)
    healed = sp.maybe_failover()
    assert healed == [1] and health.quarantined() == []
    post = np.asarray(sp.global_merge_stats(emit_points=True)[3])
    assert post.tobytes() == truth.tobytes()
    return {
        "drill": {
            "fault": "crash@sharded.chip_merge#1:1",
            "excluded_chips": partial["excluded_chips"],
            "completeness_bound": partial["completeness_bound"],
            "degraded_answer_wall_ms": round(degraded_wall_ms, 1),
            "time_to_healed_ms": round(
                float(sp.last_failover["wall_ms"]), 2
            ),
            "failover_owner": int(sp.last_failover["owner"]),
            "healed_byte_identical": True,
        },
        "healthy_degraded_answers": 0,
        "merge_deadline_ms": env_float("SKYLINE_CHIP_MERGE_DEADLINE_MS", 0.0),
        "merge_retries": env_int("SKYLINE_CHIP_MERGE_RETRIES", 1),
        "hedge_ms": env_float("SKYLINE_CHIP_HEDGE_MS", 0.0),
        "fail_threshold": env_int("SKYLINE_CHIP_FAIL_THRESHOLD", 1),
        "quarantine_score": env_float("SKYLINE_CHIP_QUARANTINE_SCORE", 0.5),
        "failover_enabled": env_bool("SKYLINE_CHIP_FAILOVER", True),
    }


# --------------------------------------------------------------------------
# worker: the measured benchmark (runs in a child process)
# --------------------------------------------------------------------------


def run_window(cfg, ids, x, required, tracer=None):
    from skyline_tpu.stream import SkylineEngine

    eng = SkylineEngine(cfg, tracer=tracer)
    n = x.shape[0]
    t0 = time.perf_counter()
    chunk = 65536
    for i in range(0, n, chunk):
        eng.process_records(ids[i : i + chunk], x[i : i + chunk])
    eng.process_trigger(f"0,{required}")
    (result,) = eng.poll_results()
    dt = time.perf_counter() - t0
    return dt, result


def merge_cache_leg(cfg, ids, x, required) -> tuple[dict, dict, dict]:
    """Merge-cache + merge-tree truth for the bench artifact: ONE
    persistent engine, trigger twice over an unchanged window (cold miss +
    exact hit), then a small top-up and a third trigger (dirty-subset delta
    merge). Stamps hit/miss/delta counters, the last dirty fraction, and
    the tournament-tree shape (levels / partitions pruned / candidates per
    level) as ``phase_breakdown_ms`` siblings so
    ``scripts/bench_compare.py`` can gate on the cache AND the pruned tree
    staying live; the full/delta/hit latency A/B lives in
    ``benchmarks/merge_cache.py``."""
    from skyline_tpu.stream import SkylineEngine

    eng = SkylineEngine(cfg)
    n = x.shape[0]
    chunk = 65536
    for i in range(0, n, chunk):
        eng.process_records(ids[i : i + chunk], x[i : i + chunk])
    for _ in range(2):  # cold miss, then exact epoch-key hit
        eng.process_trigger(f"0,{required}")
        eng.poll_results()
    # one repeated point routes to exactly ONE partition, so the third
    # trigger exercises the dirty-subset delta path, not another full merge
    m = max(1, n // 64)
    eng.process_records(ids[:m], np.repeat(x[:1], m, axis=0))
    eng.process_trigger(f"0,{required}")
    eng.poll_results()
    st = eng.stats()
    mc = st["merge_cache"]
    total = mc["hits"] + mc["misses"]
    mc["hit_rate"] = round(mc["hits"] / total, 3) if total else 0.0
    return mc, st.get("merge_tree", {}), st.get("flush_cascade", {})


def sorted_sfs_leg(cfg, ids, x, required) -> dict:
    """Dispatch truth for the sorted-order SFS flush path (ISSUE 11): one
    telemetry-attached engine over the bench window, stamping which flush
    path each dispatch actually took (FlightRecorder ``flush.dispatch``
    entries), the knob mode, and the chooser's measured per-variant flush
    signatures. The byte-identity + speedup A/B lives in
    ``benchmarks/sorted_sfs.py`` (artifacts/sorted_sfs_ab.json); this
    block is what lets ``scripts/bench_compare.py`` catch the host path
    silently disappearing from the hot loop."""
    from skyline_tpu.ops.dispatch import sorted_sfs_mode
    from skyline_tpu.stream import SkylineEngine
    from skyline_tpu.telemetry import Telemetry

    eng = SkylineEngine(cfg, telemetry=Telemetry())
    n = x.shape[0]
    chunk = 65536
    for i in range(0, n, chunk):
        eng.process_records(ids[i : i + chunk], x[i : i + chunk])
    eng.process_trigger(f"0,{required}")
    eng.poll_results()
    paths: dict[str, int] = {}
    for e in eng.telemetry.flight.snapshot():
        if e.get("kind") == "flush.dispatch":
            p = str(e.get("path", "unknown"))
            paths[p] = paths.get(p, 0) + 1
    mode = sorted_sfs_mode()  # lint: allow-raw-gate (provenance stamp)
    block: dict = {"mode": mode, "dispatch_paths": paths}
    prof = eng.pset._flush_prof
    if prof is not None:
        block["flush_signatures"] = [
            {k: r[k] for k in ("variant", "n_bucket", "calls", "ema_ms")}
            for r in prof.doc()["kernels"]
        ]
    return block


def device_cascade_leg() -> dict:
    """Device-cascade truth for the bench artifact (ISSUE 18): the
    north-star-shaped flush A/B (quadratic SFS rounds vs the jit-safe
    device cascade, digest identity asserted before any wall) plus the
    profiler-auto leg proving ``choose_variant`` picks the winner from
    measured EMAs. ``scripts/bench_compare.py`` gates the flush speedup;
    the full grid lives in artifacts/device_cascade_ab.json."""
    from benchmarks.sorted_sfs import bench_cascade_auto, bench_cascade_flush
    from skyline_tpu.ops.dispatch import device_cascade_mode

    flush = bench_cascade_flush(n=65536)
    auto = bench_cascade_auto()
    return {
        "mode": device_cascade_mode(),  # lint: allow-raw-gate
        "flush_device_ms": flush["device_flush_ms"],
        "flush_cascade_ms": flush["cascade_flush_ms"],
        "flush_speedup": flush["speedup"],
        "digest_identical": flush["digest_identical"],
        "profiler_selects_cascade": auto["profiler_selects_cascade"],
        "cascade_selected_signatures": auto["cascade_selected_signatures"],
    }


def sharded_leg(cfg, ids, x, required) -> dict:
    """Sharded-engine truth for the bench artifact (ISSUE 12): one
    ``ShardedEngine`` over the bench window — trigger twice (cold
    two-level tournament, then facade epoch-cache hit) — stamping chip
    count, group size, merge/cache counters, and the window's own
    ``window_pruned_chip_fraction`` (≈0 on anti-correlated data, where
    every chip contributes to the front). A small fully-skewed prune
    probe then exercises the chip-witness prefilter so the
    ``pruned_chip_fraction`` that ``scripts/bench_compare.py`` gates on
    is non-trivial; the identity-asserting latency A/B lives in
    ``benchmarks/sharded_engine.py`` (artifacts/sharded_engine_ab.json)."""
    import dataclasses

    import jax

    if jax.device_count() < 4:  # the prune probe splits over four chips
        return {"skipped": True, "reason": "needs 4 devices"}
    from skyline_tpu.distributed import ShardedEngine, ShardedPartitionSet
    from skyline_tpu.telemetry import Telemetry

    scfg = cfg
    if getattr(cfg, "ingest", "host") == "device":
        # the sharded facade is host-merge only (each chip owns its own
        # ingest routing), so this leg always measures the host path
        scfg = dataclasses.replace(cfg, ingest="host")
    chips = 2 if scfg.parallelism % 2 == 0 else 1
    # a hub activates the fleet plane (ISSUE 13): the per-chip loads,
    # imbalance index and interconnect-row accounting of THIS window ride
    # the artifact as the top-level "fleet" block (child_main lifts it)
    hub = Telemetry()
    eng = ShardedEngine(scfg, chips=chips, telemetry=hub)
    n = x.shape[0]
    chunk = 65536
    for i in range(0, n, chunk):
        eng.process_records(ids[i : i + chunk], x[i : i + chunk])
    for _ in range(2):  # cold tournament, then facade epoch-cache hit
        eng.process_trigger(f"0,{required}")
        eng.poll_results()
    block = dict(eng.stats().get("sharded", {}))
    block["window_pruned_chip_fraction"] = block.pop(
        "pruned_chip_fraction", 0.0
    )
    # prune probe: chip 0 owns an origin cluster, every other chip only
    # dominated upper-region rows, so chip 0's witness skips them all
    Pp, probe_chips = 8, 4
    sp = ShardedPartitionSet(Pp, scfg.dims, 4096, chips=probe_chips)
    rng = np.random.default_rng(7)
    lo = (rng.random((64, scfg.dims)) * 40.0 + 1.0).astype(np.float32)
    hi = (rng.random((256, scfg.dims)) * 400.0 + 9000.0).astype(np.float32)
    sp.add_batch(0, lo, max_id=1 << 20, now_ms=0.0)
    for p in range(1, Pp):
        sp.add_batch(p, hi, max_id=1 << 20, now_ms=0.0)
    sp.flush_all()
    sp.global_merge_stats(emit_points=True)
    pst = sp.sharded_stats()
    block["prune_probe"] = {
        "chips": probe_chips,
        "chips_pruned": pst["chips_pruned"],
        "chips_considered": pst["chips_considered"],
    }
    block["pruned_chip_fraction"] = pst["pruned_chip_fraction"]
    if hub.fleet is not None:
        # bench_compare gates on fleet.imbalance_index (creeping chip skew
        # means the partitioner is funneling rows to few chips)
        block["fleet"] = hub.fleet.doc()
    return block


def workload_stamp(x) -> dict:
    """Workload-plane stamp (ISSUE 13): run the streaming characterizer
    over the bench window in ingest-sized chunks and record the regime it
    reports plus its own wall cost. The stamp records the stream's
    MEASURED regime, not the generator's label — at d >= 4 the unified
    anti-correlated generator's wide epsilon band genuinely produces
    positively correlated raw values (telemetry/workload.py docstring),
    and the raw signals (sum_ratio / rho / dispersion) ride along so the
    artifact stays auditable either way."""
    from skyline_tpu.telemetry.workload import WorkloadCharacterizer

    t0 = time.perf_counter()
    w = WorkloadCharacterizer(int(x.shape[1]))
    chunk = 4096
    for i in range(0, x.shape[0], chunk):
        w.observe(x[i : i + chunk])
    wall_ms = (time.perf_counter() - t0) * 1000.0
    st = w.stats()
    last = st["epochs"][-1] if st["epochs"] else {}
    return {
        "kind": st["kind"],
        "rho": st["rho"],
        "sum_ratio": last.get("sum_ratio"),
        "dispersion": last.get("dispersion"),
        "epochs_closed": st["epochs_closed"],
        "drift_total": st["drift_total"],
        "rows_seen": st["rows_seen"],
        "rows_sampled": st["rows_sampled"],
        "characterize_wall_ms": round(wall_ms, 1),
    }


def serve_leg(d: int, algo: str) -> dict:
    """Serving-plane microbenchmark: read latency p50/p99 and shed rate.

    Builds a small engine + snapshot store + the serve HTTP stack
    in-process, publishes one snapshot, then (a) hammers GET /skyline from
    ``BENCH_SERVE_READERS`` concurrent reader threads against an unlimited
    admission controller for the latency percentiles, and (b) replays a
    burst against a rate-limited controller to measure explicit load
    shedding (429 + Retry-After). Throughput here is reads served per
    second, not tuples ingested. Env knobs: BENCH_SERVE_N (window rows,
    default 65536), BENCH_SERVE_READERS (default 32), BENCH_SERVE_READS
    (per reader, default 25), BENCH_SERVE_POINTS=1 (full-payload reads
    instead of metadata-only).
    """
    import threading
    import urllib.error
    import urllib.request

    from skyline_tpu.serve import (
        AdmissionController,
        SkylineServer,
        SnapshotStore,
    )
    from skyline_tpu.stream import EngineConfig, SkylineEngine
    from skyline_tpu.telemetry import Histogram, Telemetry
    from skyline_tpu.workload.generators import anti_correlated

    n = env_int("BENCH_SERVE_N", 65536)
    readers = env_int("BENCH_SERVE_READERS", 32)
    reads_each = env_int("BENCH_SERVE_READS", 25)
    points = "1" if env_bool("BENCH_SERVE_POINTS", False) else "0"
    rng = np.random.default_rng(1)
    # one shared hub across engine + server: the server's /skyline handler
    # feeds the read stage of the same freshness lineage the engine stamps
    # (ingest/flush/merge/publish), so the stamped block below carries all
    # five stages from one bench run (ISSUE 8 acceptance)
    hub = Telemetry()
    from skyline_tpu.metrics.tracing import Tracer

    # non-syncing tracer: supplies the flush/merge_kernel phase total the
    # profiler attributes its per-signature wall time against
    eng = SkylineEngine(
        EngineConfig(parallelism=2, algo=algo, dims=d, domain_max=10000.0,
                     flush_policy="lazy"),
        tracer=Tracer(),
        telemetry=hub,
    )
    store = SnapshotStore()
    eng.attach_snapshots(store)
    eng.process_records(
        np.arange(n, dtype=np.int64), anti_correlated(rng, n, d, 0, 10000)
    )
    eng.process_trigger("bench-serve,0")
    eng.poll_results()
    snap = store.latest()

    def hammer(server, total, threads, hist, codes):
        url = (
            f"http://127.0.0.1:{server.port}/skyline"
            f"?points={points}&max_age_ms=600000"
        )
        per = total // threads

        def reader():
            for _ in range(per):
                t0 = time.perf_counter()
                try:
                    with urllib.request.urlopen(url, timeout=10) as r:
                        r.read()
                        codes.append(r.status)
                except urllib.error.HTTPError as e:
                    codes.append(e.code)
                if hist is not None:
                    hist.observe((time.perf_counter() - t0) * 1000.0)

        ts = [threading.Thread(target=reader) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    # (a) latency under concurrency, no admission limit — reader threads
    # observe straight into the shared telemetry Histogram (thread-safe),
    # the same summary machinery the worker's /stats p50/p99 tiles use
    read_hist = Histogram("serve_read_ms")
    codes: list[int] = []
    srv = SkylineServer(
        store, admission=AdmissionController(), port=0, telemetry=hub
    )
    t0 = time.perf_counter()
    hammer(srv, readers * reads_each, readers, read_hist, codes)
    wall_s = time.perf_counter() - t0
    srv.close()
    # (b) shed behavior against a deliberately tight token bucket
    shed_codes: list[int] = []
    srv = SkylineServer(
        store,
        admission=AdmissionController(read_rate=500.0, read_burst=64),
        port=0,
    )
    hammer(srv, readers * reads_each, readers, None, shed_codes)
    srv.close()
    shed = sum(1 for c in shed_codes if c == 429)
    read_pcts = read_hist.percentiles(50, 99)
    st = eng.stats()
    # EXPLAIN-plane stamp (ISSUE 9): ring state from this run's query plus
    # the record's serialized size and the pure ring-add cost — the e2e
    # on/off overhead lives in benchmarks/explain.py -> explain_ab.json
    explain = dict(st.get("explain", {"skipped": True}))
    latest = hub.explain.latest()
    if latest is not None:
        from skyline_tpu.telemetry.explain import ExplainRecorder

        explain["record_bytes"] = len(json.dumps(latest).encode())
        explain["path"] = (latest.get("merge") or {}).get("path")
        scratch = ExplainRecorder(256)
        reps = 2000
        t0 = time.perf_counter()
        for _ in range(reps):
            scratch.add(dict(latest))
        explain["ring_add_us"] = round(
            (time.perf_counter() - t0) / reps * 1e6, 2
        )
    # audit-plane stamp (ISSUE 10): shadow-verification verdict over this
    # run's published answers — scripts/bench_compare.py fails the gate on
    # ANY divergence; the on/off overhead lives in benchmarks/audit.py ->
    # audit_ab.json
    audit = dict(st.get("audit", {"skipped": True}))
    audit.pop("last_check", None)  # verbatim ring records stay off the
    audit.pop("last_divergence", None)  # artifact; totals gate the compare
    return {
        # end-to-end lineage + per-kernel registry from the same run the
        # reads above hit; child_main lifts these to top-level artifact keys
        "freshness": st.get("freshness", {}),
        "kernel_profile": st.get("kernel_profile", {}),
        "explain": explain,
        "audit": audit,
        "read_p50_ms": round(read_pcts["p50"], 2),
        "read_p99_ms": round(read_pcts["p99"], 2),
        "reads_ok": sum(1 for c in codes if c == 200),
        "reads_per_sec": round(read_hist.count / wall_s, 1),
        "readers": readers,
        "reads_per_reader": reads_each,
        "payload_points": points == "1",
        "snapshot_size": snap.size if snap is not None else 0,
        "window_n": n,
        "shed_burst_total": len(shed_codes),
        "shed_429": shed,
        "shed_rate": round(shed / max(1, len(shed_codes)), 3),
    }


def replica_leg(d: int) -> dict:
    """Replica-plane microbenchmark: WAL tail-to-serve lag (ISSUE 15).

    Builds a primary-side SnapshotStore whose publish hook appends the
    byte-exact delta record to a WAL, attaches one live ``SkylineReplica``
    tailing that WAL, publishes ``BENCH_REPLICA_PUBLISHES`` transitions,
    and reports the replica's ``replica_tail_lag_ms`` percentiles — the
    publish-stamp-to-apply lag the scripts/bench_compare.py sentinel gates
    as ``replica.read_lag_p99_ms``. Byte identity at the final common
    version is asserted into the block (a lag number from a diverged
    replica would be meaningless).
    """
    import shutil
    import tempfile

    from skyline_tpu.resilience.wal import WalWriter
    from skyline_tpu.serve import SnapshotStore, delta_wal_record
    from skyline_tpu.serve.replica import SkylineReplica

    n_pub = env_int("BENCH_REPLICA_PUBLISHES", 40)
    rows = env_int("BENCH_REPLICA_ROWS", 2048)
    tmp = tempfile.mkdtemp(prefix="bench-replica-")
    writer = store = replica = None
    try:
        writer = WalWriter(tmp, fsync="off")

        def shadow(prev, snap):
            writer.append(delta_wal_record(prev, snap))
            writer.flush(force=True)

        store = SnapshotStore()
        store.on_publish(shadow)
        replica = SkylineReplica(tmp, poll_interval_s=0.001)
        rng = np.random.default_rng(7)
        for _ in range(n_pub):
            store.publish(rng.random((rows, d), dtype=np.float32))
        converged = replica.wait_for_version(store.head_version, timeout_s=30.0)
        lag = replica.telemetry.histogram("replica_tail_lag_ms", unit="ms")
        pcts = lag.percentiles(50, 99)
        identical = bool(
            converged
            and replica.store.latest().points.tobytes()
            == store.latest().points.tobytes()
        )
        return {
            "read_lag_p50_ms": round(pcts["p50"], 2),
            "read_lag_p99_ms": round(pcts["p99"], 2),
            "publishes": n_pub,
            "rows_per_snapshot": rows,
            "records_applied": replica.records_applied,
            "head_version": replica.store.head_version,
            "converged": converged,
            "byte_identical": identical,
            "rebootstraps": replica.rebootstraps,
        }
    finally:
        if replica is not None:
            replica.close()
        if writer is not None:
            writer.close()
        shutil.rmtree(tmp, ignore_errors=True)


def cluster_leg(d: int) -> dict:
    """Cluster-plane stamp (ISSUE 16): the two numbers the gates watch.

    A skewed host-prune probe (host 0 owns an origin cluster, every other
    host only dominated upper-region rows) exercises the host-witness
    prefilter of the three-level tournament so the
    ``cluster.host_pruned_fraction`` that ``scripts/bench_compare.py``
    gates on is non-trivial — byte identity against a flat single-host
    merge is asserted before the number is recorded. A promotion drill
    (lease-holding primary publishes through a ``FencedWalWriter`` and
    goes dark; the supervisor fences + promotes the caught-up replica)
    records ``time_to_promote_ms``, which the telemetry sentinel watches
    for stalls; the identity-asserting latency A/B lives in
    ``benchmarks/cluster.py`` (artifacts/cluster_ab.json)."""
    import shutil
    import tempfile

    from skyline_tpu.cluster import (
        ClusterPartitionSet,
        ClusterSupervisor,
        FencedWalWriter,
        LeasePlane,
        WalFencedError,
    )
    from skyline_tpu.serve import SnapshotStore, delta_wal_record
    from skyline_tpu.serve.replica import SkylineReplica
    from skyline_tpu.serve.snapshot import points_digest
    from skyline_tpu.stream.batched import PartitionSet

    # prune probe: same geometry as sharded_leg's, one level up — host 0's
    # witness dominates the other hosts' summaries outright
    Pp, hosts = 8, 4
    rng = np.random.default_rng(7)
    lo = (rng.random((64, d)) * 40.0 + 1.0).astype(np.float32)
    hi = (rng.random((256, d)) * 400.0 + 9000.0).astype(np.float32)
    flat = PartitionSet(Pp, d, 4096)
    cp = ClusterPartitionSet(Pp, d, 4096, hosts=hosts)
    for pset in (flat, cp):
        pset.add_batch(0, lo, max_id=1 << 20, now_ms=0.0)
        for p in range(1, Pp):
            pset.add_batch(p, hi, max_id=1 << 20, now_ms=0.0)
        pset.flush_all()
    ref = flat.global_merge_stats(emit_points=True)
    res = cp.global_merge_stats(emit_points=True)
    identical = bool(
        res[2] == ref[2] and res[3].tobytes() == ref[3].tobytes()
    )
    cst = cp.cluster_stats()

    # promotion drill: everything on an injected clock except the
    # promotion wall itself (which is what the sentinel watches)
    tmp = tempfile.mkdtemp(prefix="bench-cluster-")
    writer = replica = None
    try:
        clock = {"now": 0.0}
        plane = LeasePlane(tmp, clock=lambda: clock["now"])
        lease = plane.acquire("primary-0", ttl_ms=500.0)
        writer = FencedWalWriter(tmp, lease.epoch, plane=plane, fsync="off")
        store = SnapshotStore()

        def shadow(prev, snap):
            writer.append(delta_wal_record(prev, snap))
            writer.flush(force=True)

        store.on_publish(shadow)
        pts = rng.random((256, d)).astype(np.float32)
        for i in range(1, 9):
            store.publish(pts[: i * 32], watermark_id=i * 32)
        replica = SkylineReplica(tmp, replica_id="r0", start=False)
        replica.bootstrap()
        while replica.apply_available():
            pass
        sup = ClusterSupervisor(
            tmp, [replica], lease_ttl_ms=500.0, clock=lambda: clock["now"]
        )
        clock["now"] = 10_000.0  # primary dead: lease expired
        doc = sup.tick()
        promoted = doc is not None and doc["holder"] == "r0"
        head_identical = bool(
            promoted
            and doc["head_digest"] == points_digest(store.latest().points)
        )
        try:
            writer.append({"type": "delta", "probe": True})
            deposed_rejected = False
        except WalFencedError:
            deposed_rejected = True
        return {
            "hosts": hosts,
            "hosts_pruned": cst["hosts_pruned"],
            "host_pruned_fraction": cst["host_pruned_fraction"],
            "rows_shipped": cst["rows_shipped"],
            "rows_saved": cst["rows_saved"],
            "probe_identical": identical,
            "promoted": promoted,
            "time_to_promote_ms": (
                doc["time_to_promote_ms"] if promoted else None
            ),
            "promoted_head_version": doc["head_version"] if promoted else None,
            "promoted_head_identical": head_identical,
            "deposed_append_rejected": deposed_rejected,
        }
    finally:
        if replica is not None:
            replica.close()
        if writer is not None:
            writer.close()
        shutil.rmtree(tmp, ignore_errors=True)


def ops_leg(d: int) -> dict:
    """Ops-plane stamp (ISSUE 17): the cost of watching the cluster.

    Three numbers: the per-record append cost of the durable ops journal
    (one CRC-framed ``os.write`` per control-plane transition — this is
    the overhead every lease renewal and fence raise pays), the wall to
    re-read and merge the journal, and the clusterview scrape wall
    against one real member over loopback HTTP (journal tail + /metrics
    + /cluster + /healthz folded into the ``/cluster/overview`` doc).
    The identity-asserting on/off A/B lives in ``benchmarks/opslog.py``
    (artifacts/opslog_ab.json); the replication-lag quantiles the
    sentinel gates are restated from the replica leg by ``child_main``.
    """
    import shutil
    import tempfile

    from skyline_tpu.metrics.httpstats import StatsServer
    from skyline_tpu.telemetry import Telemetry
    from skyline_tpu.telemetry.clusterview import ClusterView
    from skyline_tpu.telemetry.opslog import OpsLog, read_ops

    appends = env_int("BENCH_OPS_APPENDS", 2000)
    tmp = tempfile.mkdtemp(prefix="bench-ops-")
    srv = ops = None
    try:
        ops = OpsLog(tmp, fsync="off")
        t0 = time.perf_counter()
        for i in range(appends):
            ops.record("lease_acquired", epoch=i, fence=i, holder="bench")
        append_us = (time.perf_counter() - t0) / max(1, appends) * 1e6
        ops.flush(force=True)
        t0 = time.perf_counter()
        doc = read_ops(tmp)
        read_wall_ms = (time.perf_counter() - t0) * 1e3
        hub = Telemetry()
        hub.opslog = ops
        srv = StatsServer(lambda: {"ok": True}, port=0, telemetry=hub)
        view = ClusterView([f"http://127.0.0.1:{srv.port}"])
        overview = view.overview()
        return {
            "journal_append_us": round(append_us, 2),
            "journal_records": doc["total"],
            "journal_read_wall_ms": round(read_wall_ms, 2),
            "scrape_wall_ms": overview.get("scrape_wall_ms"),
            "scrape_ok": bool(overview["members"][0]["ok"]),
            "findings": len(overview["findings"]),
        }
    finally:
        if srv is not None:
            srv.close()
        if ops is not None:
            ops.close()
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit("bench.py measures on an accelerator; JAX found none")

    # persistent XLA compilation cache: the capacity-bucket executables
    # survive across bench runs, collapsing the warmup window
    from skyline_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    n = env_int("BENCH_N", 1_000_000)
    d = env_int("BENCH_D", 8)
    windows = env_int("BENCH_WINDOWS", 5)
    parallelism = env_int("BENCH_PARALLELISM", 4)

    from skyline_tpu.stream import EngineConfig
    from skyline_tpu.workload.generators import anti_correlated

    # mr-angle is the reference's documented best for anti-correlated data
    # (pdf §5.6); BENCH_ALGO overrides for partitioner A/B runs — at 8D
    # mr-angle routes ~96% of rows to 2 of 8 partitions (stream/batched.py
    # skew notes), so a balanced partitioner can do several times less
    # local-phase dominance work for the same (invariant) result
    algo = env_str("BENCH_ALGO", "mr-angle")
    cfg = EngineConfig(
        parallelism=parallelism,
        algo=algo,
        dims=d,
        domain_max=10000.0,
        buffer_size=env_int("BENCH_BUFFER", 8192),
        # pre-size to the known steady-state local-skyline bucket for the
        # 8-D anti-correlated window (~57k/partition -> 64k bucket): skips
        # the per-window capacity-growth syncs/recompiles
        initial_capacity=env_int("BENCH_INITIAL_CAP", 65536),
        # lazy = sum-sorted append-only SFS at query time: a fraction of the
        # incremental policy's dominance work for the tumbling
        # window-then-query pattern (see stream/batched.py). Set
        # BENCH_FLUSH_POLICY=incremental to measure the streaming cadence,
        # =overlap for the transport-style chunked flushes.
        flush_policy=env_str("BENCH_FLUSH_POLICY", "lazy"),
        # device ingest: pre-size the accumulation window to the known
        # window size (skips per-run growth reallocs/executables)
        window_capacity=n,
    )
    rng = np.random.default_rng(0)
    ids = np.arange(n, dtype=np.int64)
    # immediate trigger: the window is fully ingested before the query, so
    # required=0 covers all n records; a positive barrier would make sparse
    # partitions (which may never see the stream's last ids) defer forever
    # on a finite stream (the reference's heuristic-barrier quirk, §3.3)
    required = 0

    # warmup window: populates XLA's executable cache for every capacity
    # bucket so measured windows reflect steady-state streaming
    x = anti_correlated(rng, n, d, 0, 10000)
    warm_dt, warm_res = run_window(cfg, ids, x, required)

    # profile window: same workload with a device-syncing Tracer so the
    # bench JSON carries the per-phase anatomy of a window (syncs distort
    # pipelining, so this window is NOT included in the measured latencies)
    from skyline_tpu.metrics.tracing import Tracer

    tracer = Tracer(sync_device=True)
    prof_dt, _ = run_window(
        cfg, ids, anti_correlated(rng, n, d, 0, 10000), required, tracer=tracer
    )
    phases = {
        name: round(v["total_ms"], 1)
        for name, v in tracer.report().items()
    }
    phases["profile_window_total"] = round(prof_dt * 1000.0, 1)

    # the telemetry Histogram keeps small samples verbatim, so this p50 is
    # the exact median of the measured windows (same machinery as /stats)
    from skyline_tpu.telemetry import Histogram

    lat_hist = Histogram("window_latency_s", unit="s")
    sky_sizes = []
    for _ in range(windows):
        x = anti_correlated(rng, n, d, 0, 10000)
        dt, res = run_window(cfg, ids, x, required)
        lat_hist.observe(dt)
        sky_sizes.append(res["skyline_size"])

    p50_s = lat_hist.quantile(0.5)
    tuples_per_sec = n / p50_s
    # serving-plane leg: read-side latency + shed behavior (BENCH_SERVE=0
    # to skip)
    if env_bool("BENCH_SERVE", True):
        serve = serve_leg(d, algo)
    else:
        serve = {"skipped": True}
    # serve-load leg: multi-tenant body-store A/B under zipf-skewed load
    # (BENCH_LOAD=0 to skip; identity asserted before timing —
    # benchmarks/loadgen.py, RUNBOOK §2u)
    if env_bool("BENCH_LOAD", True):
        from benchmarks.loadgen import run_load

        serve_load = run_load()
    else:
        serve_load = {"skipped": True}
    # replica-plane leg: WAL tail-to-serve lag (BENCH_REPLICA=0 to skip)
    if env_bool("BENCH_REPLICA", True):
        replica = replica_leg(d)
    else:
        replica = {"skipped": True}
    # cluster-plane leg: host-prune probe + promotion drill
    # (BENCH_CLUSTER=0 to skip)
    if env_bool("BENCH_CLUSTER", True):
        cluster = cluster_leg(d)
    else:
        cluster = {"skipped": True}
    # ops-plane leg: journal append cost + clusterview scrape wall
    # (BENCH_OPS=0 to skip)
    if env_bool("BENCH_OPS", True):
        ops = ops_leg(d)
    else:
        ops = {"skipped": True}
    # dispatch-tuner leg: static-best vs controller regret under drift,
    # digest identity asserted at every trigger (BENCH_TUNER=0 to skip;
    # the full-scale grid lives in artifacts/tuner_ab.json —
    # benchmarks/tuner.py, RUNBOOK §2v)
    if env_bool("BENCH_TUNER", True):
        from benchmarks.tuner import run_ab

        tuner = run_ab(rows_per_phase=3000, d=4, chunk=750)
    else:
        tuner = {"skipped": True}
    # replication lag for the ops-plane sentinel/gate: the replica leg's
    # real tail-lag quantiles, restated under the blocks whose dotted
    # paths the watchers resolve (cluster.replication_lag_p99_ms)
    if replica.get("read_lag_p99_ms") is not None:
        cluster["replication_lag_p99_ms"] = replica["read_lag_p99_ms"]
        ops["replication_lag_p50_ms"] = replica.get("read_lag_p50_ms")
        ops["replication_lag_p99_ms"] = replica["read_lag_p99_ms"]
    # lineage + kernel registry ride the artifact as top-level blocks so
    # scripts/bench_compare.py can gate on freshness.read_lag_p99_ms
    freshness = serve.pop("freshness", {"skipped": True})
    kernel_profile = serve.pop("kernel_profile", {"skipped": True})
    explain = serve.pop("explain", {"skipped": True})
    audit = serve.pop("audit", {"skipped": True})
    merge_cache, merge_tree, flush_cascade = merge_cache_leg(
        cfg, ids, anti_correlated(rng, n, d, 0, 10000), required
    )
    sorted_sfs = sorted_sfs_leg(
        cfg, ids, anti_correlated(rng, n, d, 0, 10000), required
    )
    device_cascade = device_cascade_leg()
    sharded = sharded_leg(
        cfg, ids, anti_correlated(rng, n, d, 0, 10000), required
    )
    # the fleet block rides top-level so bench_compare's dotted path
    # (fleet, imbalance_index) resolves without reaching through sharded
    fleet = sharded.pop("fleet", {"skipped": True})
    workload = workload_stamp(anti_correlated(rng, n, d, 0, 10000))
    analysis = analysis_stamp()
    resilience = resilience_stamp()
    failover = failover_stamp()
    # the gate input is the MEASURED bench window, not the drill: a
    # healthy run that degraded any answer is a regression outright
    failover["healthy_degraded_answers"] = int(
        sharded.get("degraded_merges", 0) or 0
    )
    print(
        json.dumps(
            {
                "metric": (
                    f"skyline tuples/sec, {d}D anti-correlated "
                    f"{n}-tuple windows (p50 of end-to-end window latency)"
                ),
                "value": round(tuples_per_sec, 1),
                "unit": "tuples/s",
                "vs_baseline": round(tuples_per_sec / REFERENCE_TUPLES_PER_SEC, 2),
                "backend": dev.platform,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(jax.devices()),
                },
                "p50_window_latency_ms": round(p50_s * 1000.0, 1),
                "window_n": n,
                "dims": d,
                "windows_measured": windows,
                "algo": algo,
                "skyline_size_p50": int(np.median(sky_sizes)),
                "flush_policy": cfg.flush_policy,
                "rank_cascade": rank_cascade_stamp(),
                "serve": serve,
                "serve_load": serve_load,
                "replica": replica,
                "cluster": cluster,
                "ops": ops,
                "tuner": tuner,
                "warmup_window_s": round(warm_dt, 2),
                "phase_breakdown_ms": phases,
                "sorted_sfs": sorted_sfs,
                "device_cascade": device_cascade,
                "resilience": resilience,
                "failover": failover,
                "merge_cache": merge_cache,
                "merge_tree": merge_tree,
                "flush_cascade": flush_cascade,
                "sharded": sharded,
                "fleet": fleet,
                "workload": workload,
                "freshness": freshness,
                "kernel_profile": kernel_profile,
                "explain": explain,
                "audit": audit,
                "analysis": analysis,
                "baseline_anchor": "reference 4D/1M ~1400 tuples/s (d=8 never completed)",
            }
        )
    )


if __name__ == "__main__":
    main()
