"""The streaming skyline engine: partition routing, query barrier, global merge.

One object replaces the reference's whole Flink job graph
(FlinkSkyline.java:61-186): the ``keyBy`` shuffle becomes vectorized
host-side partition-id routing; ``SkylineLocalProcessor`` becomes
``PartitionSet`` (all logical partitions stacked on device, one batched
merge launch per flush) addressed through per-partition ``PartitionView``
facades; the query broadcast flatMap (:145-157) becomes a loop over
partitions; and ``GlobalSkylineAggregator`` (:460-660) becomes a device-side
union skyline with the same countdown-latch semantics, timing decomposition
and optimality metric.

Record-id barrier semantics (SURVEY.md §3.3): a trigger ``"qid,N"`` executes
on a partition only once that partition has seen a record id >= N — or
immediately if the partition has never seen data (``max_seen_id == -1``,
FlinkSkyline.java:351). Pending triggers are re-evaluated whenever new data
reaches the partition (:298-315).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field


import numpy as np

from skyline_tpu.ops.dispatch import query_overlap_enabled, skyline_keep_np
from skyline_tpu.parallel.partitioners import partition_ids_np
from skyline_tpu.bridge.wire import parse_trigger
from skyline_tpu.stream.batched import PartitionSet, PartitionView
from skyline_tpu.stream.window import DEFAULT_BUFFER_SIZE


@dataclass
class EngineConfig:
    """Engine flags, defaults matching the reference job's
    (FlinkSkyline.java:62-76): parallelism=4 → numPartitions=8, algo
    mr-angle, domain 1000, dims 2."""

    parallelism: int = 4
    algo: str = "mr-angle"
    domain_max: float = 1000.0
    dims: int = 2
    buffer_size: int = DEFAULT_BUFFER_SIZE
    emit_skyline_points: bool = False
    # failure detection: a query whose barrier never clears on some partition
    # finalizes as a PARTIAL result after this long (0 = wait forever, the
    # reference's behavior — its countdown latch hangs if a partition never
    # reports, SURVEY.md §5)
    query_timeout_ms: float = 0.0
    # the reference's GridDominanceFilter (J10) — commented out there "for
    # safety" over barrier-deadlock fears (FlinkSkyline.java:120-124,
    # 717-734) — implemented here SAFELY: a tuple with every coordinate
    # >= domain/2 (and one >) is dropped pre-routing, but only once a
    # witness tuple with every coordinate <= domain/2 has been seen (the
    # witness dominates-or-equals the midpoint, which by transitivity
    # dominates the dropped tuple). Barriers are unaffected: max-seen-id
    # advances before filtering.
    grid_prefilter: bool = False
    # pre-size per-partition skyline buffers (0 = grow on demand); see
    # PartitionSet.initial_capacity
    initial_capacity: int = 0
    # "incremental": merge pending rows at the buffer_size cadence (the
    # reference's processBuffer model); "lazy": accumulate and compute at
    # query time via append-only SFS rounds — far less total work for
    # tumbling-window-then-query streams (see stream/batched.py); "overlap":
    # the lazy machinery flushed every ``overlap_rows`` so device append
    # rounds run concurrently with transport/parse of the next chunk (the
    # Flink-style source/operator overlap). Identical results all three
    # ways; under a mesh the lazy rounds run shard_map SPMD.
    flush_policy: str = "incremental"
    # rows accumulated between automatic flushes under flush_policy="overlap"
    overlap_rows: int = 262144
    # expected rows per window (0 = unknown): pre-sizes the device-ingest
    # accumulation buffer so steady-state windows never grow it (each
    # growth is a reallocation + a fresh ingest executable per capacity)
    window_capacity: int = 0
    # "auto": route + sort + SFS block slicing on device when single-device
    # lazy/overlap without grid_prefilter (stream/device_window.py); "host":
    # numpy routing in process_records; "device": force the device path
    # (errors if unsupported by the configuration)
    ingest: str = "auto"
    # > 0: the sharded engine (``distributed/sharded.py``) splits the
    # partitions into this many per-chip groups, chip c owning partitions
    # [c*G, (c+1)*G), and answers by the two-level chip tournament; 0 runs
    # every partition on one chip. Placement only: answers are
    # byte-identical either way, and checkpoints restore onto any count
    mesh_chips: int = 0

    @property
    def num_partitions(self) -> int:
        # 2x over-partitioning for skew tolerance (FlinkSkyline.java:74-76)
        return 2 * self.parallelism


def echo_record_count(payload: str):
    """The reference echoes the payload's second field as record_count
    (FlinkSkyline.java:640-642) — emitting the literal string, which for a
    count-less payload would produce invalid JSON (unquoted `unknown`); we
    quote it instead. Shared by both engine modes."""
    parts = payload.split(",")
    if len(parts) > 1 and parts[1].strip().lstrip("-").isdigit():
        return int(parts[1])
    return "unknown"


def optimality_mean(survivors, sizes, num_partitions: int) -> float:
    """Mean over ALL partitions of survivors_i / localSize_i, empty
    partitions contributing 0 (FlinkSkyline.java:592-608)."""
    ratios = 0.0
    for surv, size in zip(survivors, sizes):
        if size > 0:
            ratios += surv / size
    return ratios / num_partitions


@dataclass
class _QueryState:
    """Aggregator state for one in-flight query (FlinkSkyline.java:490-495)."""

    qid: str
    payload: str
    required: int
    dispatch_ms: float
    partials: dict = field(default_factory=dict)  # pid -> (k, d) local skyline
    local_sizes: dict = field(default_factory=dict)
    start_times: dict = field(default_factory=dict)
    cpu_ms: dict = field(default_factory=dict)
    last_arrival_ms: float = 0.0
    # telemetry plane: trace id minted at trigger ingestion + the perf
    # clock at dispatch, so the end-to-end "query" span is recordable
    trace_id: str | None = None
    span_t0_ns: int = 0
    # EXPLAIN plane (telemetry/explain.py): the QueryPlan minted beside the
    # trace id; annotated along the merge path, finalized at result emission
    plan: object | None = None


class SkylineEngine:
    """Single-host streaming engine over ``num_partitions`` logical partitions.

    Usage: ``process_records`` / ``process_trigger`` as data and control
    planes; completed query results accumulate and are drained with
    ``poll_results`` (each result is a dict with the reference's JSON fields).
    """

    def __init__(self, config: EngineConfig, mesh=None, tracer=None, telemetry=None):
        """``mesh``: optional ``jax.sharding.Mesh`` — logical partitions are
        then sharded across its devices (local flushes run SPMD, one launch
        for the whole set) and the global merge runs as the sharded
        two-phase collective instead of a single-device kernel. ``None``
        (default) runs everything on one chip. The mesh is a runtime
        placement choice, not part of the query semantics, so it lives
        outside ``EngineConfig`` (results are device-count invariant —
        tests/test_mesh.py pins this).

        ``tracer``: optional ``metrics.tracing.Tracer`` — wires the
        per-phase breakdown (route / flush kernels / snapshot transfer /
        global merge) the reference surfaces as a product feature
        (SURVEY.md §5); ``None`` costs nothing.

        ``telemetry``: optional ``telemetry.Telemetry`` hub — adds latency
        histograms (ingest batch / global merge / query latency), a
        ``trace_id`` per query, and per-phase spans into the hub's bounded
        ring (exported via ``GET /trace`` / ``--trace-out``); ``None``
        (default) records nothing."""
        from skyline_tpu.metrics.tracing import NULL_TRACER

        self.config = config
        self.mesh = mesh
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.telemetry = telemetry
        # resolve the ingest path: device ingest moves routing/sort/block
        # slicing onto the accelerator (stream/device_window.py); it
        # requires single-device lazy/overlap and no grid prefilter (the
        # prefilter inspects raw values host-side)
        device_ok = (
            mesh is None
            and config.flush_policy in ("lazy", "overlap")
            and not config.grid_prefilter
        )
        if config.ingest == "device":
            if not device_ok:
                raise ValueError(
                    "ingest='device' requires single-device lazy/overlap "
                    "without grid_prefilter"
                )
            use_device = True
        elif config.ingest == "auto":
            from skyline_tpu.ops.dispatch import on_tpu

            use_device = device_ok and on_tpu()
        elif config.ingest == "host":
            use_device = False
        else:
            raise ValueError(f"unknown ingest mode {config.ingest!r}")
        # stacked device state: all partitions' skylines merge in ONE launch
        # per flush (see stream/batched.py); `partitions` are per-partition
        # facades over it
        self.pset = PartitionSet(
            config.num_partitions,
            config.dims,
            config.buffer_size,
            mesh=mesh,
            initial_capacity=config.initial_capacity,
            tracer=self.tracer,
            flush_policy=config.flush_policy,
            route=(config.algo, config.domain_max) if use_device else None,
            overlap_rows=config.overlap_rows,
            window_capacity=config.window_capacity,
            counters=telemetry.counters if telemetry is not None else None,
        )
        self.partitions = [
            PartitionView(self.pset, i) for i in range(config.num_partitions)
        ]
        self._pending_queries: dict[int, list[_QueryState]] = {
            i: [] for i in range(config.num_partitions)
        }
        self._inflight: dict[str, _QueryState] = {}
        self._results: list[dict] = []
        self.records_in = 0
        self.dropped = 0
        self.prefiltered = 0
        self._midpoint_witness = False  # grid_prefilter safety latch
        # overlapped query sync (SKYLINE_QUERY_OVERLAP): at most one global
        # merge in flight as (query, handle, now_ms, flush_wall_ms,
        # launch_ms) — launched at trigger time, harvested at the next
        # result drain / trigger / stats poll, or opportunistically while
        # ingesting once the stats bytes have landed
        self._inflight_merge: tuple | None = None
        # serving plane (serve/snapshot.py): when attached, every completed
        # global skyline publishes as an immutable versioned snapshot and
        # every ingest micro-batch advances its staleness counter
        self.snapshots = None
        # observability plane (ISSUE 8): freshness lineage + the per-kernel
        # dispatch profiler / flight recorder. All host-side — nothing here
        # touches the jitted byte-identity path. Without a hub the engine
        # still owns private instances so bench legs get the stats blocks.
        from skyline_tpu.ops.dispatch import (
            audit_enabled,
            explain_enabled,
            freshness_enabled,
            kernel_profile_enabled,
            workload_enabled,
        )
        from skyline_tpu.telemetry import (
            FreshnessTracker,
            KernelProfiler,
            WorkloadCharacterizer,
        )

        self.freshness = (
            FreshnessTracker(telemetry) if freshness_enabled() else None
        )
        if kernel_profile_enabled():
            self.profiler = (
                telemetry.profiler
                if telemetry is not None
                else KernelProfiler()
            )
        else:
            self.profiler = None
        self.pset.attach_observability(
            profiler=self.profiler,
            flight=telemetry.flight if telemetry is not None else None,
        )
        # EXPLAIN plane (ISSUE 9): one QueryPlan per trigger, landed in the
        # hub's bounded ring. The marks anchor the per-query attribution
        # windows — cascade counters and kernel dispatch counts since the
        # PREVIOUS plan finalized belong to the next query's window.
        self._explain_on = explain_enabled() and telemetry is not None
        self._explain_cascade_mark: dict = {}
        self._explain_kernel_mark: dict = {}
        if self._explain_on:
            # inc even when zero so the Prometheus series registers before
            # the first query, not after it
            telemetry.inc("explain.records", 0)
        # audit plane (ISSUE 10): sampled shadow verification of published
        # snapshots against the host oracle, plus the canary driver the
        # worker ticks from its idle loop. Post-publish and host-side only.
        self.auditor = None
        if telemetry is not None and audit_enabled():
            from skyline_tpu.audit import Auditor

            self.auditor = Auditor(self, telemetry)
            telemetry.inc("audit.checks", 0)
            telemetry.inc("audit.divergence", 0)
        # workload plane (ISSUE 13): streaming regime characterization fed
        # from the ingest path — per-dim quantile sketches, a correlation
        # estimate, and drift detection between consecutive epochs. All
        # host-side on a bounded deterministic sample; published skyline
        # bytes are untouched on/off (tests/test_workload_plane.py pins
        # this). Hung
        # off the hub so both HTTP surfaces serve the ``workload`` block.
        self.workload = None
        if workload_enabled():
            self.workload = WorkloadCharacterizer(
                config.dims,
                counters=telemetry.counters if telemetry is not None else None,
                flight=telemetry.flight if telemetry is not None else None,
            )
            if telemetry is not None:
                telemetry.workload = self.workload
        # dispatch-tuner plane (ISSUE 20): the closed-loop controller
        # over the declarative cascade table — consumes the workload
        # regime, profiler EMAs, and SLO burn; retunes table pins/knobs
        # with bounded per-epoch moves. Ticked from the query path (cheap
        # cadence check) and the worker idle loop; passive until a
        # workload epoch closes, so bytes and unit-scale behavior are
        # untouched by default.
        self.tuner = None
        from skyline_tpu.ops.dispatch import tuner_enabled

        if telemetry is not None and tuner_enabled():
            from skyline_tpu.telemetry.tuner import DispatchTuner

            self.tuner = DispatchTuner(
                telemetry=telemetry,
                workload=self.workload,
                profiler=self.profiler,
                flush_profiler=lambda: getattr(
                    self.pset, "_flush_prof", None
                ),
            )
            telemetry.tuner = self.tuner

    def attach_snapshots(self, store) -> None:
        """Publish completed global skylines to ``store`` (a
        ``serve.snapshot.SnapshotStore``). Costs nothing until attached;
        once attached, query answers materialize their points even when
        ``emit_skyline_points`` is off (the snapshot IS the read path)."""
        self.snapshots = store

    # -- data plane -------------------------------------------------------

    def process_records(
        self,
        ids: np.ndarray,
        values: np.ndarray,
        now_ms: float | None = None,
        event_ms=None,
    ) -> None:
        """Route a micro-batch of records to partitions and advance barriers.

        ids: (N,) int64 global record ids; values: (N, d) float32.
        ``event_ms`` (optional): producer event-time of this batch for the
        freshness lineage — a scalar or a (min, max) pair in epoch ms. The
        wire format carries no timestamps, so callers typically stamp the
        poll wall time (a processing-time proxy; RUNBOOK §2j).
        """
        with self.tracer.span_phase(
            "ingest/batch", self._spans, "ingest",
            args={"rows": int(values.shape[0])},
        ) as ph:
            self._process_records(ids, values, now_ms, event_ms)
        if self.telemetry is not None:
            self.telemetry.histogram("ingest_batch_ms").observe(ph.ms)

    def _process_records(
        self,
        ids: np.ndarray,
        values: np.ndarray,
        now_ms: float | None = None,
        event_ms=None,
    ) -> None:
        if values.shape[0] == 0:
            return
        if now_ms is None:
            now_ms = time.time() * 1000.0
        cfg = self.config
        self.records_in += values.shape[0]
        if self.workload is not None:
            # characterize BEFORE the ingest path forks (device routing vs
            # host routing vs grid prefilter) so every regime sees the same
            # raw stream; bounded stride-sample inside, never the full batch
            self.workload.observe(values)
        ev_hi = None
        if self.freshness is not None:
            # stamp the batch's event-time window; absent stamps fall back
            # to the wall clock (NOT the caller's now_ms — tests inject
            # synthetic clocks that would poison the lag histograms)
            if event_ms is None:
                ev_lo = ev_hi = time.time() * 1000.0
            elif isinstance(event_ms, (tuple, list)):
                ev_lo, ev_hi = float(event_ms[0]), float(event_ms[1])
            else:
                ev_lo = ev_hi = float(event_ms)
            self.freshness.on_ingest(ev_lo, ev_hi)
        if self.snapshots is not None:
            # the latest snapshot is now one ingest advance behind
            self.snapshots.note_ingest(int(ids.max()), event_ms=ev_hi)
        if self.pset.device_ingest:
            # routing + barrier stats on device; host bookkeeping syncs only
            # when a pending query needs its barrier re-evaluated
            with self.tracer.phase("ingest/devroute"):
                self.pset.ingest_chunk(ids, values, now_ms)
            if any(self._pending_queries.values()):
                self.pset.sync_ingest_bookkeeping()
                for p in range(cfg.num_partitions):
                    now_ms = self._recheck_pending(p, now_ms)
            self.pset.maybe_flush()
            self._note_flush()
            self._harvest_inflight(block=False)
            return
        with self.tracer.phase("partition_ids"):
            pids = partition_ids_np(
                values, cfg.algo, cfg.num_partitions, cfg.domain_max
            )
        doomed_pids: np.ndarray | None = None
        if cfg.grid_prefilter:
            mid = cfg.domain_max / 2.0
            if not self._midpoint_witness and bool((values <= mid).all(axis=1).any()):
                self._midpoint_witness = True
            if self._midpoint_witness:
                # advance each partition's barrier with the dropped rows'
                # ids BEFORE filtering — the reference feared exactly this
                # deadlock (a dropped tuple's id never reaching the barrier)
                doomed = (values >= mid).all(axis=1) & (values > mid).any(axis=1)
                if doomed.any():
                    doomed_pids = np.unique(pids[doomed])
                    for p in doomed_pids:
                        part = self.partitions[p]
                        mx = int(ids[doomed & (pids == p)].max())
                        if part.start_time_ms is None:
                            part.start_time_ms = now_ms
                        part.max_seen_id = max(part.max_seen_id, mx)
                    self.prefiltered += int(doomed.sum())
                    keep = ~doomed
                    values = values[keep]
                    ids = ids[keep]
                    pids = pids[keep]
        # group rows by partition with one argsort (the keyBy shuffle).
        # now_ms advances through the loop: an answer's snapshot flush can
        # take seconds (first-query compile), and later answers in the SAME
        # call must see a clock past it or the timing decomposition goes
        # impossible (local > total) — the round-2 deploy-artifact bug.
        with self.tracer.phase("route"):
            order = np.argsort(pids, kind="stable")
            sorted_pids = pids[order]
            sorted_vals = values[order]
            sorted_ids = ids[order]
            bounds = np.searchsorted(
                sorted_pids, np.arange(cfg.num_partitions + 1)
            )
            for p in range(cfg.num_partitions):
                lo, hi = bounds[p], bounds[p + 1]
                if lo == hi:
                    continue
                part = self.partitions[p]
                part.add_batch(
                    sorted_vals[lo:hi], int(sorted_ids[lo:hi].max()), now_ms
                )
                now_ms = self._recheck_pending(p, now_ms)
        # one batched launch merges every partition's pending rows at once
        self.pset.maybe_flush()
        self._note_flush()
        if doomed_pids is not None:
            # partitions whose barrier advanced only via dropped rows still
            # need their pending queries rechecked (after the kept rows of
            # this batch have routed, so answers reflect the full batch)
            for p in doomed_pids:
                now_ms = self._recheck_pending(int(p), now_ms)
        # an overlapped merge whose bytes already landed costs ~nothing to
        # harvest here; one that hasn't stays in flight (never block ingest)
        self._harvest_inflight(block=False)

    @property
    def _spans(self):
        """The hub's span ring (``/trace``), or None without telemetry."""
        return self.telemetry.spans if self.telemetry is not None else None

    def _note_flush(self) -> None:
        """Advance the freshness flush stage once NO ingested rows remain
        host-pending — lazy/overlap policies may leave rows buffered past a
        ``maybe_flush``, and those batches must keep aging in the ingest
        stage until a flush actually absorbs them."""
        if self.freshness is not None and self.pset.pending_rows_total == 0:
            self.freshness.on_flush()

    # -- control plane ----------------------------------------------------

    def process_trigger(self, payload: str, now_ms: float | None = None) -> None:
        """Broadcast a query trigger to every partition (the flatMap fan-out,
        FlinkSkyline.java:145-157).

        Fast path: when every partition's barrier passes at dispatch (the
        dominant case — a trigger after its window is ingested) and the
        engine is single-device, the local snapshots and the global merge
        all run on device with only per-partition counts coming back to
        host; the full local-skyline buffers are never transferred."""
        if now_ms is None:
            now_ms = time.time() * 1000.0
        # a previous overlapped merge lands before a new query dispatches:
        # results stay in trigger order and the engine keeps at most one
        # merge in flight
        self._harvest_inflight()
        if self.pset.has_unsynced_ingest:
            # barrier checks below read per-partition max ids
            self.pset.sync_ingest_bookkeeping()
        qid, required = parse_trigger(payload)
        q = _QueryState(qid=qid, payload=payload, required=required, dispatch_ms=now_ms)
        flight = None
        if self.telemetry is not None:
            q.trace_id = self.telemetry.mint_trace_id()
            q.span_t0_ns = time.perf_counter_ns()
            # stamp this trigger's flush/launch decisions in the flight
            # ring with its trace id so /debug/flight joins /trace and
            # /explain instead of being time-correlated by eye
            flight = self.telemetry.flight
            flight.set_trace(q.trace_id)
        if self._explain_on:
            from skyline_tpu.telemetry.explain import QueryPlan

            q.plan = QueryPlan(q.trace_id, qid)
            # park it for global_merge_launch to claim onto its handle
            self.pset.set_explain(q.plan)
        self._inflight[payload] = q
        try:
            all_ready = all(
                part.max_seen_id >= required or part.max_seen_id == -1
                for part in self.partitions
            )
            if all_ready and self.mesh is None:
                self._answer_all_device(q, now_ms)
                return
            for p in range(self.config.num_partitions):
                part = self.partitions[p]
                if part.max_seen_id >= required or part.max_seen_id == -1:
                    now_ms = self._answer(p, q, now_ms)
                else:
                    self._pending_queries[p].append(q)
        finally:
            if flight is not None:
                flight.set_trace(None)
            # a plan the merge never claimed (host path, pending barrier)
            # must not leak onto a later query's merge
            self.pset.set_explain(None)

    def _recheck_pending(self, p: int, now_ms: float) -> float:
        """Returns the advanced clock (answers add their snapshot wall so
        the caller's subsequent answers don't time-travel before them)."""
        part = self.partitions[p]
        still = []
        for q in self._pending_queries[p]:
            if part.max_seen_id >= q.required:
                now_ms = self._answer(p, q, now_ms)
            else:
                still.append(q)
        self._pending_queries[p] = still
        return now_ms

    # -- local answer + global aggregation --------------------------------

    def _answer(self, p: int, q: _QueryState, now_ms: float) -> float:
        """Partition p finalizes its local skyline for query q
        (processQuery, FlinkSkyline.java:367-403).

        Clock discipline: ``snapshot()`` runs ``flush_all`` whose wall time
        (possibly seconds, incl. first-query jit compile) accrues to
        ``processing_ms`` → local_ms. The arrival timestamp must advance
        past that work — the reference stamps arrival when the partial
        reaches the aggregator, i.e. AFTER processQuery's flush
        (FlinkSkyline.java:524-539) — or the decomposition goes impossible
        (local > total, ingestion clamped). So the snapshot's own wall is
        added to the caller's clock before recording the arrival."""
        part = self.partitions[p]
        args = {}
        with self.tracer.span_phase(
            "flush/local", self._spans, "local", trace_id=q.trace_id, tid=p,
            args=args,
        ) as ph:
            local = part.snapshot()
            self._note_flush()
            args["rows"] = int(local.shape[0])
        arrival_ms = now_ms + ph.ms
        start = part.start_time_ms if part.start_time_ms is not None else now_ms
        q.partials[p] = local
        q.local_sizes[p] = local.shape[0]
        q.start_times[p] = start
        q.cpu_ms[p] = part.processing_ms
        q.last_arrival_ms = max(q.last_arrival_ms, arrival_ms)
        if len(q.partials) >= self.config.num_partitions:
            self._finalize(q, max(arrival_ms, q.last_arrival_ms))
        return arrival_ms

    def _finalize(
        self, q: _QueryState, now_ms: float, partial_missing: list[int] | None = None
    ) -> None:
        """All partitions reported: global merge + metrics + result emission
        (GlobalSkylineAggregator final block, FlinkSkyline.java:573-657).

        ``now_ms`` continues the caller's clock; the merge's own device time
        is added on top so global_processing_time_ms stays real even under an
        injected clock."""
        args = {}
        with self.tracer.span_phase(
            "global_merge", self._spans, "merge", trace_id=q.trace_id,
            args=args,
        ) as ph:
            pids_order = sorted(q.partials)
            stacked = [q.partials[p] for p in pids_order]
            origins = np.concatenate(
                [
                    np.full(q.partials[p].shape[0], p, dtype=np.int32)
                    for p in pids_order
                ]
            )
            union = (
                np.concatenate(stacked, axis=0)
                if origins.size
                else np.empty((0, self.config.dims), dtype=np.float32)
            )

            if self.mesh is not None:
                from skyline_tpu.parallel.mesh import skyline_keep_np_sharded

                keep = skyline_keep_np_sharded(self.mesh, union)
            else:
                keep = skyline_keep_np(union)
            global_sky = union[keep]
            survivors_per_pid = np.bincount(
                origins[keep], minlength=self.config.num_partitions
            )
            if self.freshness is not None:
                self.freshness.on_merge()
            args["union_rows"] = int(union.shape[0])
            args["skyline_size"] = int(global_sky.shape[0])
        merge_ms = ph.ms
        if self.telemetry is not None:
            self.telemetry.histogram("global_merge_ms").observe(merge_ms)
        now = now_ms + merge_ms
        job_start = min(q.start_times.values()) if q.start_times else now
        # a pure-timeout finalize may have zero arrivals; anchor to now
        # (test q.partials, not the timestamp — an injected clock at 0.0 is a
        # legitimate arrival time)
        map_finish = q.last_arrival_ms if q.partials else now
        local_ms = max(q.cpu_ms.values()) if q.cpu_ms else 0.0
        map_wall = max(0.0, map_finish - job_start)
        ingestion = max(0.0, map_wall - local_ms)
        global_ms = now - map_finish
        total_ms = now - job_start
        latency_ms = now - q.dispatch_ms

        optimality = optimality_mean(
            [survivors_per_pid[p] for p in pids_order],
            [q.local_sizes[p] for p in pids_order],
            self.config.num_partitions,
        )

        if self.snapshots is not None:
            self._publish_snapshot(global_sky, q)
        with self.tracer.phase("publish/emit"):
            self._emit_result(
                q,
                skyline_size=int(global_sky.shape[0]),
                optimality=float(optimality),
                ingestion=ingestion,
                local_ms=local_ms,
                global_ms=global_ms,
                total_ms=total_ms,
                latency_ms=latency_ms,
                points=global_sky if self.config.emit_skyline_points else None,
                partial_missing=partial_missing,
            )

    def _publish_snapshot(
        self, points, q: _QueryState, source_key=None, degraded=None
    ) -> None:
        """Publish a completed global skyline, stamped with the query's
        trace id and wrapped in a "publish" span when telemetry is on.
        ``source_key``: opaque identity of the engine state the points came
        from (the partition-epoch key) — the store dedupes repeat publishes
        of an unchanged state instead of minting a new version.
        ``degraded``: the sharded facade's partial marker — the snapshot
        carries honest incompleteness fields (``partial``,
        ``excluded_chips``, ``completeness_bound``) all the way to
        ``/skyline`` (RUNBOOK §2p). Callers pass ``source_key=None`` with
        it: a degraded snapshot must never dedupe against — or be served
        in place of — a full snapshot of the same engine state."""
        meta = {"query_id": q.qid, "source_key": source_key}
        if degraded is not None:
            meta["partial"] = True
            meta["excluded_chips"] = degraded["excluded_chips"]
            meta["completeness_bound"] = degraded["completeness_bound"]
        if q.trace_id is not None:
            meta["trace_id"] = q.trace_id
        if self.freshness is not None:
            # the merged window's newest event time becomes the snapshot's
            # published watermark (monotone; None until any event stamped)
            meta["event_wm_ms"] = self.freshness.on_publish()
        with self.tracer.span_phase(
            "publish", self._spans, "publish", trace_id=q.trace_id
        ):
            snap = self.snapshots.publish(points, **meta)
        if q.plan is not None and snap is not None:
            # a deduped publish returns the EXISTING snapshot — the plan
            # still records which version its answer's bytes live under
            q.plan.publish = {
                "version": int(snap.version),
                "deduped": bool(self.snapshots.last_publish_deduped),
                "event_wm_ms": meta.get("event_wm_ms"),
            }

    def _emit_result(
        self,
        q: _QueryState,
        *,
        skyline_size: int,
        optimality: float,
        ingestion: float,
        local_ms: float,
        global_ms: float,
        total_ms: float,
        latency_ms: float,
        points=None,
        partial_missing=None,
        degraded=None,
    ) -> None:
        result = {
            "query_id": q.qid,
            "record_count": echo_record_count(q.payload),
            "skyline_size": skyline_size,
            "optimality": optimality,
            "ingestion_time_ms": int(ingestion),
            "local_processing_time_ms": int(local_ms),
            "global_processing_time_ms": int(global_ms),
            "total_processing_time_ms": int(total_ms),
            "query_latency_ms": int(latency_ms),
        }
        if partial_missing is not None:
            result["partial"] = True
            result["missing_partitions"] = partial_missing
        if degraded is not None:
            # chip-level degradation (RUNBOOK §2p): the answer is the
            # EXACT skyline of the surviving chips' records (NOT a
            # subset of the truth — a point dominated only by
            # excluded-chip data legitimately appears), marked with who
            # is missing and how much record mass the bound guarantees
            result["partial"] = True
            result["excluded_chips"] = degraded["excluded_chips"]
            result["completeness_bound"] = degraded["completeness_bound"]
        if points is not None:
            result["skyline_points"] = (
                points.tolist() if hasattr(points, "tolist") else points
            )
        if self.telemetry is not None:
            if q.trace_id is not None:
                # optional wire extension field: format_result appends it
                # after the reference's fields, so parity consumers are
                # unaffected (bridge/wire.py)
                result["trace_id"] = q.trace_id
            # SLO denominator/numerator pair: every emitted answer counts,
            # chip-degraded ones additionally burn the degraded budget
            # (skyline_degraded_answers_total, telemetry/slo.py)
            self.telemetry.inc("queries.answered")
            if degraded is not None:
                self.telemetry.inc("degraded_answers")
                # degraded publishes are control-plane transitions: the
                # fleet's honest-availability story must survive the
                # process, so they join the durable ops journal
                ops = getattr(self.telemetry, "opslog", None)
                if ops is not None:
                    ops.record(
                        "degraded_publish",
                        trace_id=q.trace_id,
                        excluded_chips=degraded["excluded_chips"],
                        completeness_bound=degraded["completeness_bound"],
                    )
            self.telemetry.histogram("query_latency_ms").observe(latency_ms)
            if q.span_t0_ns:
                self.telemetry.spans.record(
                    "query", q.span_t0_ns, time.perf_counter_ns(),
                    trace_id=q.trace_id,
                    args={"query_id": q.qid, "skyline_size": skyline_size},
                )
        if self.workload is not None and partial_missing is None and degraded is None:
            # one trajectory point per complete answer (partials would
            # poison the dominance-rate series with truncated skylines)
            self.workload.note_query(skyline_size, self.records_in)
        if q.plan is not None:
            self._finalize_plan(
                q,
                skyline_size=skyline_size,
                local_ms=local_ms,
                global_ms=global_ms,
                total_ms=total_ms,
                latency_ms=latency_ms,
            )
        if (
            self.auditor is not None
            and partial_missing is None
            and degraded is None
            and self.snapshots is not None
        ):
            # shadow-verify AFTER the answer is out the door (plan already
            # finalized, snapshot already published); partial answers
            # intentionally exclude state, so they are never audited.
            # Observability must never take the answer down.
            try:
                self.auditor.maybe_check(q)
            except Exception:
                if self.telemetry is not None:
                    self.telemetry.inc("audit.errors")
        self._results.append(result)
        self._inflight.pop(q.payload, None)

    def _finalize_plan(
        self, q, *, skyline_size, local_ms, global_ms, total_ms, latency_ms
    ) -> None:
        """Close out a query's EXPLAIN plan: attribute the window's
        flush-cascade and kernel-dispatch deltas, stamp the timing
        decomposition, land the record in the hub ring, and nest an
        ``explain/<path>`` child span under the query span. Observability
        must never take the answer down, so the whole tail is defensive."""
        try:
            from skyline_tpu.telemetry.explain import (
                cascade_delta,
                kernel_delta,
            )

            plan = q.plan
            if plan.merge is None:
                # per-partition host merge (mesh, pending barriers,
                # timeouts): no device merge claimed the plan
                plan.merge = {"path": "host", "cached": False,
                              "skyline_size": int(skyline_size)}
            cascade_now = self.pset.flush_cascade_stats()
            plan.cascade = cascade_delta(
                self._explain_cascade_mark, cascade_now
            )
            self._explain_cascade_mark = cascade_now
            if self.profiler is not None:
                kernels_now = self.profiler.snapshot_counts()
                plan.kernels = kernel_delta(
                    self._explain_kernel_mark, kernels_now
                )
                self._explain_kernel_mark = kernels_now
            plan.timing = {
                "local_ms": round(float(local_ms), 3),
                "global_ms": round(float(global_ms), 3),
                "total_ms": round(float(total_ms), 3),
                "latency_ms": round(float(latency_ms), 3),
            }
            if self.workload is not None:
                # the regime this answer was computed under — joins the
                # drift trajectory to individual answers in /explain
                plan.workload = self.workload.regime()
            if self.tuner is not None:
                # one cadence-gated controller epoch per query window,
                # then the dispatch context this answer ran under
                self.tuner.maybe_tune()
                plan.tuner = self.tuner.explain_block()
            self.telemetry.explain.add(plan.to_doc())
            self.telemetry.inc("explain.records")
            if q.span_t0_ns:
                self.telemetry.spans.record(
                    f"explain/{plan.merge.get('path')}",
                    q.span_t0_ns,
                    time.perf_counter_ns(),
                    trace_id=q.trace_id,
                    tid=3,
                    args={
                        "path": plan.merge.get("path"),
                        "pruned": (plan.tree or {}).get(
                            "partitions_pruned", 0
                        ),
                        "kernels": len(plan.kernels),
                        "version": (plan.publish or {}).get("version"),
                    },
                )
        except Exception:
            pass

    def _answer_all_device(self, q: _QueryState, now_ms: float) -> None:
        """All barriers passed at dispatch: answer every partition and run
        the global merge on device. Equivalent to _answer x P followed by
        _finalize, but local skylines never leave the device — only the
        packed (counts, survivors, global_count) stats vector (plus the
        compacted points buffer when requested) transfers.

        Timing decomposition follows the same clock discipline as
        _answer/_finalize: the flush wall advances the arrival clock (local
        phase); the merge wall rides on top (global phase)."""
        # one stacked launch covers every partition's local skyline
        with self.tracer.span_phase(
            "flush/local", self._spans, "local", trace_id=q.trace_id,
            args={"partitions": self.config.num_partitions},
        ) as ph:
            self.pset.flush_all()
            self._note_flush()
        flush_wall_ms = ph.ms
        # an attached snapshot store needs the materialized points even when
        # the result JSON omits them — the snapshot IS the serving read path
        want_points = (
            self.config.emit_skyline_points or self.snapshots is not None
        )
        if query_overlap_enabled() and self.mesh is None:
            # overlapped sync: launch every merge kernel now, keep the
            # handle, and return — ingest continues while the device works.
            # The result emits at the next harvest point (poll_results /
            # next trigger / stats / timeout check, or opportunistically in
            # process_records once the stats bytes land), where the phase
            # records only the residual harvest time instead of the full
            # merge wall.
            with self.tracer.span_phase(
                "global_merge/launch", None, None
            ) as ph:
                handle = self.pset.global_merge_launch(emit_points=want_points)
            self._inflight_merge = (q, handle, now_ms, flush_wall_ms, ph.ms)
            return
        args = {}
        with self.tracer.span_phase(
            "global_merge", self._spans, "merge", trace_id=q.trace_id,
            args=args,
        ) as ph:
            counts, surv, g, pts = self.pset.global_merge_stats(
                emit_points=want_points
            )
            if self.freshness is not None:
                self.freshness.on_merge()
            args["skyline_size"] = int(g)
        merge_ms = ph.ms
        if self.telemetry is not None:
            self.telemetry.histogram("global_merge_ms").observe(merge_ms)
        self._emit_device_result(
            q, now_ms, flush_wall_ms, merge_ms, counts, surv, g, pts,
            source_key=self.pset.epoch_key,
        )

    def _harvest_inflight(self, block: bool = True) -> bool:
        """Land the overlapped merge, if one is in flight. ``block=False``
        harvests only when the stats transfer already completed (an
        effectively-free sync) — the ingest path uses it so a still-running
        merge never stalls new data. Returns True when a result emitted."""
        if self._inflight_merge is None:
            return False
        q, handle, now_ms, flush_wall_ms, launch_ms = self._inflight_merge
        if not block and not handle.ready():
            return False
        self._inflight_merge = None
        args = {}
        with self.tracer.span_phase(
            "global_merge/harvest", self._spans, "merge", trace_id=q.trace_id,
            args=args,
        ) as ph:
            counts, surv, g, pts = self.pset.global_merge_harvest(handle)
            if self.freshness is not None:
                self.freshness.on_merge()
            args["skyline_size"] = int(g)
            args["overlapped"] = True
        # the query's merge cost = launch dispatch + harvest sync; the
        # in-flight span in between ran under ingest, so charging it here
        # would double-count the overlap the split exists to buy
        merge_ms = launch_ms + ph.ms
        if self.telemetry is not None:
            self.telemetry.histogram("global_merge_ms").observe(merge_ms)
        self._emit_device_result(
            q, now_ms, flush_wall_ms, merge_ms, counts, surv, g, pts,
            source_key=handle.key,
        )
        return True

    def _emit_device_result(
        self, q, now_ms, flush_wall_ms, merge_ms, counts, surv, g, pts,
        source_key,
    ) -> None:
        """Shared tail of the device answer paths (blocking + overlapped):
        snapshot publish, timing decomposition, result emission."""
        # chip-level degradation marker from the sharded facade's harvest
        # (None on flat engines and on full sharded answers)
        degraded = getattr(self.pset, "last_partial", None)
        if self.snapshots is not None:
            # the epoch key identifies the flushed state the merge saw, so
            # repeated triggers over unchanged state dedupe in the store
            # (the host _finalize path publishes un-keyed: its unions mix
            # per-partition arrival times, so no single key describes
            # them). A DEGRADED answer publishes un-keyed too: it must
            # never dedupe against — nor be deduped by — a full snapshot
            # of the same epoch.
            if degraded is not None:
                self._publish_snapshot(pts, q, source_key=None,
                                       degraded=degraded)
            else:
                self._publish_snapshot(pts, q, source_key=source_key)

        starts = [s for s in self.pset.start_time_ms if s is not None]
        map_finish = now_ms + flush_wall_ms
        now = map_finish + merge_ms
        job_start = min(starts) if starts else now
        local_ms = self.pset.processing_ms
        map_wall = max(0.0, map_finish - job_start)
        with self.tracer.phase("publish/emit"):
            self._emit_result(
                q,
                skyline_size=g,
                optimality=optimality_mean(
                    surv, counts, self.config.num_partitions
                ),
                ingestion=max(0.0, map_wall - local_ms),
                local_ms=local_ms,
                global_ms=now - map_finish,
                total_ms=now - job_start,
                latency_ms=now - q.dispatch_ms,
                points=pts if self.config.emit_skyline_points else None,
                degraded=degraded,
            )

    # -- failure detection -------------------------------------------------

    def check_timeouts(self, now_ms: float | None = None) -> int:
        """Finalize overdue queries as partial results (the watchdog the
        reference lacks). A timed-out query emits with ``"partial": true``
        and ``"missing_partitions"`` listing the non-reporting partitions;
        its pending barrier entries are withdrawn. Returns the number of
        queries timed out."""
        timeout = self.config.query_timeout_ms
        if timeout <= 0:
            return 0
        # an overlapped merge's query is still in _inflight; land it before
        # the scan so the watchdog can't double-finalize it as partial
        self._harvest_inflight()
        if not self._inflight:
            return 0
        if now_ms is None:
            now_ms = time.time() * 1000.0
        overdue = [
            q for q in self._inflight.values() if now_ms - q.dispatch_ms > timeout
        ]
        for q in overdue:
            missing = [
                p
                for p in range(self.config.num_partitions)
                if p not in q.partials
            ]
            for p in missing:
                self._pending_queries[p] = [
                    pq for pq in self._pending_queries[p] if pq is not q
                ]
            self._finalize(q, now_ms, partial_missing=missing)
        return len(overdue)

    # -- results ----------------------------------------------------------

    def poll_results(self) -> list[dict]:
        self._harvest_inflight()
        out, self._results = self._results, []
        return out

    @property
    def inflight_queries(self) -> int:
        return len(self._inflight)

    # -- observability ----------------------------------------------------

    def stats(self, include_skyline_counts: bool = False) -> dict:
        """Live engine counters — the role the Flink Web UI plays for the
        reference (SURVEY.md §5, docker-compose.yml:26), as a poll-able dict.

        ``include_skyline_counts=True`` adds exact per-partition skyline
        sizes at the cost of one device sync; leave False on hot paths.
        """
        if self.pset.has_unsynced_ingest:
            self.pset.sync_ingest_bookkeeping()
        # counters below must describe a settled state, not a merge mid-air
        self._harvest_inflight()
        tree_info = self.pset.last_tree_info or {}
        out = {
            "records_in": self.records_in,
            "dropped": self.dropped,
            "prefiltered": self.prefiltered,
            "inflight_queries": len(self._inflight),
            "pending_flush_rows": self.pset.pending_rows_total,
            "processing_ms": self.pset.processing_ms,
            "partitions": {
                "records_seen": self.pset.records_seen.tolist(),
                "max_seen_id": self.pset.max_seen_id.tolist(),
            },
            "meshed": self.mesh is not None,
            "merge_cache": {
                "hits": self.pset.merge_cache_hits,
                "misses": self.pset.merge_cache_misses,
                "delta_merges": self.pset.merge_delta_merges,
                "delta_rows": self.pset.merge_delta_rows,
                "last_dirty_fraction": self.pset.last_dirty_fraction,
            },
            "merge_tree": {
                "merges": self.pset.merge_tree_merges,
                "levels": tree_info.get("levels", 0),
                "partitions_pruned": self.pset.merge_partitions_pruned,
                "pruned_fraction": tree_info.get("pruned_fraction", 0.0),
                "candidates_per_level": tree_info.get(
                    "candidates_per_level", []
                ),
            },
            "flush_cascade": self.pset.flush_cascade_stats(),
        }
        if self._explain_on:
            out["explain"] = self.telemetry.explain.doc()
        if self.auditor is not None:
            out["audit"] = self.telemetry.audit.doc()
        if self.freshness is not None:
            out["freshness"] = self.freshness.stats()
        if self.workload is not None:
            out["workload"] = self.workload.stats()
        if self.profiler is not None:
            phase = self.tracer.report().get("flush/merge_kernel")
            out["kernel_profile"] = self.profiler.doc(
                phase_total_ms=phase["total_ms"] if phase else None
            )
        if include_skyline_counts:
            out["partitions"]["skyline_counts"] = (
                self.pset.sky_counts().tolist()
            )
        return out
