"""Batched partition-set state: all logical partitions' skylines in one
stacked device buffer, merged in one launch.

A per-partition state model dispatches 3 dominance kernels + a compact per
partition per flush — ~P*4 launches per micro-batch, each paying the
host's dispatch latency. ``PartitionSet`` keeps the SAME semantics (per-partition
incremental skylines, barriers, timing — SkylineLocalProcessor's state model,
FlinkSkyline.java:214-445) but stores all P partitions as ``(P, cap, d)`` /
``(P, cap)`` stacked buffers and merges every partition's pending rows in ONE
vmapped kernel launch per flush.

Semantic deltas vs per-partition flushing, both documented here on purpose:

- flush granularity: a flush happens when the LARGEST partition's pending
  rows reach ``buffer_size`` (or on demand), and it flushes ALL partitions'
  pending rows at once. Results are identical — the incremental merge is
  order- and batching-invariant (the merge law, SURVEY.md §4) — only the
  points at which device work happens differ.
- per-partition CPU attribution: flush wall time is accounted to the set,
  and every partition reports the same ``processing_ms`` (the set total).
  The reference's per-query ``local_processing_time_ms`` is the MAX over
  partitions (FlinkSkyline.java:579-588), which under shared attribution is
  exactly the set total — the number the dashboard stacks local bars from.
"""

from __future__ import annotations

import time
from contextlib import nullcontext

import jax.numpy as jnp
import numpy as np

from skyline_tpu.metrics.tracing import CAPTURED, NULL_TRACER, capturing
from skyline_tpu.resilience.faults import fault_point
from skyline_tpu.ops import cascade
from skyline_tpu.ops.dispatch import (
    flush_stage_depth,
    mixed_precision_enabled,
    on_tpu,
    profile_cost_enabled,
)
from skyline_tpu.stream.window import (
    DEFAULT_BUFFER_SIZE,
    GRID_BINS,
    _MIN_CAP,
    _active_bucket,
    _next_pow2,
    extract_cached_leaf,
    extract_sky_leaf,
    global_merge_delta_device,
    global_merge_stats_device,
    global_points_device,
    grid_summary_device,
    merge_step_active,
    meshed_merge_step,
    meshed_sfs_cleanup,
    meshed_sfs_round,
    partition_summaries_device,
    prune_witness_mask,
    sfs_cleanup,
    sfs_round,
    sfs_round_single,
    tree_pair_merge,
    tree_points_device,
    tree_stats_device,
)


from skyline_tpu.stream import device_window as dw

# Sequential-SFS probe block: rounds start at this size so a small-skyline
# partition never pays big-block dominance work; the loop escalates to the
# row-scaled block once a round's surviving count exceeds half a block
# (a probe round keeps at most B survivors, so half-a-block survival is
# strong evidence of a large skyline).
_PROBE_B = 8192

# Device-ingest chunks are split/padded to power-of-two buckets capped here,
# bounding the set of ingest executables.
_CHUNK_BUCKET_MAX = 65536

# Host chunk for the grid prefilter: the broadcast path's (chunk,
# GRID_REPS, d) comparison stays ~10 MB at 10D instead of scaling with the
# whole pending window.
_PREFILTER_CHUNK = 16384

# Packed-field grid certificate: a row's codes ``vy + 64`` and a
# representative's codes ``u + 1`` sit in 7-bit fields of one int64, so
# each field of the difference is ``vy - u + 63`` in [30, 95] (codes lie in
# [-1, GRID_BINS] and [0, GRID_BINS]): no field borrows from the next, and
# its bit 6 is set iff ``u < vy``. Nine fields fill 63 bits.
_FIELD_BITS = 7
_PACKED_MAX_DIMS = 63 // _FIELD_BITS


def _field_shifts(d: int) -> np.ndarray:
    return np.arange(d, dtype=np.int64) * _FIELD_BITS


def _grid_codes(b: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """(m, d) int64 grid codes of ``rows`` against a (GRID_BINS+1, d)
    ladder: the largest index with ``b[vy] <= y``, -1 below the ladder
    and for NaN. On a strictly increasing column the right-side binary
    search gives exactly ``#(b <= y) - 1``; on a column validation
    disabled it still lands in [-1, GRID_BINS], where it never meets a
    certifying representative (those are coded GRID_BINS+1 there)."""
    c = np.asarray(rows, np.float32)
    vy = np.empty(c.shape, np.int64)
    for j in range(c.shape[1]):
        vy[:, j] = np.searchsorted(b[:, j], c[:, j], side="right")
    vy -= 1
    vy[np.isnan(c)] = -1  # searchsorted orders NaN above every bound
    return vy


def _grid_certifiers(u: np.ndarray, packed: bool) -> np.ndarray:
    """The representatives of one partition that can certify a drop: every
    code <= GRID_BINS, duplicates and covered codes removed (a code vector
    at or above another in every dim certifies only rows the other already
    does, so the drop mask is unchanged). ``packed`` returns them as
    ``_packed_drop`` operands, (K,) int64; else as (K, d) codes."""
    u = np.unique(u[(u <= GRID_BINS).all(axis=1)], axis=0)
    covered = (u[None, :, :] <= u[:, None, :]).all(axis=2).sum(axis=1) > 1
    u = u[~covered]
    if not packed:
        return u
    return ((u.astype(np.int64) + 1) << _field_shifts(u.shape[1])).sum(axis=1)


def _packed_drop(vy: np.ndarray, U: np.ndarray) -> np.ndarray:
    """Rows whose (m, d) codes ``vy`` lie strictly above some packed
    representative in ``U`` in every dim (see ``_FIELD_BITS``)."""
    shifts = _field_shifts(vy.shape[1])
    W = ((vy + 64) << shifts).sum(axis=1)
    H = int((np.int64(64) << shifts).sum())
    drop = np.zeros(W.shape[0], dtype=bool)
    for Uk in U:
        drop |= ((W - Uk) & H) == H
    return drop


class _MergeHandle:
    """An in-flight global merge: every kernel launched, nothing synced.

    Produced by ``PartitionSet.global_merge_launch``; consumed (once) by
    ``PartitionSet.global_merge_harvest``. Between the two the caller is free
    to keep ingesting — the handle pins the launch-time epoch vector, so
    harvest-time bookkeeping knows whether the set moved underneath it.
    """

    __slots__ = (
        "key",
        "epoch",
        "emit_points",
        "use_cache",
        "cached",
        "result",
        "stats",
        "union",
        "keep",
        "root_vals",
        "dirty",
        "clean_total",
        "explain",
    )

    def __init__(self):
        self.cached = False
        self.result = None
        self.stats = None
        self.union = None
        self.keep = None
        self.root_vals = None
        self.dirty = None
        self.clean_total = 0
        # the EXPLAIN QueryPlan riding this merge (telemetry/explain.py);
        # annotated host-side at launch/tree/harvest, None when the plane
        # is off — the handle carries it so overlapped merges attribute to
        # the query that launched them, not whatever is current at harvest
        self.explain = None

    def ready(self) -> bool:
        """True once harvest would not block (best-effort: backends without
        ``is_ready`` report False, so callers fall back to a later blocking
        harvest rather than an early one)."""
        if self.cached:
            return True
        try:
            return bool(self.stats.is_ready())
        except AttributeError:
            return False


class PartitionSet:
    """Device-stacked state for ``num_partitions`` logical partitions.

    With a ``mesh``, the stacked partition axis is sharded across the mesh
    devices (``num_partitions`` divisible by mesh size — the reference's
    ``2×parallelism`` logical keys round-robined onto ``parallelism``
    workers, FlinkSkyline.java:74-76, with workers = chips). The batched
    merge has no cross-partition data flow, so each flush runs fully SPMD:
    one launch, every chip merging its resident partitions over ICI-free
    local compute. Without a mesh, the same code runs single-device.
    """

    def __init__(
        self,
        num_partitions: int,
        dims: int,
        buffer_size: int = DEFAULT_BUFFER_SIZE,
        mesh=None,
        initial_capacity: int = 0,
        tracer=None,
        flush_policy: str = "incremental",
        route: tuple[str, float] | None = None,
        overlap_rows: int = 262144,
        window_capacity: int = 0,
        counters=None,
    ):
        """``initial_capacity``: pre-size the per-partition skyline buffers
        (rounded up to the power-of-two bucket). Capacity normally grows on
        demand with one count sync per doubling; a workload that knows its
        steady-state skyline size (e.g. repeated same-shape windows) can
        pre-size to skip every growth step and its sync.

        ``flush_policy``:

        - ``"incremental"`` (default): merge pending rows into the running
          skylines whenever the largest partition's pending buffer reaches
          ``buffer_size`` — the reference's processBuffer cadence
          (FlinkSkyline.java:232). Work is spread across ingest; memory for
          pending rows is bounded by the threshold.
        - ``"lazy"``: accumulate pending rows (host RAM ~ window size) and
          compute at query time via sum-sorted append-only SFS rounds — no
          buffer re-pruning, no full-buffer compaction. For
          tumbling-window-then-query streams this does a fraction of the
          incremental policy's dominance work (see stream/window.py SFS
          notes). Results are identical (the merge law). Under a ``mesh``
          the rounds run SPMD via ``shard_map`` over the partition axis
          (one launch, each chip appending to its resident partitions; the
          skew-sequential path and the device-side global merge are
          single-device specializations, so the meshed flush always uses
          the vmapped rounds and the engine's host-side global merge).
        - ``"overlap"``: the lazy machinery with automatic chunked flushes
          every ``overlap_rows`` accumulated rows, so the append rounds of
          an earlier chunk run on device WHILE the host parses / uploads
          the next one (JAX async dispatch). A mid-window flush on
          non-empty state pays the old-vs-new SFS cleanup pass per chunk —
          a fraction of the append work — in exchange for hiding device
          time behind the transport-bound ingest (the concurrent
          source/operator dataflow Flink gets by construction,
          FlinkSkyline.java:84-104). Results identical (merge law).

        ``route``: ``(algo, domain_max)`` enables DEVICE ingest for the
        lazy/overlap policies: raw chunks are uploaded as they arrive and
        partition routing, the flush-time (pid, sum) sort, and SFS block
        slicing all run on device (see stream/device_window.py). ``None``
        keeps the host routing path (the engine routes and calls
        ``add_batch``). Single-device only.

        ``counters``: optional ``metrics.collector.Counters``-like sink
        (``inc(name, n)``) mirroring the merge-cache counters into the
        telemetry plane (``merge.cache_hit`` / ``merge.cache_miss`` /
        ``merge.delta_merge`` / ``merge.delta_rows`` → Prometheus
        ``skyline_merge_*_total`` on GET /metrics).
        """
        self.num_partitions = num_partitions
        self.dims = dims
        self.buffer_size = buffer_size
        self.initial_capacity = initial_capacity
        self.overlap_rows = overlap_rows
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if flush_policy not in ("incremental", "lazy", "overlap"):
            raise ValueError(f"unknown flush_policy {flush_policy!r}")
        if route is not None and (
            mesh is not None or flush_policy == "incremental"
        ):
            raise ValueError(
                "device ingest (route=...) requires a single-device "
                "lazy/overlap PartitionSet"
            )
        self.flush_policy = flush_policy
        self._route = route
        self.window_capacity = window_capacity
        # device-ingest accumulation state (route is not None):
        self._dev_window = None  # (dev_cap, d) +inf-padded row buffer
        self._dev_pids = None  # (dev_cap,) int32, sentinel num_partitions
        self._dev_cap = 0
        self._dev_rows = 0  # valid rows currently accumulated
        # per-chunk (stats_dev (2, P), now_ms) awaiting a host bookkeeping
        # sync (lazy: only a query barrier or a flush needs them)
        self._chunk_stats: list[tuple] = []
        self.mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # shard over the mesh's FIRST axis (multi-axis meshes keep the
            # remaining axes replicated), so divisibility is against that
            # axis's extent, not the total device count
            axis = mesh.axis_names[0]
            n_axis = int(mesh.shape[axis])
            if num_partitions % n_axis:
                raise ValueError(
                    f"num_partitions {num_partitions} must be divisible by "
                    f"mesh axis {axis!r} size {n_axis}"
                )
            self._sharding = NamedSharding(mesh, PartitionSpec(axis))
        else:
            self._sharding = None
        p = num_partitions
        # pending micro-batch rows awaiting a flush, per partition
        self._pending: list[list[np.ndarray]] = [[] for _ in range(p)]
        self._pending_rows = np.zeros(p, dtype=np.int64)
        # stacked running skylines: (P, cap, d) values + (P, cap) validity
        self._cap = _next_pow2(max(initial_capacity, _MIN_CAP))
        self.sky = self._put(
            np.full((p, self._cap, dims), np.inf, dtype=np.float32)
        )
        self.sky_valid = self._put(np.zeros((p, self._cap), dtype=bool))
        # survivor counts: device vector (exact, read lazily) + host upper
        # bounds (drive capacity growth WITHOUT per-flush syncs)
        self._count_dev = self._put(np.zeros((p,), dtype=np.int32))
        self._count_ub = np.zeros(p, dtype=np.int64)
        # barrier + metrics bookkeeping (FlinkSkyline.java:243-248, 267)
        self.max_seen_id = np.full(p, -1, dtype=np.int64)
        self.start_time_ms: list[float | None] = [None] * p
        self.records_seen = np.zeros(p, dtype=np.int64)
        self.processing_ns: int = 0  # set-wide (see module docstring)
        # host-side caches of device state, invalidated by flush/restore:
        # repeated per-partition snapshots (e.g. a trigger answering all P
        # partitions) then cost ONE count sync + ONE buffer transfer total
        self._counts_cache: np.ndarray | None = None
        self._host_cache: np.ndarray | None = None
        # flushed-state versioning: a monotone per-partition epoch, bumped
        # by every flush path that merges rows into that partition (and by
        # restore). The epoch vector is the identity of the device state —
        # the global-merge cache keys on it, and the serving plane dedupes
        # snapshot publishes against it (epoch_key).
        self._epoch = np.zeros(p, dtype=np.int64)
        # epoch-keyed global-merge result cache (see global_merge_stats):
        # {key, epoch, counts, surv, g, pts_dev, pts_host}
        self._gm_cache: dict | None = None
        self._counters = counters
        # kernel profiler + decision flight recorder (telemetry/profiler.py),
        # attached by the engine when observability is enabled; None keeps
        # every dispatch site on the bare tracer-phase path
        self._profiler = None
        self._flight = None
        # one-shot EXPLAIN plan sink: the engine parks the current query's
        # QueryPlan here before launching its merge; global_merge_launch
        # claims it onto the handle (and clears it) so annotation follows
        # the merge, not the PartitionSet
        self._explain = None
        # flush-path chooser profiler: whole-flush wall per variant
        # (flush_sorted_sfs vs flush_sfs_sequential/vmapped) under the
        # (d, N, backend) signature — kept SEPARATE from self._profiler,
        # whose per-round records these flush-level aggregates would
        # double-count. Lazily created: the TPU/mesh paths never pay it.
        self._flush_prof = None
        self.merge_cache_hits = 0
        self.merge_cache_misses = 0
        self.merge_delta_merges = 0
        self.merge_delta_rows = 0
        self.last_dirty_fraction: float | None = None
        # per-partition prune summaries for the tournament-tree merge:
        # (P, 2d+2) device array [min_corner | witness | min_sum | max_sum],
        # launched async at flush tails and stamped with the epoch vector it
        # describes (a stale stamp means a tiny re-launch at merge time)
        self._summary_dev = None
        self._summary_epoch: np.ndarray | None = None
        self.merge_tree_merges = 0
        self.merge_partitions_pruned = 0
        self.last_tree_info: dict | None = None
        # witness_of vector from the last prune pass (window.py
        # prune_witness_mask) — the per-partition prune REASONS the
        # EXPLAIN plane folds into a QueryPlan's tree block
        self.last_prune_witness: np.ndarray | None = None
        # quantized-grid flush prefilter (ISSUE 5 stage 1): the device
        # handle pair (bounds, rep cell codes) launched async at flush
        # tails; the validated host copy is harvested lazily at the next
        # flush. Stale summaries are sound (a removed skyline row always
        # leaves a transitive strict dominator behind — _prefilter_rows),
        # but restore replaces the world and must invalidate.
        self._grid_dev = None
        self._grid_host = None
        self._grid_epoch: np.ndarray | None = None
        self.prefilter_dropped = 0
        self.prefilter_seen = 0
        self.prefilter_packed_seen = 0
        # mixed-precision stage 2: running device scalar of bf16-resolved
        # pair counts (one tiny add per flush round, synced only on the
        # stats path) + the high-water mark already fed to the telemetry
        # counters (flush_cascade_stats delta-feeds them)
        self._mp_resolved_dev = None
        # incremental merge tile pairs whose kernel body ran, summed on the
        # device while a profiler capture is on; read at the next global
        # merge harvest (a sync the answer already makes); the SFS rounds'
        # twin beside it
        self._tiles_run_dev = None
        self._sfs_tiles_run_dev = None
        self._bf16_resolved_reported = 0
        self.bf16_resolved = 0
        # a deferred (async-started) count-bound tighten from the last lazy
        # flush, consumed by the next sky_counts()/global merge
        self._tighten_pending = False

    def _put(self, arr: np.ndarray):
        """Place a (P, ...) array on device, partition-sharded if meshed."""
        if self._sharding is not None:
            import jax

            return jax.device_put(arr, self._sharding)
        return jnp.asarray(arr)

    # -- state versioning --------------------------------------------------

    @property
    def epoch(self) -> np.ndarray:
        """Per-partition flush epochs (monotone; read-only view)."""
        return self._epoch

    @property
    def epoch_key(self) -> bytes:
        """Opaque identity of the flushed device state: equal keys mean no
        flush touched any partition in between. The merge cache keys on it
        and the serving plane uses it as the snapshot-dedupe source key."""
        return self._epoch.tobytes()

    def _bump_epoch(self, which) -> None:
        """Advance the epoch of every partition in ``which`` (index list or
        boolean mask) — called by each flush path for the partitions whose
        merged state is about to change."""
        self._epoch[which] += 1

    def _inc(self, name: str, n: int = 1) -> None:
        if self._counters is not None:
            self._counters.inc(name, n)

    # -- observability hooks ------------------------------------------------

    def attach_observability(self, profiler=None, flight=None) -> None:
        """Attach a ``telemetry.profiler.KernelProfiler`` and/or
        ``FlightRecorder``. The profiler sub-attributes every
        ``flush/merge_kernel`` tracer phase to its dispatch signature
        (variant, d, N-bucket, backend, mp — see stream/window.py
        ``KERNEL_VARIANTS``); the flight recorder keeps the last N
        dispatch/cascade/prune/cache decisions. Both are host-side wrappers
        around already-timed regions — skyline bytes are unchanged."""
        self._profiler = profiler
        self._flight = flight
        if profiler is not None:
            # share with the dispatch-level chooser so host-path mask
            # dispatches (sorted_sfs_mask vs mask_scan) land in /profile
            # and the EXPLAIN kernel deltas too
            from skyline_tpu.ops.dispatch import register_profiler

            register_profiler(profiler)

    def set_explain(self, plan) -> None:
        """Park the current query's ``QueryPlan`` for the next
        ``global_merge_launch`` to claim (None clears). Host-side
        annotation only — nothing the plan records enters a kernel."""
        self._explain = plan

    def _kernel(self, variant: str, n: int, mp: bool = False, cost_thunk=None):
        """Profiling context for one merge-kernel dispatch (nullcontext
        when no profiler is attached)."""
        if self._profiler is None:
            return nullcontext()
        return self._profiler.record(
            variant, self.dims, n, mp=mp, cost_thunk=cost_thunk
        )

    def _merge_cost_thunk(self, batch_dev, bvalid_dev, active, out_active, mp):
        """AOT ``cost_analysis()`` thunk for the incremental merge step's
        current dispatch signature (``SKYLINE_PROFILE_COST``). Shapes are
        captured eagerly — the live buffers are donated by the dispatch —
        and the lower+compile runs only once per signature, inside the
        profiler's first-call path."""
        import jax

        shapes = tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in (self.sky, self.sky_valid, batch_dev, bvalid_dev)
        )

        def thunk():
            return (
                merge_step_active.lower(*shapes, active, out_active, mp)
                .compile()
                .cost_analysis()
            )

        return thunk

    def _fnote(self, kind: str, **fields) -> None:
        if self._flight is not None:
            self._flight.note(kind, **fields)

    # -- ingest -----------------------------------------------------------

    def add_batch(
        self, p: int, values: np.ndarray, max_id: int, now_ms: float
    ) -> None:
        """Buffer a routed micro-batch for partition ``p``; the caller
        decides when to ``flush_all`` (usually via ``maybe_flush``)."""
        n = values.shape[0]
        if n == 0:
            return
        if self.start_time_ms[p] is None:
            self.start_time_ms[p] = now_ms
        self.max_seen_id[p] = max(self.max_seen_id[p], int(max_id))
        self.records_seen[p] += n
        self._pending[p].append(values)
        self._pending_rows[p] += n

    @property
    def device_ingest(self) -> bool:
        return self._route is not None

    @property
    def has_unsynced_ingest(self) -> bool:
        return bool(self._chunk_stats)

    @property
    def pending_rows_total(self) -> int:
        """Un-flushed rows across both ingest paths (host pending lists +
        the device accumulation window)."""
        return int(self._pending_rows.sum()) + self._dev_rows

    def ingest_chunk(self, ids, values, now_ms: float) -> None:
        """Device-ingest twin of route-then-``add_batch``: upload one raw
        micro-batch, compute its partition ids and per-partition barrier
        stats on device (stream/device_window.py), and append it to the
        accumulated window. Host-side barrier/metrics bookkeeping is synced
        lazily (``sync_ingest_bookkeeping``) — the hot no-pending-queries
        path never waits on the device."""
        n = values.shape[0]
        if n == 0:
            return
        algo, domain_max = self._route
        if int(ids.max()) >= 2**31:
            raise ValueError(
                "device ingest tracks record ids as int32; ids >= 2^31 "
                "need the host ingest path"
            )
        for s in range(0, n, _CHUNK_BUCKET_MAX):
            chunk = np.asarray(values[s : s + _CHUNK_BUCKET_MAX], np.float32)
            cids = ids[s : s + _CHUNK_BUCKET_MAX]
            m = chunk.shape[0]
            bucket = _next_pow2(m)
            vp = np.full((bucket, self.dims), np.inf, dtype=np.float32)
            vp[:m] = chunk
            ip = np.full((bucket,), -1, dtype=np.int32)
            ip[:m] = cids
            self._ensure_dev_capacity(self._dev_rows + bucket)
            self._dev_window, self._dev_pids, stats = dw.ingest_chunk(
                self._dev_window,
                self._dev_pids,
                jnp.asarray(vp),
                jnp.asarray(ip),
                m,
                self._dev_rows,
                algo=algo,
                num_partitions=self.num_partitions,
                domain_max=domain_max,
            )
            self._dev_rows += m
            self._chunk_stats.append((stats, now_ms))

    def _ensure_dev_capacity(self, need: int) -> None:
        """Allocate or double the device accumulation buffers. The write
        offset is row-granular while ``need`` includes the incoming chunk's
        padded bucket, so the dynamic_update_slice never clamps."""
        if self._dev_window is None:
            # window_capacity hint: pre-size so a full expected window
            # (plus the final chunk's padded bucket) never reallocates
            hint = (
                _next_pow2(self.window_capacity + _CHUNK_BUCKET_MAX)
                if self.window_capacity
                else 0
            )
            cap = max(_next_pow2(need), hint, 131072)
            self._dev_window = jnp.full(
                (cap, self.dims), jnp.inf, dtype=jnp.float32
            )
            self._dev_pids = jnp.full(
                (cap,), self.num_partitions, dtype=jnp.int32
            )
            self._dev_cap = cap
            return
        while self._dev_cap < need:
            new_cap = self._dev_cap * 2
            self._dev_window = jnp.concatenate(
                [
                    self._dev_window,
                    jnp.full(
                        (new_cap - self._dev_cap, self.dims),
                        jnp.inf,
                        dtype=jnp.float32,
                    ),
                ],
                axis=0,
            )
            self._dev_pids = jnp.concatenate(
                [
                    self._dev_pids,
                    jnp.full(
                        (new_cap - self._dev_cap,),
                        self.num_partitions,
                        dtype=jnp.int32,
                    ),
                ]
            )
            self._dev_cap = new_cap

    def sync_ingest_bookkeeping(self) -> None:
        """Fold queued per-chunk device stats into the host barrier/metrics
        state (max_seen_id, records_seen, start_time_ms). One small
        transfer per queued chunk; called before any barrier check or
        flush, never on the pure-ingest hot path."""
        if not self._chunk_stats:
            return
        with self.tracer.phase("ingest/bookkeeping_sync"):
            for stats_dev, now_ms in self._chunk_stats:
                s = np.asarray(stats_dev, dtype=np.int64)
                counts, maxids = s[0], s[1]
                got = counts > 0
                self.records_seen[got] += counts[got]
                np.maximum(
                    self.max_seen_id,
                    np.where(got, maxids, -1),
                    out=self.max_seen_id,
                )
                for p in np.nonzero(got)[0]:
                    if self.start_time_ms[p] is None:
                        self.start_time_ms[p] = now_ms
        self._chunk_stats = []

    def maybe_flush(self) -> bool:
        """Flush all partitions once the largest pending buffer reaches
        ``buffer_size`` (the processBuffer threshold, FlinkSkyline.java:232,
        applied set-wide). Returns True if a flush happened. Under the lazy
        policy this never fires — all work happens at query time. Under the
        overlap policy it fires whenever ``overlap_rows`` rows have
        accumulated across both ingest paths."""
        if self.flush_policy == "lazy":
            return False
        if self.flush_policy == "overlap":
            if self.pending_rows_total >= self.overlap_rows:
                with self.tracer.phase("flush/auto"):
                    self.flush_all(tighten=False)
                return True
            return False
        if int(self._pending_rows.max()) >= self.buffer_size:
            with self.tracer.phase("flush/auto"):
                self.flush_all()
            return True
        return False


    def _drain_pending(self) -> list[np.ndarray]:
        """Move every partition's pending micro-batches out as one (m, d)
        array per partition (empty partitions get (0, d)), clearing the
        pending state. Shared by both flush policies."""
        rows = [
            (
                self._pending[p][0]
                if len(self._pending[p]) == 1
                else np.concatenate(self._pending[p], axis=0)
            )
            if self._pending[p]
            else np.empty((0, self.dims), dtype=np.float32)
            for p in range(self.num_partitions)
        ]
        self._pending = [[] for _ in range(self.num_partitions)]
        self._pending_rows[:] = 0
        return rows

    def _pad_block(self, part_rows: np.ndarray, B: int):
        """Pad one partition's (w, d) rows to a (B, d) +inf block +
        validity mask — the single padding convention both SFS paths and
        the batched assembly share."""
        w = part_rows.shape[0]
        block = np.full((B, self.dims), np.inf, dtype=np.float32)
        block[:w] = part_rows
        return block, np.arange(B) < w, w

    def _round_batch(self, rows: list[np.ndarray], rnd: int, B: int):
        """Assemble round ``rnd``'s (P, B, d) padded batch + validity +
        per-partition widths from the drained ``rows``."""
        batch = np.full(
            (self.num_partitions, B, self.dims), np.inf, dtype=np.float32
        )
        bvalid = np.zeros((self.num_partitions, B), dtype=bool)
        widths = np.zeros(self.num_partitions, dtype=np.int64)
        for p, r in enumerate(rows):
            part_rows = r[rnd * B : (rnd + 1) * B]
            w = part_rows.shape[0]
            if w:
                batch[p], bvalid[p], widths[p] = self._pad_block(part_rows, B)
        return batch, bvalid, widths

    def flush_all(self, tighten: bool = True) -> None:
        """Merge every partition's pending rows into its running skyline:
        one batched device launch per round (incremental policy), or
        append-only SFS rounds over the sum-sorted pending windows
        (lazy/overlap policies — host pending lists first, then the device
        accumulation window; a restored checkpoint can leave host pendings
        on a device-ingest set).

        ``tighten=False`` (overlap auto-flushes) runs the device flush
        SYNC-FREE: had-old detection and buckets come from the host upper
        bounds, the cleanup's exact old counts stay a device array, and the
        trailing bound-tightening sync is skipped — the host never blocks
        on the device mid-stream, which is the point of the overlap policy.
        Query-time flushes keep the default (exact buckets for the global
        merge)."""
        fault_point("flush.pre_merge")
        total = int(self._pending_rows.sum())
        if self.dims <= 2 and self.mesh is None:
            # d <= 2: the whole flush (host pendings + device window + old
            # skylines, every policy) collapses to one sort-and-sweep pass —
            # no SFS rounds, no pairwise work (ops/sweep2d.py)
            if total or self._dev_rows:
                self._flush_sweep()
            return
        if self.flush_policy in ("lazy", "overlap"):
            if total:
                self._flush_lazy()
            if self._dev_rows:
                self._flush_lazy_device(tighten)
            return
        if total == 0:
            return
        t0 = time.perf_counter_ns()
        mp = mixed_precision_enabled()
        self._bump_epoch(self._pending_rows > 0)
        with self.tracer.phase("flush/assemble"):
            rows = self._drain_pending()
        rows = self._prefilter_rows(rows)

        max_rows = max(r.shape[0] for r in rows)
        # one common power-of-two batch bucket B; partitions with more than B
        # pending rows (heavy skew) take extra rounds
        B = _next_pow2(min(max_rows, max(self.buffer_size, _MIN_CAP)))
        n_rounds = -(-max_rows // B)
        self._fnote(
            "flush.dispatch", policy="incremental", rows=total,
            rounds=n_rounds, block=B,
        )
        # staged pipeline: round r+1..r+depth are assembled and device_put
        # AFTER round r's merge kernel is dispatched (async), so host-side
        # assembly and the upload overlap the in-flight kernel — and a
        # growth sync at round r+1 waits behind an upload that's already
        # moving instead of serializing in front of it
        depth = flush_stage_depth()
        staged: dict[int, tuple] = {}

        def _stage(r: int):
            with self.tracer.phase("flush/assemble"):
                batch, bvalid, widths = self._round_batch(rows, r, B)
            with self.tracer.phase("flush/device_put"):
                return self._put(batch), self._put(bvalid), widths

        for rnd in range(n_rounds):
            if rnd not in staged:
                staged[rnd] = _stage(rnd)
            batch_dev, bvalid_dev, widths = staged.pop(rnd)

            def _grow_bucket():
                return _next_pow2(max(int((self._count_ub + widths).max()), 1))

            grow = _grow_bucket()
            if grow > self._cap:
                # about to grow: tighten the bounds with ONE real count sync
                # (growth events are log-bounded, so steady-state flushes
                # stay fully async)
                with self.tracer.phase("flush/count_sync"):
                    self._count_ub = np.asarray(self._count_dev, dtype=np.int64)
                grow = _grow_bucket()
            out_cap = max(self._cap, grow)
            variant = (
                "meshed_merge_step" if self.mesh is not None else "merge_step"
            )
            active = cost_thunk = None
            if self.mesh is None:
                # active-prefix merge: dominance passes + compact run
                # over the live-count bucket, not the storage capacity.
                active = min(
                    self._cap,
                    _active_bucket(max(int(self._count_ub.max()), 1)),
                )
                if capturing():
                    from skyline_tpu.ops.pallas_dominance import tile_pairs

                    # every partition runs against the largest one's
                    # prefix: the launch's pairs, and (from the host's
                    # upper bounds, no sync) the pairs among live rows;
                    # the tile pairs its three kernel passes launch
                    CAPTURED.count(
                        "flush/merge_pairs", self.num_partitions * active * B
                    )
                    CAPTURED.count(
                        "flush/merge_live_pairs",
                        int((np.minimum(self._count_ub, active) * widths).sum()),
                    )
                    CAPTURED.count(
                        "flush/merge_tiles",
                        self.num_partitions * (
                            tile_pairs(B, B) + tile_pairs(active, B)
                            + tile_pairs(B, active)
                        ),
                    )
                if self._profiler is not None and profile_cost_enabled():
                    cost_thunk = self._merge_cost_thunk(
                        batch_dev, bvalid_dev, active, grow, mp
                    )
            with self.tracer.phase("flush/merge_kernel"), self._kernel(
                variant, out_cap, mp, cost_thunk=cost_thunk
            ):
                if self.mesh is not None:
                    # explicit SPMD: pallas_call has no GSPMD partitioning
                    # rule, so the meshed flush must shard_map over the
                    # partition axis (each device merges only its resident
                    # partitions)
                    merge = meshed_merge_step(
                        self.mesh, self.mesh.axis_names[0], on_tpu(), out_cap,
                        mp,
                    )
                    self.sky, self.sky_valid, self._count_dev, res = merge(
                        self.sky, self.sky_valid, batch_dev, bvalid_dev
                    )
                else:
                    # out_active is the SAME bucket out_cap grew from, so
                    # merge_step_active's max(cap, out_active) == out_cap
                    # structurally.
                    self.sky, self.sky_valid, self._count_dev, res, tiles = (
                        merge_step_active(
                            self.sky,
                            self.sky_valid,
                            batch_dev,
                            bvalid_dev,
                            active,
                            grow,
                            mp,
                        )
                    )
                    if capturing():
                        self._tiles_run_dev = (
                            tiles if self._tiles_run_dev is None
                            else self._tiles_run_dev + tiles
                        )
                if mp:
                    self._accum_resolved(res)
            self._cap = out_cap
            self._count_ub = np.minimum(out_cap, self._count_ub + widths)
            for s in range(rnd + 1, min(rnd + 1 + depth, n_rounds)):
                if s not in staged:
                    staged[s] = _stage(s)
        self._counts_cache = None
        self._host_cache = None
        self._maybe_launch_summaries()
        self._maybe_launch_grid()
        self.processing_ns += time.perf_counter_ns() - t0

    def _sfs_vmapped(self, rows: list[np.ndarray], max_rows: int):
        """Balanced-load SFS: one vmapped launch per round for all
        partitions. Returns the device counts vector."""
        # bigger blocks than the incremental threshold pay off here: the
        # cross-prune work is block-count invariant, so fewer rounds just
        # save dispatches (at B^2/2 self-prune cost per round)
        B = _next_pow2(min(max_rows, max(self.buffer_size, 8192)))
        n_rounds = -(-max_rows // B)
        mp = mixed_precision_enabled()
        counts = self._count_dev
        # lag-2 tightening: the rows-streamed bound on _count_ub grows
        # linearly, but the true skyline may stay tiny (uniform/correlated
        # streams); reading the count vector from two rounds back — work
        # the device already drained while later rounds queued — keeps the
        # active bucket near the true size without stalling the pipeline
        prev: list[tuple] = []  # (counts_dev_after_round, widths_of_round)
        # same staged assemble/upload pipeline as the incremental rounds
        # (see flush_all): the next rounds' host work overlaps this round's
        # kernel, and a capacity-growth sync waits behind uploads that are
        # already in flight instead of serializing ahead of them
        depth = flush_stage_depth()
        staged: dict[int, tuple] = {}

        def _stage(r: int):
            with self.tracer.phase("flush/assemble"):
                batch, bvalid, widths = self._round_batch(rows, r, B)
            with self.tracer.phase("flush/device_put"):
                return self._put(batch), self._put(bvalid), widths

        for rnd in range(n_rounds):
            if rnd not in staged:
                staged[rnd] = _stage(rnd)
            batch_dev, bvalid_dev, widths = staged.pop(rnd)
            if len(prev) >= 2:
                c2, w1 = prev[-2][0], prev[-1][1]
                with self.tracer.phase("flush/count_sync"):
                    c2 = np.asarray(c2, dtype=np.int64)
                self._count_ub = np.minimum(self._count_ub, c2 + w1)
            # the SFS append writes a full B-row block at offset count, so
            # capacity must cover count + B for every partition
            need = int(self._count_ub.max()) + B
            if need > self._cap:
                with self.tracer.phase("flush/count_sync"):
                    self._count_ub = np.asarray(counts, dtype=np.int64)
                need = int(self._count_ub.max()) + B
                if need > self._cap:
                    self._grow_cap(_next_pow2(need))
            active = min(
                self._cap, _active_bucket(max(int(self._count_ub.max()), 1))
            )
            variant = (
                "meshed_sfs_round" if self.mesh is not None else "sfs_vmapped"
            )
            with self.tracer.phase("flush/merge_kernel"), self._kernel(
                variant, active, mp
            ):
                if self.mesh is not None:
                    rnd_fn = meshed_sfs_round(
                        self.mesh, self.mesh.axis_names[0], on_tpu(), active,
                        mp,
                    )
                    self.sky, counts, res = rnd_fn(
                        self.sky, counts, batch_dev, bvalid_dev
                    )
                else:
                    self.sky, counts, res, tiles = sfs_round(
                        self.sky, counts, batch_dev, bvalid_dev, active, mp
                    )
                    self._count_sfs_tiles(tiles, active, B)
                if mp:
                    self._accum_resolved(res)
            prev.append((counts, widths))
            self._count_ub = np.minimum(self._cap, self._count_ub + widths)
            for s in range(rnd + 1, min(rnd + 1 + depth, n_rounds)):
                if s not in staged:
                    staged[s] = _stage(s)
        self._count_dev = counts
        return counts

    def _seq_block_size(self, rows_p: int) -> int:
        """The large-skyline sequential block: a ~500k-row heavy partition
        runs 8 rounds at B=64k instead of 30 at 16k (the self-prune cost
        grows only linearly in B, the per-round dispatch and sync are the
        real price). Only used once the running count has
        PROVEN large — per-round work is B x bucket(S + B), so big blocks
        on a small-skyline stream multiply total work for nothing (uniform
        4D: S ~ 500 of 500k rows)."""
        return _next_pow2(
            min(
                max(rows_p, 1),
                max(self.buffer_size, 16384, min(rows_p // 8, 65536)),
            )
        )

    def _pad_sky_rows(self, s, new_cap: int):
        add = jnp.full(
            (new_cap - s.shape[0], self.dims), jnp.inf, dtype=jnp.float32
        )
        return jnp.concatenate([s, add], axis=0)

    def _restack_skies(self, new_skies: list, new_counts: list):
        """One stacked reassembly after a sequential pass (device-side; no
        host transfer), padded to the largest per-partition capacity
        reached."""
        final_cap = max(s.shape[0] for s in new_skies)
        new_skies = [
            s if s.shape[0] == final_cap else self._pad_sky_rows(s, final_cap)
            for s in new_skies
        ]
        self.sky = jnp.stack(new_skies)
        self._cap = final_cap
        counts = jnp.stack(new_counts).astype(jnp.int32)
        self._count_dev = counts
        return counts

    def _sfs_sequential(self, rows: list[np.ndarray]):
        """Skew-path SFS: heavy partitions processed one at a time with
        per-partition block and active buckets — total work tracks each
        partition's own rows instead of P x the heaviest. Returns the
        device counts vector."""
        # exact starting counts make the per-partition active buckets
        # tight; a fresh set (all upper bounds zero) provably has zero
        # counts, skipping a host<->device round trip
        if not int(self._count_ub.max()):
            counts_host = np.zeros(self.num_partitions, dtype=np.int64)
        else:
            counts_host = self.sky_counts("flush/count_sync").astype(np.int64)
        mp = mixed_precision_enabled()
        row_counts = np.array([r.shape[0] for r in rows], dtype=np.int64)

        # capacity grows ON DEMAND as survivor counts actually grow (one
        # exact count sync per doubling, like the vmapped path) — the old
        # worst-case pre-grow (prior counts + ALL streamed rows) allocated
        # a 16M-row bucket for a 10M-row skewed stream, and executables at
        # that shape are what crashed the remote-compile helper on the QoS
        # config. Start with room for existing survivors + one big block.
        B_max = self._seq_block_size(int(row_counts.max()))
        need0 = int(counts_host.max()) + B_max
        if need0 > self._cap:
            self._grow_cap(_next_pow2(need0))

        new_skies = []
        new_counts = []
        for p in range(self.num_partitions):
            rp = rows[p]
            sky_p = self.sky[p]
            cap_p = sky_p.shape[0]
            cnt_p = self._count_dev[p]
            ub_p = int(counts_host[p])
            if rp.shape[0]:
                # start at the probe block; escalate to the big block only
                # once the running count proves the skyline is large (a
                # known-large prior skyline escalates immediately)
                B_big = self._seq_block_size(rp.shape[0])
                B = B_big if ub_p > _PROBE_B // 2 else min(_PROBE_B, B_big)
                # lag-2 tightening (see _sfs_vmapped): low-skyline heavy
                # partitions would otherwise pay active buckets that track
                # rows streamed instead of survivors
                prev: list[tuple] = []
                off = 0
                while off < rp.shape[0]:
                    if len(prev) >= 2:
                        with self.tracer.phase("flush/count_sync"):
                            c2, w1 = int(prev[-2][0]), prev[-1][1]
                        ub_p = min(ub_p, c2 + w1)
                        # escalate once survival proves high: a probe
                        # round keeps <= B survivors, so compare against
                        # half a block (uniform keeps ~1% and never trips)
                        if B < B_big and c2 > B // 2:
                            B = B_big
                    if ub_p + B > cap_p:
                        # tighten with one exact count sync (a blocking
                        # read of the previous round), then grow with a
                        # full block of slack past the trip band — growing
                        # to exactly ub+B would leave cap in a band this
                        # check re-enters every round, paying a pipeline
                        # stall per round instead of one per doubling
                        with self.tracer.phase("flush/count_sync"):
                            ub_p = min(ub_p, int(cnt_p))
                        if ub_p + 2 * B > cap_p:
                            cap_p = _next_pow2(ub_p + 2 * B)
                            sky_p = self._pad_sky_rows(sky_p, cap_p)
                    with self.tracer.phase("flush/assemble"):
                        block, bvalid, w = self._pad_block(
                            rp[off : off + B], B
                        )
                    active = min(
                        cap_p, _active_bucket(max(ub_p, 1))
                    )
                    with self.tracer.phase("flush/device_put"):
                        block_dev = jnp.asarray(block)
                        bvalid_dev = jnp.asarray(bvalid)
                    with self.tracer.phase("flush/merge_kernel"), (
                        self._kernel("sfs_sequential", active, mp)
                    ):
                        sky_p, cnt_p, res, tiles = sfs_round_single(
                            sky_p, cnt_p, block_dev, bvalid_dev, active, mp
                        )
                        self._count_sfs_tiles(tiles, active, B)
                        if mp:
                            self._accum_resolved(res)
                    prev.append((cnt_p, w))
                    ub_p = min(cap_p, ub_p + w)
                    off += w
            new_skies.append(sky_p)
            new_counts.append(cnt_p)
            self._count_ub[p] = ub_p
        return self._restack_skies(new_skies, new_counts)

    def _sfs_sequential_dev(
        self, ws, bounds: np.ndarray, rank=None, tighten=True
    ):
        """Device-window twin of ``_sfs_sequential``: blocks are sliced out
        of the sorted window ``ws`` at host-tracked offsets instead of
        assembled from host rows — same probe/escalation, lag-2 tightening,
        and on-demand capacity growth. ``rank``: (ws_ranks, sorted_dims)
        switches the rounds to the rank cascade. ``tighten=False`` seeds
        the per-partition bounds from the host upper bounds instead of a
        count sync (sync-free overlap flushes; bounds-only use). Returns
        the device counts vector."""
        # fresh set: counts are provably zero, skip the sync (see
        # _sfs_sequential)
        if not int(self._count_ub.max()):
            counts_host = np.zeros(self.num_partitions, dtype=np.int64)
        elif not tighten:
            counts_host = self._count_ub.copy()
        else:
            counts_host = self.sky_counts("flush/count_sync").astype(np.int64)
        mp = mixed_precision_enabled()
        widths = np.diff(bounds)
        # blocks sliced from the sorted window must fit its SORT_TAIL pad
        # (a dynamic_slice past the buffer clamps backward and desyncs the
        # block from its validity mask) — cap every device block there
        B_max = min(self._seq_block_size(int(widths.max())), dw.SORT_TAIL)
        need0 = int(counts_host.max()) + B_max
        if need0 > self._cap and not tighten:
            # growth pressure under loose bounds: tighten with one exact
            # sync ONLY now (the bounds otherwise ratchet up with rows
            # streamed and capacity would track the stream, not the
            # skyline — the same on-demand fallback _sfs_vmapped_dev uses)
            counts_host = self.sky_counts("flush/count_sync").astype(np.int64)
            need0 = int(counts_host.max()) + B_max
        if need0 > self._cap:
            self._grow_cap(_next_pow2(need0))

        new_skies = []
        new_counts = []
        for p in range(self.num_partitions):
            lo, hi = int(bounds[p]), int(bounds[p + 1])
            sky_p = self.sky[p]
            cap_p = sky_p.shape[0]
            cnt_p = self._count_dev[p]
            ub_p = int(counts_host[p])
            if hi > lo:
                B_big = min(self._seq_block_size(hi - lo), dw.SORT_TAIL)
                B = B_big if ub_p > _PROBE_B // 2 else min(_PROBE_B, B_big)
                prev: list[tuple] = []
                off = lo
                while off < hi:
                    if len(prev) >= 2:
                        with self.tracer.phase("flush/count_sync"):
                            c2, w1 = int(prev[-2][0]), prev[-1][1]
                        ub_p = min(ub_p, c2 + w1)
                        if B < B_big and c2 > B // 2:
                            B = B_big
                    if ub_p + B > cap_p:
                        with self.tracer.phase("flush/count_sync"):
                            ub_p = min(ub_p, int(cnt_p))
                        if ub_p + 2 * B > cap_p:
                            cap_p = _next_pow2(ub_p + 2 * B)
                            sky_p = self._pad_sky_rows(sky_p, cap_p)
                    w = min(B, hi - off)
                    active = min(cap_p, _active_bucket(max(ub_p, 1)))
                    variant = (
                        "sfs_rank" if rank is not None else "sfs_sequential"
                    )
                    with self.tracer.phase("flush/merge_kernel"), (
                        self._kernel(variant, active, mp)
                    ):
                        if rank is not None:
                            sky_p, cnt_p = dw.sfs_round_at_rank(
                                sky_p, cnt_p, ws, rank[0], rank[1],
                                off, w, B=B, active=active,
                            )
                        else:
                            sky_p, cnt_p, res, tiles = dw.sfs_round_at(
                                sky_p, cnt_p, ws, off, w,
                                B=B, active=active, mp=mp,
                            )
                            self._count_sfs_tiles(tiles, active, B)
                            if mp:
                                self._accum_resolved(res)
                    prev.append((cnt_p, w))
                    ub_p = min(cap_p, ub_p + w)
                    off += w
            new_skies.append(sky_p)
            new_counts.append(cnt_p)
            self._count_ub[p] = ub_p
        return self._restack_skies(new_skies, new_counts)

    def _grow_cap(self, new_cap: int) -> None:
        """Grow the stacked skyline storage to ``new_cap`` rows (padding
        with +inf, which both flush policies treat as invalid)."""
        pad = jnp.full(
            (self.num_partitions, new_cap - self._cap, self.dims),
            jnp.inf,
            dtype=jnp.float32,
        )
        self.sky = self._put(jnp.concatenate([self.sky, pad], axis=1))
        self._cap = new_cap

    # -- flush dominance cascade (grid prefilter + mixed precision) ---------

    def _count_sfs_tiles(self, tiles, active: int, B: int) -> None:
        """While a profiler capture is on, count the tile pairs one SFS
        round's two Pallas passes launch (``flush/sfs_tiles``, from the
        shapes: every lane a (B x active) rectangular and a triangular B
        pass) and fold those whose body runs (``tiles``, one per lane) into
        a device scalar, read at the next global merge harvest."""
        if not capturing():
            return
        from skyline_tpu.ops.pallas_dominance import tile_pairs

        CAPTURED.count(
            "flush/sfs_tiles",
            tiles.size
            * (tile_pairs(active, B) + tile_pairs(B, B, triangular=True)),
        )
        t = jnp.sum(tiles, dtype=jnp.int32)
        self._sfs_tiles_run_dev = (
            t if self._sfs_tiles_run_dev is None
            else self._sfs_tiles_run_dev + t
        )

    def _accum_resolved(self, res) -> None:
        """Fold one round's bf16-resolved counts into the running device
        scalar — a tiny async add, synced only by ``flush_cascade_stats``
        (never on the flush hot path)."""
        s = jnp.sum(res, dtype=jnp.int32)
        self._mp_resolved_dev = (
            s if self._mp_resolved_dev is None else self._mp_resolved_dev + s
        )

    def _prefilter_on(self) -> bool:
        """Grid prefilter liveness for this set: single device, ``dims >
        2`` (the d <= 2 sweep flush has no merge kernels to save), gate
        resolved through the cascade table per flush."""
        return cascade.applies(
            "flush_prefilter", d=self.dims, meshed=self.mesh is not None
        )

    def _maybe_launch_grid(self) -> None:
        """Flush-tail hook (both host-row flush paths): start the grid
        summary compute for the state just flushed, async, so the NEXT
        flush's prefilter reads landed bytes instead of syncing cold."""
        if not self._prefilter_on():
            return
        active = min(
            self._cap, _active_bucket(max(int(self._count_ub.max()), 1))
        )
        self._grid_dev = grid_summary_device(
            self.sky, self._count_dev, active
        )
        for a in self._grid_dev:
            try:
                a.copy_to_host_async()
            except AttributeError:
                pass
        self._grid_host = None
        self._grid_epoch = self._epoch.copy()

    def _grid_summaries(self):
        """Validated host copy of the launched grid summary, or ``None``
        when no summary exists yet: ``(bounds, certifiers)``, the latter
        one ``_grid_certifiers`` result per partition.
        Host-side validation disables (per partition x dim) any boundary
        ladder f32 rounding failed to keep strictly increasing — codes
        against a non-monotone ladder could certify false dominance; a
        disabled dim never certifies, which disables drops for its whole
        partition (the certificate needs every dim). Empty partitions
        produce NaN ladders and disable everything — zero drops,
        conservative."""
        if self._grid_dev is None:
            return None
        if self._grid_host is None:
            bounds = np.asarray(self._grid_dev[0])
            ux = np.asarray(self._grid_dev[1]).copy()
            with np.errstate(invalid="ignore"):  # NaN ladder = empty part.
                bad = ~np.all(np.diff(bounds, axis=1) > 0, axis=1)  # (P, d)
            if bad.any():
                ux[np.broadcast_to(bad[:, None, :], ux.shape)] = GRID_BINS + 1
            packed = self.dims <= _PACKED_MAX_DIMS
            self._grid_host = (
                bounds, [_grid_certifiers(u, packed) for u in ux]
            )
        return self._grid_host

    def _prefilter_rows(self, rows: list[np.ndarray]) -> list[np.ndarray]:
        """Stage 1 of the flush cascade: drop pending rows whose grid cell
        is strictly dominated by a representative cell of their partition's
        resident skyline, before any merge kernel launches. Rows are coded
        by one binary search per dim (``_grid_codes``); for ``dims <=
        _PACKED_MAX_DIMS`` each row's codes pack into one int64 and each
        representative costs one subtract and mask (``_packed_drop``),
        above that a broadcast compare against every representative.

        Soundness: a row y coded ``vy`` and a representative x coded ``ux``
        with ``ux < vy`` in EVERY dim satisfy
        ``x <= bounds[ux] < bounds[vy] <= y`` per-dim (the ladder is
        validated strictly increasing), i.e. x strictly dominates y. x was
        a LIVE skyline row when the summary launched; if a later flush
        removed it, its remover chain ends at a current row that still
        strictly dominates y (each removal step only tightens every
        coordinate), so the exact merge drops y anyway — and any pending
        row y itself would have pruned is strictly dominated by the same
        chain (transitivity). Survivor set AND compaction/append order are
        therefore byte-identical with the prefilter on or off
        (tests/test_flush_cascade.py asserts this). NaN rows code to -1
        and are never dropped; +inf rows code to GRID_BINS and may drop
        (legitimately — a finite representative strictly dominates +inf).
        """
        if not self._prefilter_on():
            return rows
        grid = self._grid_summaries()
        seen = int(sum(r.shape[0] for r in rows))
        dropped = 0
        packed = self.dims <= _PACKED_MAX_DIMS
        if grid is not None and seen:
            bounds, certifiers = grid
            with self.tracer.phase("flush/prefilter"):
                for p, r in enumerate(rows):
                    n = r.shape[0]
                    u = certifiers[p]
                    if n == 0 or u.shape[0] == 0:
                        continue  # no representative can certify here
                    b = bounds[p]  # (GRID_BINS+1, d) boundary ladder
                    keep = np.ones(n, dtype=bool)
                    any_drop = False
                    for s in range(0, n, _PREFILTER_CHUNK):
                        vy = _grid_codes(b, r[s : s + _PREFILTER_CHUNK])
                        if packed:
                            drop = _packed_drop(vy, u)
                        else:
                            drop = np.any(
                                np.all(u[None, :, :] < vy[:, None, :], axis=2),
                                axis=1,
                            )
                        if drop.any():
                            keep[s : s + vy.shape[0]] = ~drop
                            any_drop = True
                    if packed:
                        self.prefilter_packed_seen += n
                    if any_drop:
                        dropped += int(n - keep.sum())
                        rows[p] = r[keep]
        self.prefilter_seen += seen
        self.prefilter_dropped += dropped
        if seen:
            self._fnote(
                "flush.prefilter", seen=seen, dropped=dropped,
                path="packed" if packed else "broadcast",
            )
        # inc 0 too: the Prometheus series must register at the first
        # prefiltered flush, not the first nonzero drop (obs_smoke asserts
        # presence right after one flush+stats round trip)
        self._inc("flush.prefilter_dropped", dropped)
        # register unconditionally: the series must exist even where mixed
        # precision defaults off (CPU-fallback), so scrapers see a stable
        # schema and obs_smoke can assert both series on any backend
        self._inc("flush.bf16_resolved", 0)
        return rows

    def flush_cascade_stats(self) -> dict:
        """Flush-cascade observability block (stage-1 grid-prefilter
        counters, host-exact, plus the stage-2 bf16-resolved device
        accumulator). The device scalar is synced HERE — stats/bench
        paths only, the flush hot path never blocks on it — and the total
        is delta-fed to the telemetry counters so /metrics and this dict
        always agree."""
        if self._mp_resolved_dev is not None:
            total = int(np.asarray(self._mp_resolved_dev))
            self.bf16_resolved = total
            delta = total - self._bf16_resolved_reported
            if delta:
                self._inc("flush.bf16_resolved", delta)
                self._bf16_resolved_reported = total
        seen = self.prefilter_seen
        return {
            "prefilter_enabled": self._prefilter_on(),
            "mixed_precision": mixed_precision_enabled(),
            "prefilter_seen": seen,
            "prefilter_dropped": self.prefilter_dropped,
            "prefilter_drop_fraction": (
                self.prefilter_dropped / seen if seen else 0.0
            ),
            "prefilter_packed_seen": self.prefilter_packed_seen,
            "bf16_resolved": self.bf16_resolved,
        }

    def _flush_lazy(self) -> None:
        """Lazy-policy flush: sum-sort each partition's accumulated window
        and stream it through append-only SFS rounds — one vmapped launch
        per round for balanced loads, per-partition rounds under routing
        skew. See stream/window.py's SFS notes for the invariant."""
        t0 = time.perf_counter_ns()
        self._bump_epoch(self._pending_rows > 0)
        with self.tracer.phase("flush/assemble"):
            rows = self._drain_pending()
        # prefilter BEFORE the sum sort: dropped rows skip the sort too,
        # and a stable sort of the surviving subset keeps the same relative
        # order the post-sort drop would (byte-identical SFS appends)
        rows = self._prefilter_rows(rows)
        with self.tracer.phase("flush/assemble"):
            for p, r in enumerate(rows):
                if r.shape[0] > 1:
                    order = np.argsort(r.sum(axis=1), kind="stable")
                    rows[p] = r[order]
        had_old, old_counts = self._check_had_old()

        max_rows = max(r.shape[0] for r in rows)
        total_rows = int(sum(r.shape[0] for r in rows))
        # path choice: the vmapped round costs P lanes of (B x active) work
        # per round regardless of how many lanes carry real rows, i.e.
        # ~P * max_rows lane-rows total; the per-partition sequential path
        # costs ~total_rows. Under routing skew (mr-angle at 8D sends ~96%
        # of rows to 2 of 8 partitions) sequential wins by ~P/2; balanced
        # streams keep the one-launch-per-round batching.
        sequential = self.mesh is None and (
            self.num_partitions * max_rows > 2 * total_rows
        )
        device_variant = "sequential" if sequential else "vmapped"
        path = self._choose_lazy_path(device_variant, total_rows)
        self._fnote(
            "flush.dispatch", policy=self.flush_policy, rows=total_rows,
            max_rows=max_rows, sequential=sequential, path=path,
        )
        if path == "sorted_sfs":
            self._inc("flush.sorted_sfs")
            with self._flush_prof.record(
                "flush_sorted_sfs", self.dims, total_rows
            ):
                counts = self._sfs_sorted_host(rows)
        elif path == "device_cascade":
            self._inc("flush.device_cascade")
            with self._flush_prof.record(
                "flush_device_cascade", self.dims, total_rows
            ):
                counts = self._sfs_device_cascade(rows)
        elif self._flush_prof is not None:
            # chooser active: time the device flush end to end (counts
            # sync included) so the EMA compare is honest
            with self._flush_prof.record(
                "flush_sfs_" + device_variant, self.dims, total_rows
            ):
                counts = (
                    self._sfs_sequential(rows)
                    if sequential
                    else self._sfs_vmapped(rows, max_rows)
                )
                np.asarray(counts)
        elif sequential:
            counts = self._sfs_sequential(rows)
        else:
            counts = self._sfs_vmapped(rows, max_rows)
        self._finish_lazy_flush(
            counts,
            had_old,
            old_counts,
            int(old_counts.max()) if had_old else 0,
            t0,
        )

    def _choose_lazy_path(self, device_variant: str, total_rows: int) -> str:
        """Pick the lazy-flush merge path: ``sorted_sfs`` (host cascade,
        ops/sorted_sfs.py) or the device SFS variant. Per ISSUE 11 this is
        a profiler-driven choice, not an env gate: under ``auto`` each
        candidate's WHOLE-FLUSH wall is recorded once per (d, N-bucket,
        backend) signature and the measured EMA decides thereafter
        (``dispatch.choose_variant``; the sorted path explores first). The
        host path needs concrete host rows, so meshes and TPU backends
        never list it; the DEVICE cascade (``ops/device_cascade.py``,
        ISSUE 18) is jit-safe and joins the candidate row whenever the
        host cascade is OUT of play (TPU, or ``SKYLINE_SORTED_SFS=off``)
        — on host backends with the sorted cascade available, the device
        cascade loses to it at every measured signature, so listing it
        would make every fresh engine pay a losing exploration flush for
        nothing (``SKYLINE_DEVICE_CASCADE=on`` still forces it anywhere
        for A/B). Meshed flushes stay on the shard_map SFS rounds. The
        candidate set and race now resolve through the declarative
        cascade table (``ops/cascade.py resolve_flush``), which also
        honors tuner-pinned winners for this (d, N-bucket) signature."""
        meshed = self.mesh is not None
        if meshed:
            return device_variant
        if cascade.flush_chooser_active(meshed) and self._flush_prof is None:
            from skyline_tpu.telemetry.profiler import KernelProfiler

            self._flush_prof = KernelProfiler()
        return cascade.resolve_flush(
            device_variant, self.dims, total_rows, meshed, self._flush_prof
        )

    def _sfs_sorted_host(self, rows: list[np.ndarray]):
        """Host sorted-order SFS flush: per partition, take the exact
        survivor mask of old ∪ new on the host (ops/sorted_sfs.py dedup +
        sum-sorted scan) and append the surviving new rows after the old
        prefix — the same rows in the same order the device SFS rounds
        append (rows arrive pre-sorted by row sum from ``_flush_lazy``,
        and the cascade only selects, never reorders), so every
        downstream consumer sees byte-identical state; the shared
        ``_finish_lazy_flush`` old-vs-new cleanup then runs unchanged.
        Returns the device counts vector like its device siblings."""
        from skyline_tpu.ops.sorted_sfs import sorted_sfs_keep

        if not int(self._count_ub.max()):
            counts_host = np.zeros(self.num_partitions, dtype=np.int64)
        else:
            counts_host = self.sky_counts("flush/count_sync").astype(np.int64)
        new_skies = []
        new_counts = []
        for p in range(self.num_partitions):
            rp = rows[p]
            sky_p = self.sky[p]
            cnt_p = self._count_dev[p]
            old_n = int(counts_host[p])
            if rp.shape[0]:
                with self.tracer.phase("flush/assemble"):
                    old = np.asarray(sky_p[:old_n]) if old_n else None
                with self.tracer.phase("flush/merge_kernel"), self._kernel(
                    "sorted_sfs", old_n + rp.shape[0]
                ):
                    keep = sorted_sfs_keep(rp, old)
                surv = rp[keep]
                need = old_n + surv.shape[0]
                cap_p = max(sky_p.shape[0], _next_pow2(max(need, 1)))
                with self.tracer.phase("flush/assemble"):
                    buf = np.full(
                        (cap_p, self.dims), np.inf, dtype=np.float32
                    )
                    if old_n:
                        buf[:old_n] = old
                    buf[old_n:need] = surv
                with self.tracer.phase("flush/device_put"):
                    sky_p = jnp.asarray(buf)
                    cnt_p = jnp.asarray(np.int32(need))
                self._count_ub[p] = need
            new_skies.append(sky_p)
            new_counts.append(cnt_p)
        return self._restack_skies(new_skies, new_counts)

    def _sfs_device_cascade(self, rows: list[np.ndarray]):
        """Device-cascade flush: same per-partition shape as
        ``_sfs_sorted_host`` — exact survivor mask of old ∪ new, new
        survivors appended after the old prefix in arrival order — but
        the mask comes from the jit-compiled sorted dominance cascade
        (``ops/device_cascade.py``), so the merge kernel runs on the
        accelerator instead of a host numpy scan. Byte-identical state
        by the same argument: the cascade only selects, never reorders,
        and the old prefix always survives the union (old rows are
        mutually non-dominated and new rows arrive pre-screened)."""
        from skyline_tpu.ops.device_cascade import device_cascade_keep

        if not int(self._count_ub.max()):
            counts_host = np.zeros(self.num_partitions, dtype=np.int64)
        else:
            counts_host = self.sky_counts("flush/count_sync").astype(np.int64)
        new_skies = []
        new_counts = []
        for p in range(self.num_partitions):
            rp = rows[p]
            sky_p = self.sky[p]
            cnt_p = self._count_dev[p]
            old_n = int(counts_host[p])
            if rp.shape[0]:
                with self.tracer.phase("flush/assemble"):
                    old = (
                        np.asarray(sky_p[:old_n])
                        if old_n
                        else np.empty((0, self.dims), dtype=np.float32)
                    )
                with self.tracer.phase("flush/merge_kernel"), self._kernel(
                    "device_cascade", old_n + rp.shape[0]
                ):
                    keep = device_cascade_keep(rp, old)
                surv = rp[keep]
                need = old_n + surv.shape[0]
                cap_p = max(sky_p.shape[0], _next_pow2(max(need, 1)))
                with self.tracer.phase("flush/assemble"):
                    buf = np.full(
                        (cap_p, self.dims), np.inf, dtype=np.float32
                    )
                    if old_n:
                        buf[:old_n] = old
                    buf[old_n:need] = surv
                with self.tracer.phase("flush/device_put"):
                    sky_p = jnp.asarray(buf)
                    cnt_p = jnp.asarray(np.int32(need))
                self._count_ub[p] = need
            new_skies.append(sky_p)
            new_counts.append(cnt_p)
        return self._restack_skies(new_skies, new_counts)

    def _check_had_old(self):
        """Non-empty initial state needs exact old counts for the final
        old-vs-new cleanup pass (one sync; fresh windows skip it)."""
        had_old = bool((self._count_ub > 0).any())
        old_counts = (
            self.sky_counts("flush/count_sync").astype(np.int32)
            if had_old
            else None
        )
        if had_old and not int(old_counts.max()):
            had_old = False
        return had_old, old_counts

    def _finish_lazy_flush(
        self, counts, had_old, old_counts, old_max, t0, rank=None,
        tighten=True,
    ) -> None:
        """Shared tail of the lazy flush paths: old-vs-new cleanup,
        validity/caches, bound tightening. ``old_counts``: exact
        per-partition pre-flush counts, host or device array (host required
        under a mesh for sharding); ``old_max``: a host bound on their max
        (buckets only). ``rank``: (ws_ranks, sorted_dims) from the
        rank-cascade device flush — the cleanup then compares in rank space
        (old prefixes are universe members). ``tighten=False`` skips the
        trailing count sync (overlap auto-flushes; the next flush's buckets
        then run off the lag-2 upper bounds)."""
        if had_old:
            old_active = min(self._cap, _active_bucket(max(old_max, 1)))
            active = min(
                self._cap, _active_bucket(max(int(self._count_ub.max()), 1))
            )
            with self.tracer.phase("flush/merge_kernel"), self._kernel(
                "sfs_cleanup", active
            ):
                if rank is not None:
                    self.sky, counts = dw.sfs_cleanup_rank(
                        self.sky,
                        counts,
                        jnp.asarray(old_counts),
                        rank[1],
                        old_active,
                        active,
                    )
                elif self.mesh is not None:
                    cl = meshed_sfs_cleanup(
                        self.mesh, self.mesh.axis_names[0], on_tpu(),
                        old_active, active,
                    )
                    self.sky, counts = cl(
                        self.sky, counts, self._put(np.asarray(old_counts))
                    )
                else:
                    self.sky, counts = sfs_cleanup(
                        self.sky, counts, jnp.asarray(old_counts),
                        old_active, active,
                    )
        self._count_dev = counts
        # validity is a pure function of counts under append-only state
        self.sky_valid = jnp.arange(self._cap)[None, :] < counts[:, None]
        self._counts_cache = None
        self._host_cache = None
        if tighten:
            # start the count transfer now but don't block on it: the
            # caller's next step is almost always the global merge, whose
            # active bucket comes from _count_ub — the first consumer
            # (sky_counts / global_merge_stats) absorbs the already-landed
            # bytes instead of stalling ingest here on a cold sync
            try:
                counts.copy_to_host_async()
            except AttributeError:
                pass
            self._tighten_pending = True
        self._maybe_launch_summaries()
        self._maybe_launch_grid()
        self.processing_ns += time.perf_counter_ns() - t0

    def _flush_sweep(self) -> None:
        """d <= 2 flush, every policy: union the old skylines, the host
        pending rows, and the device accumulation window into ONE buffer
        and take per-partition skylines by sort + segmented prefix-min
        sweep (ops/sweep2d.py) — O(N log N), no pairwise dominance, no SFS
        rounds, exact by the merge law (skyline(union) per partition).

        Two launches + one count sync: the core launch yields exact
        survivor counts, the host sizes storage to their max, the scatter
        launch packs the stacked (P, cap, d) layout. The sync costs ~ms
        where the SFS rounds it replaces cost seconds, so the overlap
        policy's sync-free property is deliberately traded away here.
        d == 1 rides as (x, 0) pairs: constant second dim makes 2D
        dominance degenerate to 1D (strictness must come from x)."""
        t0 = time.perf_counter_ns()
        # dirty set without a sync: host pending rows are known per
        # partition; a non-empty device window could touch any partition,
        # so it conservatively dirties all (over-bumping only costs cache
        # reuse, never correctness)
        if self._dev_rows > 0:
            self._bump_epoch(slice(None))
        else:
            self._bump_epoch(self._pending_rows > 0)
        from skyline_tpu.ops.sweep2d import (
            partitioned_sweep2_core,
            scatter_sweep2,
        )

        P = self.num_partitions
        with self.tracer.phase("flush/assemble"):
            rows = self._drain_pending()
            host_vals = np.concatenate(
                [r for r in rows if r.shape[0]] or
                [np.empty((0, self.dims), np.float32)]
            )
            host_pids = np.repeat(
                np.arange(P, dtype=np.int32),
                [r.shape[0] for r in rows],
            )
        n_host = host_vals.shape[0]
        # valid prefixes only (the conventions the SFS paths use): the dev
        # window is allocated in doubling buckets that never shrink, and
        # sky rows past the active bucket are invalid by the count bounds —
        # sorting either's full allocation would inflate every flush and
        # churn n_bucket recompiles
        dev_bucket = (
            min(self._dev_cap, _next_pow2(self._dev_rows))
            if self._dev_rows
            else 0
        )
        sky_active = min(
            self._cap, _active_bucket(max(int(self._count_ub.max()), 1))
        )
        n_in = P * sky_active + n_host + dev_bucket
        n_bucket = _next_pow2(n_in)
        pad = n_bucket - n_in
        with self.tracer.phase("flush/device_put"):
            host_vals_d = jnp.asarray(host_vals)
            host_pids_d = jnp.asarray(host_pids)
        sky_flat = self.sky[:, :sky_active].reshape(
            P * sky_active, self.dims
        )
        sky_pids = jnp.repeat(jnp.arange(P, dtype=jnp.int32), sky_active)
        sky_ok = self.sky_valid[:, :sky_active].reshape(-1)
        parts_v = [sky_flat, host_vals_d]
        parts_p = [sky_pids, host_pids_d]
        parts_ok = [sky_ok, jnp.ones((n_host,), bool)]
        if dev_bucket:
            parts_v.append(self._dev_window[:dev_bucket])
            parts_p.append(self._dev_pids[:dev_bucket])
            parts_ok.append(jnp.arange(dev_bucket) < self._dev_rows)
        if pad:
            parts_v.append(jnp.full((pad, self.dims), jnp.inf, jnp.float32))
            parts_p.append(jnp.zeros((pad,), jnp.int32))
            parts_ok.append(jnp.zeros((pad,), bool))
        values = jnp.concatenate(parts_v)
        pids = jnp.concatenate(parts_p)
        valid = jnp.concatenate(parts_ok)
        if self.dims == 1:
            values = jnp.concatenate(
                [values, jnp.zeros((n_bucket, 1), jnp.float32)], axis=1
            )
        with self.tracer.phase("flush/sweep"):
            srows, sp, keep, rank, counts = partitioned_sweep2_core(
                values, pids, valid, P
            )
            counts_host = np.asarray(counts, dtype=np.int64)  # the one sync
        new_cap = max(
            self._cap, _next_pow2(max(int(counts_host.max()), _MIN_CAP))
        )
        with self.tracer.phase("flush/sweep"):
            sky2, counts_dev = scatter_sweep2(
                srows, sp, keep, rank, counts, P, new_cap
            )
            if self.dims == 1:
                sky2 = sky2[:, :, :1]
        self.sky = sky2
        self._cap = new_cap
        self._count_dev = counts_dev
        self.sky_valid = (
            jnp.arange(new_cap)[None, :] < counts_dev[:, None]
        )
        self._count_ub = counts_host.copy()
        self._counts_cache = None
        self._host_cache = None
        self._dev_rows = 0
        self.processing_ns += time.perf_counter_ns() - t0

    def _flush_lazy_device(self, tighten: bool = True) -> None:
        """Lazy/overlap flush over the device accumulation window: one
        (pid, sum) sort + segment-bounds launch, then SFS rounds slicing
        blocks straight from the sorted buffer (stream/device_window.py) —
        no host routing, assembly, or per-block upload. Barrier/metrics
        bookkeeping is NOT synced here (flushing doesn't need it; triggers
        sync it on demand).

        ``tighten=False``: no count syncs — had-old detection and every
        bucket come from the host upper bounds (conservative is correct:
        rows past the true counts are +inf/invalid), the cleanup's exact
        per-partition old counts stay the pre-flush DEVICE count vector,
        and the trailing tighten sync is skipped. Only the segment-bounds
        transfer (host loop control) touches the device."""
        t0 = time.perf_counter_ns()
        n = self._dev_rows
        n_bucket = _next_pow2(n)
        with self.tracer.phase("flush/sort"):
            ws, bounds_dev = dw.sort_window(
                self._dev_window,
                self._dev_pids,
                n,
                n_bucket,
                self.num_partitions,
                dw.SORT_TAIL,
            )
        with self.tracer.phase("flush/bounds_sync"):
            bounds = np.asarray(bounds_dev, dtype=np.int64)
        self._bump_epoch(np.diff(bounds) > 0)
        self._dev_rows = 0
        if tighten:
            had_old, old_counts = self._check_had_old()
            old_max = int(old_counts.max()) if had_old else 0
        else:
            had_old = bool((self._count_ub > 0).any())
            # exact per-partition old counts WITHOUT a sync: the pre-flush
            # device count vector (cleanup classifies old-vs-new rows by
            # these, so exactness matters; buckets below only need bounds)
            old_counts = self._count_dev if had_old else None
            old_max = int(self._count_ub.max()) if had_old else 0
        # rank-cascade mode: rank the window (+ live sky prefixes, which
        # must share the rank universe) once per flush; the rounds then
        # compare dense ranks instead of values (2 VPU ops/dim + one
        # rank-sum compare vs 3/dim — see ops/pallas_dominance.py)
        rank = None
        if dw.rank_flush_enabled():
            active_old = (
                min(self._cap, _active_bucket(max(old_max, 1)))
                if had_old
                else 0
            )
            univ_bucket = _next_pow2(
                n_bucket + self.num_partitions * active_old
            )
            with self.tracer.phase("flush/rank"):
                sorted_dims, wr = dw.rank_window(
                    ws,
                    self.sky,
                    self._count_dev,
                    n_bucket,
                    active_old,
                    univ_bucket,
                )
            rank = (wr, sorted_dims)
        widths = np.diff(bounds)
        max_rows = int(widths.max())
        total_rows = int(widths.sum())
        # same skew heuristic as the host path (see _flush_lazy)
        sequential = self.num_partitions * max_rows > 2 * total_rows
        self._fnote(
            "flush.dispatch", policy=self.flush_policy, device_window=True,
            rows=total_rows, max_rows=max_rows, sequential=sequential,
        )
        if sequential:
            counts = self._sfs_sequential_dev(ws, bounds, rank, tighten)
        else:
            counts = self._sfs_vmapped_dev(ws, bounds, max_rows, rank)
        self._finish_lazy_flush(
            counts, had_old, old_counts, old_max, t0, rank, tighten
        )

    def _sfs_vmapped_dev(
        self, ws, bounds: np.ndarray, max_rows: int, rank=None
    ):
        """Device-window twin of ``_sfs_vmapped``: one vmapped launch per
        round, every lane slicing its block from the shared sorted window.
        ``rank``: (ws_ranks, sorted_dims) switches to the rank cascade.
        Returns the device counts vector."""
        # cap at SORT_TAIL: see _sfs_sequential_dev's B_max note
        B = min(
            _next_pow2(min(max_rows, max(self.buffer_size, 8192))),
            dw.SORT_TAIL,
        )
        n_rounds = -(-max_rows // B)
        mp = mixed_precision_enabled()
        counts = self._count_dev
        lo = bounds[:-1]
        hi = bounds[1:]
        prev: list[tuple] = []  # lag-2 tightening, see _sfs_vmapped
        for rnd in range(n_rounds):
            offs = np.minimum(lo + rnd * B, hi)
            w = np.clip(hi - offs, 0, B)
            if len(prev) >= 2:
                c2, w1 = prev[-2][0], prev[-1][1]
                with self.tracer.phase("flush/count_sync"):
                    c2 = np.asarray(c2, dtype=np.int64)
                self._count_ub = np.minimum(self._count_ub, c2 + w1)
            need = int(self._count_ub.max()) + B
            if need > self._cap:
                with self.tracer.phase("flush/count_sync"):
                    self._count_ub = np.asarray(counts, dtype=np.int64)
                need = int(self._count_ub.max()) + B
                if need > self._cap:
                    self._grow_cap(_next_pow2(need))
            active = min(
                self._cap, _active_bucket(max(int(self._count_ub.max()), 1))
            )
            variant = "sfs_rank" if rank is not None else "sfs_vmapped"
            with self.tracer.phase("flush/merge_kernel"), self._kernel(
                variant, active, mp
            ):
                offs_d = jnp.asarray(offs.astype(np.int32))
                w_d = jnp.asarray(w.astype(np.int32))
                if rank is not None:
                    self.sky, counts = dw.sfs_round_at_rank_vmapped(
                        self.sky, counts, ws, rank[0], rank[1],
                        offs_d, w_d, B=B, active=active,
                    )
                else:
                    self.sky, counts, res, tiles = dw.sfs_round_at_vmapped(
                        self.sky, counts, ws, offs_d, w_d,
                        B=B, active=active, mp=mp,
                    )
                    self._count_sfs_tiles(tiles, active, B)
                    if mp:
                        self._accum_resolved(res)
            prev.append((counts, w))
            self._count_ub = np.minimum(self._cap, self._count_ub + w)
        self._count_dev = counts
        return counts

    # -- query ------------------------------------------------------------

    def global_merge_stats(self, emit_points: bool = False):
        """Device-side global merge over the (flushed) stacked state.

        Returns ``(counts (P,), survivors_per_partition (P,), global_count,
        points | None)`` with ONE small device->host transfer for the stats
        (plus one bounded transfer when ``emit_points``) — replacing the
        full-buffer snapshot pull + host merge + re-upload. Single-device
        only (the engine falls back to the host path under a mesh).

        Incremental reuse (``SKYLINE_MERGE_CACHE``, default on): the result
        is cached keyed by the partition epoch vector. An identical key
        means no flush touched any partition since the cached merge, so the
        cached stats (and lazily-transferred points) come back with ZERO
        kernel launches; when only a dirty subset changed (fraction <=
        ``SKYLINE_DELTA_CUTOFF``) the merge runs over ``cached_global ∪
        dirty skylines`` instead of the full union
        (``global_merge_delta_device`` documents the correctness argument).
        Either way the result is byte-identical to the from-scratch
        recompute — tests/test_merge_cache.py property-checks this against
        random flush/query interleavings.

        With ``SKYLINE_MERGE_TREE`` (default on, ``dims > 2``, single
        device) the union pass is replaced by a pruned tournament tree:
        whole partitions whose min-corner is strictly dominated by another
        partition's witness point are dropped before any kernel launches
        (``SKYLINE_MERGE_PRUNE``), and the survivors merge pairwise up a
        log-depth binary tree so each level halves the candidate set.
        Byte-identical to the flat recompute — tests/test_merge_tree.py
        property-checks the grid.

        This method is ``global_merge_launch`` + ``global_merge_harvest``
        back to back; callers that want to overlap the merge with further
        ingest use the two halves directly (stream/engine.py's overlapped
        query sync).
        """
        return self.global_merge_harvest(self.global_merge_launch(emit_points))

    def global_merge_launch(self, emit_points: bool = False) -> _MergeHandle:
        """Launch the global merge without blocking on the result.

        Every kernel (tree or flat, full or delta) and the stats
        device->host copy are dispatched here; the returned handle carries
        the in-flight arrays plus the launch-time epoch identity. The
        caller may keep flushing new rows before harvesting — harvest
        detects the moved epoch and skips the count-bound refresh (the
        cached result itself stays valid under its own key).
        """
        if self._tighten_pending:
            # absorb the flush's async count transfer before sizing any
            # bucket below: the bytes are already in flight, so this sync
            # is cheap and the bounds it tightens halve the pairwise work
            self.sky_counts()
        h = _MergeHandle()
        h.emit_points = emit_points
        h.key = self.epoch_key
        h.epoch = self._epoch.copy()
        # claim the parked EXPLAIN plan (one-shot): it rides the handle so
        # an overlapped merge annotates the query that launched it
        h.explain, self._explain = self._explain, None
        use_cache = cascade.merge_cache_on(self.mesh is not None)
        h.use_cache = use_cache
        cache = self._gm_cache if use_cache else None
        if cache is not None and cache["key"] == h.key:
            # exact hit: no flush touched any partition since this result
            # was computed — materialize it now (zero kernel launches) so
            # harvest can't be skewed by a later cache replacement
            self.merge_cache_hits += 1
            self._inc("merge.cache_hit")
            self._fnote("merge.cache_hit", key=h.key)
            self._counts_cache = cache["counts"].copy()
            self._count_ub = cache["counts"].copy()
            h.cached = True
            h.result = (
                cache["counts"].copy(),
                cache["surv"].copy(),
                cache["g"],
                self._cached_points() if emit_points else None,
            )
            if h.explain is not None:
                h.explain.merge = {
                    "path": "cache_hit",
                    "cached": True,
                    "epoch_key": h.key.hex(),
                    "dirty_fraction": 0.0,
                    "dirty": [],
                    "clean": np.flatnonzero(
                        cache["counts"] > 0
                    ).tolist(),
                    "skyline_size": int(cache["g"]),
                }
            return h
        self.merge_cache_misses += 1
        self._inc("merge.cache_miss")
        P = self.num_partitions
        dirty = None
        dirty_mask = None
        if cache is not None:
            dirty_mask = self._epoch != cache["epoch"]
            self.last_dirty_fraction = float(dirty_mask.sum()) / P
            if cascade.delta_applies(self.last_dirty_fraction):
                dirty = dirty_mask
        elif use_cache:
            self.last_dirty_fraction = 1.0  # cold miss == everything dirty
        use_tree = cascade.merge_tree_on(self.mesh is not None, self.dims)
        path = cascade.merge_path(use_tree, dirty is not None)
        self._fnote(
            "merge.launch", path=path, dirty_fraction=self.last_dirty_fraction,
        )
        if h.explain is not None:
            if dirty_mask is not None:
                dirty_set = np.flatnonzero(dirty_mask).tolist()
                clean_set = np.flatnonzero(~dirty_mask).tolist()
            else:
                # no cached epoch to diff against: everything recomputes
                dirty_set = list(range(P))
                clean_set = []
            h.explain.merge = {
                "path": path,
                "cached": False,
                "epoch_key": h.key.hex(),
                # only meaningful when the cache plane computed it this
                # launch; otherwise it's a stale carry-over
                "dirty_fraction": (
                    self.last_dirty_fraction if use_cache else None
                ),
                "dirty": dirty_set,
                "clean": clean_set,
            }
        stats = None
        if dirty is not None:
            h.dirty = dirty
            if use_tree:
                stats = self._merge_tree_delta(cache, dirty, h)
            if stats is None:
                union, keep, stats, _, clean_total = self._merge_delta(
                    cache, dirty
                )
                h.union, h.keep = union, keep
                h.clean_total = clean_total
        else:
            if use_tree:
                stats = self._merge_tree_full(h)
            if stats is None:
                # the count upper bounds are maintained without syncs, so
                # these buckets cost no round trip (pessimistic is safe:
                # rows between count and active are invalid by the mask;
                # union_cap from the SUMMED bounds keeps the pass
                # union-sized under routing skew)
                active = min(
                    self._cap,
                    _active_bucket(max(int(self._count_ub.max()), 1)),
                )
                # quarter-pow2 ladder on the union too: the triangular pass
                # costs O(union_cap^2), so the ladder's ~1.14x tighter
                # bucket is ~1.3x less pairwise work at the north-star
                # union (~437k rows)
                union_cap = _active_bucket(max(int(self._count_ub.sum()), 1))
                union, keep, stats = global_merge_stats_device(
                    self.sky, self._count_dev, active, union_cap
                )
                h.union, h.keep = union, keep
        h.stats = stats
        # start the stats transfer before any host-side bookkeeping so the
        # copy overlaps it instead of starting cold inside np.asarray
        try:
            stats.copy_to_host_async()
        except AttributeError:
            pass
        return h

    def global_merge_harvest(self, handle: _MergeHandle):
        """Block on an in-flight merge and return ``(counts, surv, g,
        points | None)`` — the second half of ``global_merge_stats``. The
        sync cost lands under the ``query/global_stats_sync`` phase; when
        the caller overlapped enough ingest, the bytes already arrived and
        the phase records only the harvest."""
        h = handle
        if h.cached:
            return h.result
        P = self.num_partitions
        with self.tracer.phase("query/global_stats_sync"):
            svec = np.asarray(h.stats, dtype=np.int64)
        # the flushes this merge read are done: the scalars are ready
        if self._tiles_run_dev is not None:
            CAPTURED.count("flush/merge_tiles_run", int(self._tiles_run_dev))
            self._tiles_run_dev = None
        if self._sfs_tiles_run_dev is not None:
            CAPTURED.count("flush/sfs_tiles_run", int(self._sfs_tiles_run_dev))
            self._sfs_tiles_run_dev = None
        counts, surv, g = svec[:P].copy(), svec[P : 2 * P].copy(), int(svec[2 * P])
        if h.dirty is not None:
            self.merge_delta_merges += 1
            drows = h.clean_total + int(counts[h.dirty].sum())
            self.merge_delta_rows += drows
            self._inc("merge.delta_rows", drows)
            if h.explain is not None:
                h.explain.merge["delta_rows"] = drows
                h.explain.merge["clean_rows"] = int(h.clean_total)
        if h.explain is not None and h.explain.merge is not None:
            h.explain.merge["skyline_size"] = g
        pts = None
        if h.use_cache:
            # compact the survivors into the cache buffer even when the
            # caller skipped points: the next delta merge reads them, and a
            # later emit_points hit transfers lazily. Capacity 2*pow2(g)
            # keeps the delta kernel's clean dynamic_slice from ever
            # clamping (lo <= g, clean_active <= pow2(g)). Stored under the
            # handle's LAUNCH-time key: even if flushes landed since, the
            # result correctly describes that epoch's state.
            gcap = 2 * _next_pow2(max(g, 1))
            if h.root_vals is not None:
                pts_dev = tree_points_device(h.root_vals, gcap)
            else:
                pts_dev = global_points_device(h.union, h.keep, gcap)
            self._gm_cache = {
                "key": h.key,
                "epoch": h.epoch.copy(),
                "counts": counts.copy(),
                "surv": surv.copy(),
                "g": g,
                "pts_dev": pts_dev,
                "pts_host": None,
            }
            if h.emit_points:
                pts = self._cached_points()
        elif h.emit_points:
            out_cap = _next_pow2(max(g, 1))
            with self.tracer.phase("query/points_transfer"):
                if h.root_vals is not None:
                    pts_dev = tree_points_device(h.root_vals, out_cap)
                else:
                    pts_dev = global_points_device(h.union, h.keep, out_cap)
                pts = np.asarray(pts_dev)[:g].copy()
        if self.epoch_key == h.key:
            # only refresh the live count bookkeeping when no flush landed
            # between launch and harvest — stale counts would corrupt the
            # capacity upper bounds that size every later bucket
            self._counts_cache = counts.copy()
            self._count_ub = counts.copy()
        return counts, surv, g, pts

    def _merge_delta(self, cache, dirty: np.ndarray):
        """Launch the dirty-subset merge (``global_merge_delta_device``)
        against the cached global points. Returns ``(union, keep, stats,
        union_cap, clean_total)`` — stats packs the CURRENT per-partition
        counts, so the caller's sync/points path is shared with the full
        merge. ``clean_bounds`` rides as a DEVICE array: survivor-layout
        changes between merges then never recompile; only the (recurring)
        dirty pattern and the size buckets are executable keys."""
        surv = cache["surv"]
        bounds = np.concatenate(([0], np.cumsum(surv))).astype(np.int32)
        seg = np.where(dirty, 0, surv)
        clean_total = int(seg.sum())
        clean_active = _active_bucket(max(int(seg.max()), 1))
        active = min(
            self._cap,
            _active_bucket(max(int(self._count_ub[dirty].max()), 1)),
        )
        union_cap = _active_bucket(
            max(clean_total + int(self._count_ub[dirty].sum()), 1)
        )
        union, keep, stats = global_merge_delta_device(
            self.sky,
            self._count_dev,
            cache["pts_dev"],
            jnp.asarray(bounds),
            active,
            clean_active,
            union_cap,
            tuple(bool(b) for b in dirty),
        )
        return union, keep, stats, union_cap, clean_total

    # -- pruned tournament-tree merge --------------------------------------

    def _maybe_launch_summaries(self) -> None:
        """Flush-tail hook: start the per-partition prune summary compute
        (async, tiny) so the next merge's prefilter reads landed bytes
        instead of launching cold. Only when the tree + prefilter are both
        live for this set (``dims > 2``, single device)."""
        if cascade.merge_tree_on(
            self.mesh is not None, self.dims
        ) and cascade.gate("partition_prune"):
            self._launch_summaries()

    def _launch_summaries(self) -> None:
        active = min(
            self._cap, _active_bucket(max(int(self._count_ub.max()), 1))
        )
        self._summary_dev = partition_summaries_device(
            self.sky, self._count_dev, active
        )
        try:
            self._summary_dev.copy_to_host_async()
        except AttributeError:
            pass
        self._summary_epoch = self._epoch.copy()

    def _tree_summaries(self) -> np.ndarray:
        """Host copy of the (P, 2d+2) prune summaries for the CURRENT
        epoch — usually already in flight from the flush-tail launch;
        re-launched (one tiny kernel) when the stamp is stale."""
        if self._summary_epoch is None or not np.array_equal(
            self._summary_epoch, self._epoch
        ):
            self._launch_summaries()
        return np.asarray(self._summary_dev)

    def _prune_mask(self, alive: np.ndarray) -> np.ndarray:
        """The O(P²·d) bound-dominance prefilter (core now in
        ``stream.window.prune_witness_mask`` — see its docstring for the
        soundness argument). Keeps the per-partition witness reasons on
        ``self.last_prune_witness`` for the EXPLAIN plane; the mask itself
        is byte-for-byte the historical one."""
        pruned, self.last_prune_witness = prune_witness_mask(
            self._tree_summaries(), alive, self.dims
        )
        return pruned

    def _merge_tree_full(self, h: _MergeHandle):
        """Assemble + launch the pruned tournament tree over the current
        partition skylines. Returns the packed stats device vector, or
        ``None`` to fall back to the flat union pass (no live leaves)."""
        alive = self._count_ub > 0
        considered = int(alive.sum())
        npruned = 0
        if cascade.gate("partition_prune") and considered > 1:
            pruned = self._prune_mask(alive)
            npruned = int(pruned.sum())
            leaf_mask = alive & ~pruned
            if h.explain is not None:
                wit = self.last_prune_witness
                h.explain.tree = {
                    "pruned": [
                        {"partition": int(b), "witness": int(wit[b])}
                        for b in np.flatnonzero(pruned)
                    ],
                }
        else:
            leaf_mask = alive
        pids = np.flatnonzero(leaf_mask)
        if pids.size == 0:
            return None  # empty set: the flat pass handles the zero state
        leaves = []
        for p in pids:
            w = min(
                self._cap,
                _active_bucket(max(int(self._count_ub[p]), 1)),
            )
            vals, lpids, cnt = extract_sky_leaf(
                self.sky, self._count_dev, int(p), w
            )
            leaves.append((vals, lpids, cnt, int(self._count_ub[p])))
        return self._run_tree(leaves, h, npruned, considered)

    def _merge_tree_delta(self, cache, dirty: np.ndarray, h: _MergeHandle):
        """Delta merge routed through the tree: dirty partitions feed
        their skylines as leaves, clean partitions feed their cached
        global-survivor segments (masked past the true width — the tail
        rows belong to the NEXT partitions' survivors). Leaves stay in
        ascending pid order, so the root is byte-identical to the flat
        delta's compaction. No prefilter here: the witness summaries
        describe partition skylines, not cached survivor segments."""
        surv = cache["surv"]
        bounds = np.concatenate(([0], np.cumsum(surv))).astype(np.int64)
        gpts = cache["pts_dev"]
        leaves = []
        clean_total = 0
        for p in range(self.num_partitions):
            if dirty[p]:
                if self._count_ub[p] <= 0:
                    continue
                w = min(
                    self._cap,
                    _active_bucket(max(int(self._count_ub[p]), 1)),
                )
                vals, lpids, cnt = extract_sky_leaf(
                    self.sky, self._count_dev, int(p), w
                )
                leaves.append((vals, lpids, cnt, int(self._count_ub[p])))
            else:
                sw = int(surv[p])
                if sw <= 0:
                    continue
                clean_total += sw
                w = _active_bucket(sw)
                vals, lpids, cnt = extract_cached_leaf(
                    gpts,
                    jnp.asarray(np.int32(bounds[p])),
                    jnp.asarray(np.int32(sw)),
                    int(p),
                    w,
                )
                leaves.append((vals, lpids, cnt, sw))
        h.clean_total = clean_total
        if not leaves:
            return None
        return self._run_tree(leaves, h, 0, len(leaves))

    def _run_tree(self, leaves, h: _MergeHandle, npruned: int, considered: int):
        """Pair the leaves up a binary tree (adjacent pairs, odd tail
        passes through) so pid order — and therefore byte identity with the
        flat pass's stable compaction — is preserved at every level. Each
        node's capacity covers the sum of its children's count upper
        bounds, so ``compact`` never silently clips."""
        levels = 0
        cand = [len(leaves)]
        nodes = leaves
        while len(nodes) > 1:
            levels += 1
            nxt = []
            for i in range(0, len(nodes) - 1, 2):
                av, ap, ac, aub = nodes[i]
                bv, bp, bc, bub = nodes[i + 1]
                out_cap = _active_bucket(max(aub + bub, 1))
                vals, pids_out, cnt = tree_pair_merge(
                    av, ap, ac, bv, bp, bc, out_cap
                )
                nxt.append((vals, pids_out, cnt, min(aub + bub, out_cap)))
            if len(nodes) % 2:
                nxt.append(nodes[-1])
            nodes = nxt
            cand.append(len(nodes))
        root_vals, root_pids, root_cnt, _ = nodes[0]
        h.root_vals = root_vals
        self.merge_tree_merges += 1
        self.merge_partitions_pruned += npruned
        # inc even when zero so the Prometheus series registers on the
        # first tree merge, not the first nonzero prune
        self._inc("merge.tree_levels", levels)
        self._inc("merge.partitions_pruned", npruned)
        self._fnote(
            "merge.tree", levels=levels, pruned=npruned, considered=considered,
        )
        self.last_tree_info = {
            "levels": levels,
            "partitions_pruned": npruned,
            "candidates_per_level": cand,
            "pruned_fraction": (npruned / considered) if considered else 0.0,
        }
        if h.explain is not None:
            # the prune hook (full path only) may already have stashed the
            # witness rows; fold the tree shape in beside them
            tree = h.explain.tree or {"pruned": []}
            tree.update(self.last_tree_info)
            tree["considered"] = considered
            h.explain.tree = tree
        return tree_stats_device(
            self._count_dev, root_pids, root_cnt, self.num_partitions
        )

    def merge_points_device(self, handle: _MergeHandle, out_cap: int):
        """Device buffer of a HARVESTED merge's global skyline points,
        ``(out_cap, d)`` with rows past the true count +inf-padded — no
        host transfer. The sharded engine's cross-chip tournament feeds
        each chip-local root straight into ``tree_pair_merge`` through
        this, so chip results never round-trip through host memory.

        Valid only between a harvest and the next flush (the caller holds
        the chip's epoch fixed across the two-level merge). Prefers the
        cache-plane buffer when it describes the handle's epoch; otherwise
        compacts the handle's in-flight tree/flat result."""
        h = handle
        cache = self._gm_cache
        if cache is not None and cache["key"] == h.key:
            pts = cache["pts_dev"]
            if pts.shape[0] >= out_cap:
                return pts[:out_cap]
            return jnp.pad(
                pts,
                ((0, out_cap - pts.shape[0]), (0, 0)),
                constant_values=jnp.inf,
            )
        if h.root_vals is not None:
            return tree_points_device(h.root_vals, out_cap)
        return global_points_device(h.union, h.keep, out_cap)

    def _cached_points(self) -> np.ndarray:
        """Host copy of the cached global skyline points, transferred at
        most once per cached merge (later hits reuse the host array)."""
        c = self._gm_cache
        if c["pts_host"] is None:
            with self.tracer.phase("query/points_transfer"):
                c["pts_host"] = np.asarray(c["pts_dev"])[: c["g"]].copy()
        return c["pts_host"].copy()

    def sky_counts(self, phase: str = "query/count_sync") -> np.ndarray:
        """Exact survivor counts (P,) — one device sync (cached until the
        next flush), timed under ``phase`` (the flush's own reads pass
        ``flush/count_sync``)."""
        if self._counts_cache is None:
            with self.tracer.phase(phase):
                self._counts_cache = np.asarray(self._count_dev, dtype=np.int64)
            self._count_ub = self._counts_cache.copy()
        self._tighten_pending = False
        return self._counts_cache

    def _host_sky(self) -> np.ndarray:
        if self._host_cache is None:
            with self.tracer.phase("query/snapshot_transfer"):
                self._host_cache = np.asarray(self.sky)
        return self._host_cache

    def snapshot(self, p: int) -> np.ndarray:
        """Flush pending rows and return partition ``p``'s local skyline
        (k, d) on host — the processQuery path (FlinkSkyline.java:367-403)."""
        self.flush_all()  # times itself; t0 after it avoids double-counting
        t0 = time.perf_counter_ns()
        count = int(self.sky_counts()[p])
        out = self._host_sky()[p, :count].copy()
        self.processing_ns += time.perf_counter_ns() - t0
        return out

    def skyline_host(self, p: int) -> np.ndarray:
        """Partition ``p``'s device skyline pulled to host WITHOUT flushing
        pending rows (checkpointing reads state as-is)."""
        count = int(self.sky_counts()[p])
        return self._host_sky()[p, :count].copy()

    def pending_rows_of(self, p: int) -> np.ndarray:
        """Partition ``p``'s un-flushed pending rows as one (m, d) array."""
        if not self._pending[p]:
            return np.empty((0, self.dims), dtype=np.float32)
        if len(self._pending[p]) == 1:
            return self._pending[p][0]
        return np.concatenate(self._pending[p], axis=0)

    def audit_state(self) -> tuple[list[np.ndarray], list[np.ndarray]]:
        """Off-hot-path state capture for the audit plane: every
        partition's device skyline plus its un-flushed pending rows, as
        host arrays. One bulk device→host transfer (the ``_host_sky``
        cache) — no flush, no merge, no epoch advance, so capturing for a
        shadow check never perturbs the state being checked."""
        if self._host_cache is None:
            with self.tracer.phase("audit/state_sync"):
                self._host_cache = np.asarray(self.sky)
        skies = [self.skyline_host(p) for p in range(self.num_partitions)]
        pendings = [
            self.pending_rows_of(p) for p in range(self.num_partitions)
        ]
        return skies, pendings

    def restore_all(
        self, skies: list[np.ndarray], pendings: list[np.ndarray]
    ) -> None:
        """Checkpoint-restore every partition's skyline + pending buffer in
        one host pass and one device upload.

        ``skies[p]`` rows are assumed mutually non-dominated (they came from
        ``skyline_host``). Replaces ALL existing state, including barrier and
        metrics bookkeeping (reset to fresh; the caller re-applies saved
        values, as ``utils.checkpoint.load_engine`` does).
        """
        assert len(skies) == len(pendings) == self.num_partitions
        # discard any un-flushed device-ingest window (checkpoint saves
        # flush it first, so a restore over live state starts clean)
        self._dev_rows = 0
        self._chunk_stats = []
        self.max_seen_id[:] = -1
        self.start_time_ms = [None] * self.num_partitions
        self.records_seen[:] = 0
        self.processing_ns = 0
        counts = np.array([s.shape[0] for s in skies], dtype=np.int64)
        # honor the configured pre-sizing across restore, so a resumed
        # engine keeps the growth-sync-free capacity the knob promises
        cap = max(
            _next_pow2(max(int(counts.max()), 1)),
            _next_pow2(max(self.initial_capacity, _MIN_CAP)),
        )
        svals = np.full(
            (self.num_partitions, cap, self.dims), np.inf, dtype=np.float32
        )
        svalid = np.zeros((self.num_partitions, cap), dtype=bool)
        for p, sky in enumerate(skies):
            k = sky.shape[0]
            svals[p, :k] = sky
            svalid[p, :k] = True
        self.sky = self._put(svals)
        self.sky_valid = self._put(svalid)
        self._count_dev = self._put(counts.astype(np.int32))
        self._count_ub = counts.copy()
        self._cap = cap
        self._counts_cache = None
        self._host_cache = None
        # restored state is a different world: advance every epoch so any
        # merge cached against the pre-restore state can never be reused
        self._epoch += 1
        self._gm_cache = None
        # the grid prefilter summary described the replaced skylines; the
        # staleness argument (_prefilter_rows) covers EVOLVED state, not a
        # swapped world, so it must go
        self._grid_dev = None
        self._grid_host = None
        self._grid_epoch = None
        self._tighten_pending = False
        for p, pending in enumerate(pendings):
            if pending.shape[0]:
                self._pending[p] = [pending]
                self._pending_rows[p] = pending.shape[0]
            else:
                self._pending[p] = []
                self._pending_rows[p] = 0

    @property
    def processing_ms(self) -> float:
        return self.processing_ns / 1e6


class PartitionView:
    """Per-partition facade over a ``PartitionSet`` — the engine and
    checkpointing address partitions individually while storage stays
    stacked.

    Contract note: ``add_batch`` does NOT auto-flush at the buffer
    threshold. Flush policy belongs to the set (one batched launch for all
    partitions) — the owner must call ``PartitionSet.maybe_flush()`` after
    routing a micro-batch, as ``SkylineEngine.process_records`` does.
    ``snapshot`` still flushes, so query results never miss pending rows
    either way."""

    __slots__ = ("_set", "partition_id")

    def __init__(self, pset: PartitionSet, p: int):
        self._set = pset
        self.partition_id = p

    # bookkeeping fields (read/write, used by the engine's barrier +
    # grid-prefilter paths)
    @property
    def max_seen_id(self) -> int:
        return int(self._set.max_seen_id[self.partition_id])

    @max_seen_id.setter
    def max_seen_id(self, v: int) -> None:
        self._set.max_seen_id[self.partition_id] = v

    @property
    def start_time_ms(self):
        return self._set.start_time_ms[self.partition_id]

    @start_time_ms.setter
    def start_time_ms(self, v) -> None:
        self._set.start_time_ms[self.partition_id] = v

    @property
    def records_seen(self) -> int:
        return int(self._set.records_seen[self.partition_id])

    @records_seen.setter
    def records_seen(self, v: int) -> None:
        self._set.records_seen[self.partition_id] = v

    @property
    def processing_ns(self) -> int:
        return self._set.processing_ns

    @property
    def processing_ms(self) -> float:
        return self._set.processing_ms

    def add_batch(self, values: np.ndarray, max_id: int, now_ms: float) -> None:
        self._set.add_batch(self.partition_id, values, max_id, now_ms)

    def flush(self) -> None:
        self._set.flush_all()

    def snapshot(self) -> np.ndarray:
        return self._set.snapshot(self.partition_id)

    def skyline_host(self) -> np.ndarray:
        return self._set.skyline_host(self.partition_id)

    @property
    def sky_count(self) -> int:
        return int(self._set.sky_counts()[self.partition_id])
