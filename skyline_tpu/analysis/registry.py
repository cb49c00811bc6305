"""Declarative runtime-knob registry — the single source of truth.

Five perf PRs grew ~50 ``SKYLINE_*`` / ``BENCH_*`` environment knobs read
ad hoc via ``os.environ`` across the engine, dispatch, serve plane and
bench harness, each call site with its own parser and its own idea of what
``"false"`` means (``!= "0"`` at one site, ``in ("1", "true", ...)`` at
another). This module declares every knob ONCE — name, type, default,
applicability, RUNBOOK anchor — and owns the only sanctioned readers
(``env_str`` / ``env_bool`` / ``env_int`` / ``env_float``). The knob lint
(``skyline_tpu.analysis.knob_lint``) walks the tree and fails CI on any
``os.environ`` read outside this module, any accessor read of an
undeclared knob, and any declared knob nothing reads (dead).

Parsing contract (the PR-6 unification):

- bool: ``"0" / "false" / "no" / "off"`` (any case) are False,
  ``"1" / "true" / "yes" / "on"`` are True, unset/empty means the
  call-site default, anything else warns once and means the default.
  Every boolean knob in the tree goes through this one parser, so
  ``SKYLINE_MERGE_PRUNE=false`` can no longer silently mean *enabled*
  while ``SKYLINE_EMIT_PER_SLIDE=false`` means disabled.
- int / float: unset/empty means the default; an unparseable value warns
  once and means the default (a typo'd knob must not crash a worker that
  has been ingesting for an hour).

This module must stay stdlib-only and import-light: ``skyline_tpu/
__init__.py`` and the dispatch hot path import it.
"""

from __future__ import annotations

import os
import warnings
from dataclasses import dataclass, field

_TRUTHY = frozenset(("1", "true", "yes", "on"))
_FALSY = frozenset(("0", "false", "no", "off"))

# sentinel: "use the knob's declared default" is deliberately NOT the
# accessor default — call sites state their default explicitly (config.py's
# flag defaults live on JobConfig) and tests assert the two never drift
_UNSET = object()


@dataclass(frozen=True)
class Knob:
    """One declared runtime knob.

    ``default`` is the documented effective value when the variable is
    unset (None = unset-sensitive: the call site branches on presence,
    e.g. SKYLINE_MIXED_PRECISION's backend-dependent auto). ``job_field``
    names the JobConfig dataclass field a flag-backed knob defaults from —
    tests assert registry default == JobConfig field default so the table
    cannot drift. ``external`` marks variables owned by another system
    (JAX, XLA): read through the accessor for lint coverage, but exempt
    from the dead-knob and naming checks.
    """

    name: str
    type: str  # bool | int | float | str | enum
    default: object
    description: str
    applies_to: str
    runbook: str = ""
    choices: tuple = ()
    job_field: str = ""
    external: bool = False

    def __post_init__(self):
        if self.type not in ("bool", "int", "float", "str", "enum"):
            raise ValueError(f"{self.name}: bad type {self.type!r}")
        if self.type == "enum" and not self.choices:
            raise ValueError(f"{self.name}: enum knob needs choices")


def _k(name, type, default, description, applies_to, **kw) -> Knob:
    return Knob(name, type, default, description, applies_to, **kw)


KNOBS: tuple[Knob, ...] = (
    # -- dispatch / engine perf gates (ops/dispatch.py) --------------------
    _k("SKYLINE_RANK_CASCADE", "bool", False,
       "dense-rank dominance cascade for the self-skyline passes "
       "(default off until the hardware A/B lands)", "engine/tpu", runbook="§2"),
    _k("SKYLINE_MERGE_CACHE", "bool", True,
       "epoch-keyed global-merge result cache (repeated triggers launch "
       "zero kernels)", "engine", runbook="§2e"),
    _k("SKYLINE_DELTA_CUTOFF", "float", 0.75,
       "max dirty-partition fraction for the delta-merge path; above it "
       "the full union merge runs", "engine", runbook="§2e"),
    _k("SKYLINE_STAGE_DEPTH", "int", 1,
       "flush rounds staged ahead of the in-flight merge kernel "
       "(0 = no staging, 1 = double buffering)", "engine", runbook="§2e"),
    _k("SKYLINE_MERGE_TREE", "bool", True,
       "pruned tournament-tree global merge for d > 2 (0 = flat union "
       "merge, the A/B baseline)", "engine", runbook="§2f"),
    _k("SKYLINE_MERGE_PRUNE", "bool", True,
       "witness-dominance partition prefilter ahead of the tree merge",
       "engine", runbook="§2f"),
    _k("SKYLINE_FLUSH_PREFILTER", "bool", True,
       "quantized-grid host prefilter ahead of the flush merge kernels",
       "engine", runbook="§2g"),
    _k("SKYLINE_MIXED_PRECISION", "bool", None,
       "bf16 margin pass inside the flush dominance kernels; unset = auto "
       "(on for TPU, off elsewhere — XLA CPU emulates bf16)", "engine",
       runbook="§2g"),
    _k("SKYLINE_CHIP_PRUNE", "bool", True,
       "chip-level witness prefilter in the sharded engine's two-level "
       "merge (a dominated chip never crosses the interconnect)",
       "engine/sharded", runbook="§2n"),
    _k("SKYLINE_CHIP_BARRIER", "enum", "merge",
       "when the sharded engine writes chip-consistency barrier records: "
       "merge (every two-level merge), checkpoint (checkpoint time only), "
       "off (no chip WAL plane)", "engine/sharded",
       choices=("merge", "checkpoint", "off"), runbook="§2n"),
    _k("SKYLINE_CHIP_MERGE_DEADLINE_MS", "float", 0.0,
       "per-chip level-1 merge deadline in the sharded tournament; a chip "
       "that misses it is excluded and the answer publishes marked "
       "partial (0 = unbounded, the byte-identity default)",
       "engine/sharded", runbook="§2p"),
    _k("SKYLINE_CHIP_MERGE_RETRIES", "int", 1,
       "bounded retries per chip inside the merge deadline (transient "
       "faults get a second chance before exclusion)", "engine/sharded",
       runbook="§2p"),
    _k("SKYLINE_CHIP_MERGE_BACKOFF_MS", "float", 50.0,
       "base backoff between per-chip merge retries (doubles per "
       "attempt)", "engine/sharded", runbook="§2p"),
    _k("SKYLINE_CHIP_HEDGE_MS", "float", 0.0,
       "straggler hedge: launch a second attempt for a chip still "
       "running after this many ms (0 = no hedging)", "engine/sharded",
       runbook="§2p"),
    _k("SKYLINE_CHIP_FAILOVER", "bool", True,
       "online partition-group failover: a quarantined chip's group is "
       "re-owned by a healthy chip at the next merge launch",
       "engine/sharded", runbook="§2p"),
    _k("SKYLINE_CHIP_FAILOVER_LOCK_MS", "float", 5000.0,
       "bounded wait for a chip's merge lock before failover captures "
       "its group state (an in-flight merge attempt must drain first; "
       "past the bound failover defers to the next tick)",
       "engine/sharded", runbook="§2p"),
    _k("SKYLINE_QUERY_OVERLAP", "bool", True,
       "overlapped query sync: launch the global merge at trigger time, "
       "harvest at emission", "engine", runbook="§2f"),
    _k("SKYLINE_PALLAS_INTERPRET", "bool", False,
       "run the Pallas kernels in interpret mode on CPU (lowering "
       "validation without TPU hardware)", "kernels/test"),
    _k("SKYLINE_SORTED_SFS", "enum", "auto",
       "sorted-order SFS dominance cascade for d>2 on non-TPU backends: "
       "auto (per-(d,N,backend) choice from measured KernelProfiler wall "
       "data), on (force the sorted host path), off (device kernels only)",
       "engine", choices=("auto", "on", "off"), runbook="§2m"),
    _k("SKYLINE_SORTED_SFS_BLOCK", "int", 8192,
       "max scan-block width of the sorted SFS cascade (the exact "
       "in-block pairwise tile; blocks start at 1024 and double up to "
       "this)", "engine", runbook="§2m"),
    _k("SKYLINE_DEVICE_CASCADE", "enum", "auto",
       "device-side sorted dominance cascade (jit-safe, TPU + traced "
       "paths): auto (per-(d,N,backend,mp) choice from measured "
       "KernelProfiler wall data), on (force the cascade, including "
       "under trace), off (quadratic device kernels only)",
       "engine", choices=("auto", "on", "off"), runbook="§2t"),
    _k("SKYLINE_DEVICE_CASCADE_BLOCK", "int", 2048,
       "scan block size of the device cascade (buffer chunks, in-block "
       "pairwise tiles, and ambiguous-band tiles; rounded to a power of "
       "two, floored at 1024 on the Pallas path)", "engine",
       runbook="§2t"),
    # -- utils -------------------------------------------------------------
    # -- multihost ---------------------------------------------------------
    _k("SKYLINE_COORDINATOR", "str", None,
       "jax.distributed coordinator address for multi-host runs",
       "parallel/multihost"),
    _k("SKYLINE_NUM_PROCESSES", "int", None,
       "jax.distributed process count (None = auto-detect)",
       "parallel/multihost"),
    _k("SKYLINE_PROCESS_ID", "int", None,
       "jax.distributed process id (None = auto-detect)",
       "parallel/multihost"),
    # -- job flags (utils/config.py; SKYLINE_<FLAG> overrides the default,
    #    the CLI flag overrides both; defaults live on JobConfig) ----------
    _k("SKYLINE_PARALLELISM", "int", 4, "worker parallelism", "job flag",
       job_field="parallelism"),
    _k("SKYLINE_ALGO", "str", "mr-angle", "partitioner algorithm",
       "job flag", job_field="algo"),
    _k("SKYLINE_INPUT_TOPIC", "str", "input-tuples", "input topic",
       "job flag", job_field="input_topic"),
    _k("SKYLINE_QUERY_TOPIC", "str", "queries", "query topic", "job flag",
       job_field="query_topic"),
    _k("SKYLINE_OUTPUT_TOPIC", "str", "output-skyline", "output topic",
       "job flag", job_field="output_topic"),
    _k("SKYLINE_DOMAIN", "float", 1000.0, "domain max per dimension",
       "job flag", job_field="domain"),
    _k("SKYLINE_DIMS", "int", 2, "tuple dimensionality", "job flag",
       job_field="dims"),
    _k("SKYLINE_BOOTSTRAP", "str", "localhost:9092",
       "Kafka bootstrap address", "job flag", job_field="bootstrap"),
    _k("SKYLINE_BUFFER_SIZE", "int", 4096, "per-partition buffer size",
       "job flag", job_field="buffer_size"),
    _k("SKYLINE_EMIT_SKYLINE_POINTS", "bool", False,
       "include skyline points in result JSON", "job flag",
       job_field="emit_skyline_points"),
    _k("SKYLINE_QUERY_TIMEOUT_MS", "float", 0.0,
       "finalize overdue queries as partial results (0 = wait forever)",
       "job flag", job_field="query_timeout_ms"),
    _k("SKYLINE_GRID_PREFILTER", "bool", False,
       "domain-midpoint dominance prefilter (the reference's disabled "
       "GridDominanceFilter, barrier-safe)", "job flag",
       job_field="grid_prefilter"),
    _k("SKYLINE_INITIAL_CAPACITY", "int", 0,
       "pre-size per-partition skyline buffers", "job flag",
       job_field="initial_capacity"),
    _k("SKYLINE_FLUSH_POLICY", "enum", "incremental", "flush policy",
       "job flag", choices=("incremental", "lazy", "overlap"),
       job_field="flush_policy"),
    _k("SKYLINE_OVERLAP_ROWS", "int", 262144,
       "rows between automatic flushes under flush-policy overlap",
       "job flag", job_field="overlap_rows"),
    _k("SKYLINE_INGEST", "enum", "auto",
       "where routing/sort/block assembly runs", "job flag",
       choices=("auto", "host", "device"), job_field="ingest"),
    _k("SKYLINE_MESH", "int", 0,
       "shard partitions over this many devices (0 = single device)",
       "job flag", job_field="mesh"),
    _k("SKYLINE_MESH_CHIPS", "int", 0,
       "sharded streaming engine: split partitions into this many per-chip "
       "groups with a two-level tournament merge (0 = single device)",
       "job flag", runbook="§2n", job_field="mesh_chips"),
    _k("SKYLINE_STATS_PORT", "int", 0,
       "serve live /stats JSON on this port (0 = off)", "job flag",
       runbook="§2b", job_field="stats_port"),
    _k("SKYLINE_WINDOW", "int", 0,
       "sliding-window size in tuples (0 = unbounded)", "job flag",
       runbook="§2c", job_field="window_size"),
    _k("SKYLINE_SLIDE", "int", 0, "slide in tuples (with SKYLINE_WINDOW)",
       "job flag", runbook="§2c", job_field="slide"),
    _k("SKYLINE_EMIT_PER_SLIDE", "bool", False,
       "emit one result JSON per completed slide", "job flag",
       runbook="§2c", job_field="emit_per_slide"),
    _k("SKYLINE_MAX_DRAIN_POLLS", "int", 256,
       "cap on trigger-pending data re-polls per worker step", "job flag",
       job_field="max_drain_polls"),
    _k("SKYLINE_SERVE", "int", -1,
       "query-serving plane port (-1 = off, 0 = pick a free port)",
       "job flag", runbook="§2d", job_field="serve_port"),
    _k("SKYLINE_SERVE_READ_RATE", "float", 0.0,
       "snapshot-read token rate per second (0 = unlimited)", "job flag",
       runbook="§2d", job_field="serve_read_rate"),
    _k("SKYLINE_SERVE_READ_BURST", "int", 256,
       "snapshot-read token bucket capacity", "job flag", runbook="§2d",
       job_field="serve_read_burst"),
    _k("SKYLINE_SERVE_MAX_QUERIES", "int", 2,
       "concurrent forced merges (POST /query)", "job flag",
       runbook="§2d", job_field="serve_max_queries"),
    _k("SKYLINE_SERVE_QUERY_QUEUE", "int", 8,
       "queued forced merges beyond the concurrent cap", "job flag",
       runbook="§2d", job_field="serve_query_queue"),
    _k("SKYLINE_SERVE_QUERY_DEADLINE_MS", "float", 10_000.0,
       "deadline for an admitted forced merge", "job flag", runbook="§2d",
       job_field="serve_query_deadline_ms"),
    _k("SKYLINE_SERVE_DELTA_RING", "int", 128,
       "snapshot transitions kept for /deltas catch-up", "job flag",
       runbook="§2d", job_field="serve_delta_ring"),
    _k("SKYLINE_SERVE_HISTORY", "int", 64,
       "snapshot versions retained in the store", "job flag",
       runbook="§2d", job_field="serve_history"),
    _k("SKYLINE_SERVE_READ_CACHE", "int", 64,
       "serialized-response LRU entries (0 disables)", "job flag",
       runbook="§2e", job_field="serve_read_cache"),
    _k("SKYLINE_SERVE_READY_TIMEOUT_S", "float", 10.0,
       "startup wait for the serving loop to bind its socket", "serve",
       runbook="§2d"),
    _k("SKYLINE_SERVE_SHUTDOWN_TIMEOUT_S", "float", 10.0,
       "close() wait for the serving loop thread to drain", "serve",
       runbook="§2d"),
    _k("SKYLINE_SERVE_HEADER_TIMEOUT_S", "float", 10.0,
       "per-connection wait for a complete request header block", "serve",
       runbook="§2d"),
    _k("SKYLINE_SERVE_SSE_QUEUE", "int", 64,
       "per-subscriber event queue for GET /subscribe; a subscriber that "
       "falls further behind is drained and sent a resync event", "serve",
       runbook="§2q"),
    _k("SKYLINE_SERVE_TENANT_RATE", "float", 0.0,
       "per-tenant snapshot-read token rate per second, keyed on the "
       "X-Tenant header (0 = no per-tenant limit)", "job flag",
       runbook="§2q", job_field="serve_tenant_rate"),
    _k("SKYLINE_SERVE_TENANT_BURST", "int", 64,
       "per-tenant snapshot-read token bucket capacity", "job flag",
       runbook="§2q", job_field="serve_tenant_burst"),
    _k("SKYLINE_REPLICAS", "int", 0,
       "WAL-tailing read replicas spawned in-process by the worker "
       "(requires --checkpoint-dir and --serve)", "job flag",
       runbook="§2q", job_field="replicas"),
    _k("SKYLINE_REPLICA_OF", "str", "",
       "run as a standalone read replica tailing this WAL directory "
       "instead of a worker (mutually exclusive with --replicas)",
       "job flag", runbook="§2q", job_field="replica_of"),
    _k("SKYLINE_CLUSTER_HOSTS", "int", 0,
       "multi-host cluster ingest: partition the stream across this many "
       "host-level partition groups with a third (host) tournament merge "
       "level (0 = single host)", "job flag", runbook="§2r",
       job_field="cluster_hosts"),
    _k("SKYLINE_CLUSTER_LEASE_TTL_MS", "float", 3000.0,
       "write-lease time-to-live: the primary must renew within this "
       "window or the ClusterSupervisor fences its epoch and promotes "
       "the most-caught-up replica", "cluster", runbook="§2r"),
    _k("SKYLINE_CLUSTER_LEASE_RENEW_MS", "float", 0.0,
       "primary lease renew cadence (0 = TTL/3); must be well under the "
       "TTL or the primary deposes itself", "cluster", runbook="§2r"),
    _k("SKYLINE_CLUSTER_HOST_PRUNE", "bool", True,
       "host-level witness prefilter in the cluster merge: a host whose "
       "summary is witness-dominated ships zero rows to the coordinator "
       "(byte-identical either way)", "cluster", runbook="§2r"),
    _k("SKYLINE_CLUSTER_MIGRATION_BUDGET", "int", 8,
       "max live partition-group migrations between hosts per coordinator "
       "lifetime (drain/checkpoint-slice/restore/fence cycles); guards "
       "against health-signal flapping thrashing state", "cluster",
       runbook="§2r"),
    _k("SKYLINE_REPLICA_MAX_STALE_MS", "float", 30_000.0,
       "replica staleness fence: reads whose snapshot is older than this "
       "are refused with 503 + Retry-After instead of served silently "
       "stale", "serve", runbook="§2q"),
    _k("SKYLINE_REPLICA_POLL_MS", "float", 25.0,
       "replica WAL tail poll interval when no new frames are available",
       "serve", runbook="§2q"),
    _k("SKYLINE_BODYSTORE", "bool", True,
       "zero-copy body store: serialize wire bodies once at publish time "
       "and serve them via fence-checked buffer handoffs (primary retained "
       "bytes; replicas map the primary's bodystore.dat)", "serve",
       runbook="§2u"),
    _k("SKYLINE_BODYSTORE_BYTES", "int", 8 << 20,
       "body-store data ring capacity in bytes; bodies larger than this "
       "skip the mmap (in-process retained bytes still serve them)",
       "serve", runbook="§2u"),
    _k("SKYLINE_BODYSTORE_SLOTS", "int", 512,
       "body-store directory slots ((version, format) keys live at "
       "(version*5+fmt) mod slots)", "serve", runbook="§2u"),
    _k("SKYLINE_BODYSTORE_RETRIES", "int", 4,
       "bounded seqlock retries per body-store read before declaring a "
       "miss and falling back to Python serialization", "serve",
       runbook="§2u"),
    _k("SKYLINE_BODYSTORE_KEEP", "int", 4,
       "snapshot versions whose wire bodies the primary retains in-process "
       "(zero-copy dict hits; older versions fall through to the mmap "
       "ring)", "serve", runbook="§2u"),
    _k("SKYLINE_BODYSTORE_NATIVE", "bool", True,
       "use the native sky_format_rows row serializer for body encoding "
       "(0 forces the byte-identical pure-Python encoders)", "serve",
       runbook="§2u"),
    _k("SKYLINE_BODYSTORE_VERIFY", "bool", False,
       "verify EVERY native-encoded body against the Python encoder "
       "(default verifies only the first per process); mismatch disables "
       "the native path", "serve", runbook="§2u"),
    _k("SKYLINE_TRACE_OUT", "str", "",
       "write the span ring as Chrome trace-event JSON on shutdown",
       "job flag", runbook="§2b", job_field="trace_out"),
    _k("SKYLINE_TRACE_RING", "int", 4096, "span ring capacity",
       "job flag", runbook="§2b", job_field="trace_ring"),
    _k("SKYLINE_JAX_PROFILE_DIR", "str", "",
       "wrap each forced-query injection in jax.profiler.trace",
       "job flag", runbook="§2b", job_field="jax_profile_dir"),
    _k("SKYLINE_CHECKPOINT_DIR", "str", "",
       "enable crash safety: WAL + periodic checkpoints under this "
       "directory (empty = off)", "job flag", runbook="§2i",
       job_field="checkpoint_dir"),
    _k("SKYLINE_CHECKPOINT_INTERVAL_S", "float", 30.0,
       "seconds between automatic checkpoints (0 = only on clean "
       "shutdown / manual)", "job flag", runbook="§2i",
       job_field="checkpoint_interval_s"),
    _k("SKYLINE_CHECKPOINT_RETAIN", "int", 3,
       "checkpoints kept on disk (older ones pruned)", "job flag",
       runbook="§2i", job_field="checkpoint_retain"),
    _k("SKYLINE_WAL_FSYNC", "enum", "batch",
       "WAL durability: always (per append), batch (per worker step), "
       "off (OS page cache only)", "job flag",
       choices=("always", "batch", "off"), runbook="§2i",
       job_field="wal_fsync"),
    _k("SKYLINE_WAL_SEGMENT_BYTES", "int", 4_194_304,
       "WAL segment rotation size", "job flag", runbook="§2i",
       job_field="wal_segment_bytes"),
    _k("SKYLINE_WAL_TAILER_TTL_S", "float", 600.0,
       "staleness TTL on replica tail acks: barrier() keeps segments a "
       "live tailer hasn't consumed, but an ack older than this stops "
       "pinning retention (dead replica protection)", "resilience",
       runbook="§2q"),
    # -- resilience runtime (skyline_tpu/resilience) -----------------------
    _k("SKYLINE_FAULT_PLAN", "str", None,
       "deterministic fault-injection plan, e.g. crash@flush.pre_merge:3 "
       "(comma-separated action@point:nth clauses; actions: crash, exit, "
       "corrupt, slow, hang; chip-scopable as point#chip; test/chaos use "
       "only)", "resilience", runbook="§2i"),
    _k("SKYLINE_FAULT_SLOW_MS", "float", 250.0,
       "injected delay of a slow@ fault clause", "resilience",
       runbook="§2p"),
    _k("SKYLINE_FAULT_HANG_S", "float", 3600.0,
       "cap on a hang@ fault clause (the hung thread parks on an event "
       "released by faults.clear())", "resilience", runbook="§2p"),
    _k("SKYLINE_CHIP_FAIL_THRESHOLD", "int", 1,
       "consecutive per-chip merge failures/timeouts before quarantine",
       "resilience", runbook="§2p"),
    _k("SKYLINE_CHIP_QUARANTINE_SCORE", "float", 0.5,
       "health score below which a chip quarantines (scores decay on "
       "failure/straggle, recover on clean merges)", "resilience",
       runbook="§2p"),
    _k("SKYLINE_CHIP_STRAGGLER_FACTOR", "float", 4.0,
       "a chip's level-1 wall beyond this multiple of the peer-EMA "
       "median counts as a straggle (after a warmup of clean merges)",
       "resilience", runbook="§2p"),
    _k("SKYLINE_CHIP_HEARTBEAT_MS", "float", 30000.0,
       "per-chip heartbeat staleness limit for the health tick "
       "(relative: the whole fleet idling does not quarantine anyone)",
       "resilience", runbook="§2p"),
    _k("SKYLINE_SUPERVISOR_MAX_RESTARTS", "int", 5,
       "supervised-restart budget before giving up", "resilience",
       runbook="§2i"),
    _k("SKYLINE_SUPERVISOR_BACKOFF_S", "float", 0.5,
       "base restart backoff (doubles per crash, plus jitter)",
       "resilience", runbook="§2i"),
    _k("SKYLINE_SUPERVISOR_BACKOFF_CAP_S", "float", 30.0,
       "restart backoff ceiling", "resilience", runbook="§2i"),
    _k("SKYLINE_KAFKA_RETRIES", "int", 5,
       "kafkalite transport reconnect attempts per request", "bridge",
       runbook="§2i"),
    _k("SKYLINE_KAFKA_BACKOFF_S", "float", 0.05,
       "base kafkalite reconnect backoff (doubles per attempt)", "bridge",
       runbook="§2i"),
    # -- observability (skyline_tpu/telemetry) -----------------------------
    _k("SKYLINE_FRESHNESS", "bool", True,
       "event-time freshness lineage: per-stage lag histograms "
       "(ingest/flush/merge/publish/read) and staleness_ms on /skyline",
       "telemetry", runbook="§2j"),
    _k("SKYLINE_KERNEL_PROFILE", "bool", True,
       "per-dispatch-signature kernel profiler behind GET /profile",
       "telemetry", runbook="§2j"),
    _k("SKYLINE_PROFILE_COST", "bool", False,
       "capture XLA cost_analysis() FLOPs/bytes once per signature via an "
       "AOT lower+compile (expensive; profiling sessions only)",
       "telemetry", runbook="§2j"),
    _k("SKYLINE_FLIGHT_RING", "int", 256,
       "flight-recorder ring capacity (last N engine decisions, "
       "/debug/flight and the crash dump)", "telemetry", runbook="§2j"),
    _k("SKYLINE_EXPLAIN", "bool", True,
       "per-query EXPLAIN plane: a causal QueryPlan per trigger (merge "
       "path, prune witnesses, cascade + kernel attribution, publish "
       "watermark) behind GET /explain and /skyline?explain=1",
       "telemetry", runbook="§2k"),
    _k("SKYLINE_EXPLAIN_RING", "int", 256,
       "EXPLAIN plan ring capacity (last N finalized query plans)",
       "telemetry", runbook="§2k"),
    _k("SKYLINE_SLO_FAST_WINDOW_S", "float", 300.0,
       "fast burn-rate window for the /slo evaluation", "telemetry/slo",
       runbook="§2j"),
    _k("SKYLINE_SLO_SLOW_WINDOW_S", "float", 3600.0,
       "slow burn-rate window for the /slo evaluation", "telemetry/slo",
       runbook="§2j"),
    _k("SKYLINE_SLO_READ_P99_MS", "float", 50.0,
       "SLO target: serve read p99 latency threshold", "telemetry/slo",
       runbook="§2j"),
    _k("SKYLINE_SLO_FRESH_P99_MS", "float", 5000.0,
       "SLO target: read-stage freshness lag p99 threshold",
       "telemetry/slo", runbook="§2j"),
    _k("SKYLINE_SLO_SHED_FRACTION", "float", 0.05,
       "SLO target: max fraction of snapshot reads shed by admission",
       "telemetry/slo", runbook="§2j"),
    _k("SKYLINE_SLO_RESTARTS_PER_HOUR", "float", 6.0,
       "SLO target: supervised-restart rate ceiling", "telemetry/slo",
       runbook="§2j"),
    _k("SKYLINE_AUDIT", "bool", True,
       "online audit plane: sampled shadow verification of published "
       "snapshots against the host oracle, divergence repro bundles, and "
       "correctness canaries behind GET /audit", "audit", runbook="§2l"),
    _k("SKYLINE_AUDIT_SAMPLE", "float", 1.0,
       "fraction of published snapshots shadow-verified (deterministic "
       "accumulator, not random; 0 disables organic checks)", "audit",
       runbook="§2l"),
    _k("SKYLINE_AUDIT_RING", "int", 256,
       "audit check-record ring capacity (last N verdicts on /audit)",
       "audit", runbook="§2l"),
    _k("SKYLINE_AUDIT_DIR", "str", "artifacts/audit",
       "divergence repro-bundle directory (checkpoint + WAL slice + "
       "EXPLAIN plan + knob snapshot + both skylines)", "audit",
       runbook="§2l"),
    _k("SKYLINE_AUDIT_CANARY_S", "float", 300.0,
       "seconds between synthetic known-answer canary sweeps over every "
       "merge path while the worker is idle (0 = off)", "audit",
       runbook="§2l"),
    _k("SKYLINE_AUDIT_ORACLE", "enum", "sorted",
       "host oracle the auditor verifies answers against: sorted "
       "(dedup + sum-sorted scan, full-rate affordable) or quadratic "
       "(the O(n²d) oracle-of-the-oracle kept for tests)", "audit",
       choices=("sorted", "quadratic"), runbook="§2l"),
    _k("SKYLINE_SLO_AUDIT_DIVERGENCE", "float", 0.0001,
       "SLO target: max fraction of audited snapshots diverging from the "
       "host oracle", "telemetry/slo", runbook="§2l"),
    _k("SKYLINE_SLO_DEGRADED_ANSWERS", "float", 0.01,
       "SLO target: max fraction of answered queries published "
       "chip-degraded (marked partial)", "telemetry/slo", runbook="§2p"),
    _k("SKYLINE_SLO_TENANT_SHED", "float", 0.05,
       "SLO target: max fraction of tenant-attributed read attempts shed "
       "by the per-tenant buckets", "telemetry/slo", runbook="§2q"),
    _k("SKYLINE_SLO_REPLICATION_LAG_P99_MS", "float", 2000.0,
       "SLO target: 99% of replica WAL-fold applications land within this "
       "many ms of the frame's publish time (the staleness a failover "
       "would inherit)", "telemetry/slo", runbook="§2s"),
    _k("SKYLINE_SLO_PROMOTE_P99_MS", "float", 1000.0,
       "SLO target: 99% of supervisor promotions (fence raise to replica "
       "serving) complete within this many ms", "telemetry/slo",
       runbook="§2s"),
    _k("SKYLINE_OPSLOG", "bool", True,
       "durable cross-process ops journal beside the WAL: control-plane "
       "transitions (lease/fence/promote/demote/quarantine/migrate/"
       "degraded publish) as CRC-framed records, GET /ops on both HTTP "
       "surfaces", "telemetry/ops", runbook="§2s"),
    _k("SKYLINE_OPSLOG_FSYNC", "enum", "off",
       "ops-journal durability policy: 'off' relies on one unbuffered "
       "write per record (survives process death), 'always' fsyncs every "
       "record (power-loss durable, ~ms each), 'batch' fsyncs on flush()",
       "telemetry/ops", choices=("always", "batch", "off"), runbook="§2s"),
    _k("SKYLINE_OPSLOG_MAX_BYTES", "int", 8_388_608,
       "per-incarnation ops-journal size cap; past it records are dropped "
       "and counted (ops.dropped), never raised", "telemetry/ops",
       runbook="§2s"),
    _k("SKYLINE_CLUSTERVIEW_MEMBERS", "str", None,
       "comma-separated member base URLs the fleet-wide aggregation view "
       "scrapes for GET /cluster/overview (and the clusterview CLI "
       "default)", "telemetry/ops", runbook="§2s"),
    _k("SKYLINE_CLUSTERVIEW_TIMEOUT_S", "float", 2.0,
       "per-request timeout when the clusterview scraper polls a member's "
       "/metrics, /cluster, /healthz, /ops", "telemetry/ops",
       runbook="§2s"),
    _k("SKYLINE_CLUSTERVIEW_OPS_TAIL", "int", 64,
       "ops-journal records the clusterview scraper pulls per member "
       "(?limit= on each member's /ops)", "telemetry/ops", runbook="§2s"),
    _k("SKYLINE_FLEET", "bool", True,
       "per-chip fleet plane on the sharded engine: skyline_chip_* "
       "labeled metric families, imbalance index + skew ring, per-chip "
       "tournament spans, and GET /fleet", "telemetry", runbook="§2o"),
    _k("SKYLINE_FLEET_IMBALANCE_THRESHOLD", "float", 2.0,
       "imbalance index (max/mean chip ingest load) above which a "
       "fleet.imbalance flight-recorder entry fires (edge-triggered per "
       "excursion)", "telemetry", runbook="§2o"),
    _k("SKYLINE_FLEET_RING", "int", 64,
       "rolling skew ring capacity (per-merge imbalance samples behind "
       "the skew score)", "telemetry", runbook="§2o"),
    _k("SKYLINE_WORKLOAD", "bool", True,
       "streaming workload characterizer: per-dim quantile sketches, "
       "correlation estimate, uniform/correlated/anti_correlated "
       "classification, drift detection; the regime tag on every EXPLAIN "
       "plan", "telemetry", runbook="§2o"),
    _k("SKYLINE_WORKLOAD_EPOCH_ROWS", "int", 4096,
       "sampled rows per characterizer epoch (classification + drift "
       "check cadence)", "telemetry", runbook="§2o"),
    _k("SKYLINE_WORKLOAD_SAMPLE_CAP", "int", 512,
       "max rows sampled per ingest batch (deterministic stride, no RNG)",
       "telemetry", runbook="§2o"),
    _k("SKYLINE_WORKLOAD_RING", "int", 64,
       "epoch-summary and query-trajectory ring capacity", "telemetry",
       runbook="§2o"),
    _k("SKYLINE_WORKLOAD_SUM_RATIO", "float", 0.5,
       "row-sum variance ratio below which the stream classifies "
       "anti_correlated (constant-sum band; 1.0 = independent dims)",
       "telemetry", runbook="§2o"),
    _k("SKYLINE_WORKLOAD_CORR_THRESHOLD", "float", 0.25,
       "mean pairwise correlation above which the stream classifies "
       "correlated (subject to the dispersion tiebreak)", "telemetry",
       runbook="§2o"),
    _k("SKYLINE_WORKLOAD_DISP_THRESHOLD", "float", 0.27,
       "within-row coefficient-of-variation above which a positively "
       "correlated stream reclassifies as wide-band anti_correlated "
       "(shared per-row scale)", "telemetry", runbook="§2o"),
    _k("SKYLINE_WORKLOAD_DRIFT_THRESHOLD", "float", 0.2,
       "per-dim p50 shift (normalized by the frozen sketch range) beyond "
       "which consecutive epochs count as drift", "telemetry",
       runbook="§2o"),
    # -- closed-loop dispatch tuner (telemetry/tuner.py, ops/cascade.py) ---
    _k("SKYLINE_TUNER", "bool", True,
       "closed-loop dispatch tuner over the cascade table: pins measured "
       "EMA winners per signature and retunes table-scoped knobs per "
       "workload regime (0 = static dispatch, the A/B baseline)",
       "engine", runbook="§2v"),
    _k("SKYLINE_TUNER_EPOCH_S", "float", 5.0,
       "min seconds between controller epochs (the tuner is also passive "
       "until the first workload epoch closes)", "engine", runbook="§2v"),
    _k("SKYLINE_TUNER_HYSTERESIS", "int", 2,
       "consecutive controller epochs a new workload regime must persist "
       "before the tuner switches context (drift-flip damping)",
       "engine", runbook="§2v"),
    _k("SKYLINE_TUNER_MAX_MOVES", "int", 2,
       "max pin/knob moves per controller epoch (bounded-move rule)",
       "engine", runbook="§2v"),
    _k("SKYLINE_TUNER_CUTOFF_STEP", "float", 0.1,
       "max delta-cutoff movement per controller epoch when steering "
       "toward the observed dirty-fraction quantile", "engine",
       runbook="§2v"),
    _k("SKYLINE_TUNER_EXPLORE_ON_DRIFT", "bool", True,
       "on a confirmed regime switch with no banked state, reset the "
       "mask/flush profiler signatures so the variant race re-runs under "
       "the new distribution", "engine", runbook="§2v"),
    # -- external (owned by JAX/XLA; declared for lint coverage) -----------
    _k("JAX_PLATFORMS", "str", None, "JAX backend selection (external)",
       "external", external=True),
    _k("XLA_FLAGS", "str", None, "XLA runtime flags (external)",
       "external", external=True),
    _k("JAX_COMPILATION_CACHE_DIR", "str", None,
       "JAX persistent compilation cache directory (external; when unset "
       "the repo uses <checkout>/.jax_cache)", "external", external=True),
)

_BY_NAME: dict[str, Knob] = {k.name: k for k in KNOBS}
if len(_BY_NAME) != len(KNOBS):  # duplicate declaration is a bug, not data
    raise RuntimeError("duplicate knob declaration in KNOBS")

_warned: set[str] = set()


def knob(name: str) -> Knob:
    """The declaration behind ``name`` (raises LookupError if undeclared —
    the runtime mirror of the knob lint's undeclared-knob rule)."""
    try:
        return _BY_NAME[name]
    except KeyError:
        raise LookupError(
            f"env knob {name!r} is not declared in "
            "skyline_tpu.analysis.registry.KNOBS"
        ) from None


def knob_names() -> tuple[str, ...]:
    return tuple(_BY_NAME)


def _warn_once(name: str, raw: str, why: str) -> None:
    if name not in _warned:
        _warned.add(name)
        warnings.warn(
            f"ignoring {name}={raw!r}: {why}; using the default",
            RuntimeWarning,
            stacklevel=3,
        )


def _raw(name: str) -> str | None:
    knob(name)  # undeclared reads fail fast, even at runtime
    return os.environ.get(name)


def env_str(name: str, default=None):
    """String knob: unset or empty means ``default``."""
    v = _raw(name)
    if v is None or v == "":
        return default
    return v


def parse_bool(raw: str | None, default=False):
    """THE boolean parse. ``"0"/"false"/"no"/"off"`` (any case) are False;
    ``"1"/"true"/"yes"/"on"`` are True; unset/empty/unrecognized mean
    ``default`` (which may be None for unset-sensitive tri-state knobs)."""
    if raw is None:
        return default
    s = raw.strip().lower()
    if s == "" or (s not in _FALSY and s not in _TRUTHY):
        return default
    return s in _TRUTHY


def env_bool(name: str, default=False):
    v = _raw(name)
    if v is not None and v.strip() != "":
        s = v.strip().lower()
        if s not in _FALSY and s not in _TRUTHY:
            _warn_once(name, v, "not a recognized boolean")
    return parse_bool(v, default)


def env_int(name: str, default=0):
    v = _raw(name)
    if v is None or v.strip() == "":
        return default
    try:
        return int(v)
    except ValueError:
        _warn_once(name, v, "not an integer")
        return default


def env_float(name: str, default=0.0):
    v = _raw(name)
    if v is None or v.strip() == "":
        return default
    try:
        return float(v)
    except ValueError:
        _warn_once(name, v, "not a number")
        return default


# accessor names the knob lint recognizes as sanctioned read sites
ACCESSORS = ("env_str", "env_bool", "env_int", "env_float")


def _fmt_default(k: Knob) -> str:
    if k.default is None:
        return "unset"
    if k.type == "bool":
        return "on" if k.default else "off"
    return repr(k.default) if isinstance(k.default, str) else str(k.default)


def knob_doc_markdown() -> str:
    """The autogenerated knob table (``--knob-doc`` writes it to
    docs/KNOBS.md; ``--check-doc`` fails CI on drift)."""
    lines = [
        "# Runtime knobs",
        "",
        "Autogenerated by `python -m skyline_tpu.analysis --knob-doc` from",
        "`skyline_tpu/analysis/registry.py` — edit the registry, not this",
        "file (`--check-doc` fails CI on drift).",
        "",
        "Boolean knobs share one parser: `0/false/no/off` disable,",
        "`1/true/yes/on` enable, unset/empty/unrecognized mean the default.",
        "",
        "| Knob | Type | Default | Applies to | RUNBOOK | Description |",
        "|---|---|---|---|---|---|",
    ]
    for k in KNOBS:
        typ = k.type if not k.choices else "enum(" + "\\|".join(k.choices) + ")"
        lines.append(
            f"| `{k.name}` | {typ} | {_fmt_default(k)} | {k.applies_to} "
            f"| {k.runbook or '—'} | {k.description} |"
        )
    lines.append("")
    lines.append(f"{len(KNOBS)} knobs declared.")
    lines.append("")
    return "\n".join(lines)
