"""Unified telemetry plane: histograms, per-query spans, Prometheus export.

This package absorbs and extends ``skyline_tpu/metrics`` (which keeps the
reference-parity pieces: the result-CSV collector, ``Counters``, the
phase-total ``Tracer``, and the /stats HTTP server) with the three pillars
the serving north star needs:

- ``histogram.Histogram`` — lock-cheap fixed-bucket latency distributions
  (ingest batch, query latency, global merge, serve reads) with p50/p90/p99
  estimation; the single percentile implementation the plane reports.
- ``spans.SpanRecorder`` — a bounded ring of per-query spans keyed by a
  ``trace_id`` minted at trigger ingestion, exportable as Chrome
  trace-event JSON (``GET /trace``, ``--trace-out``) for Perfetto.
- ``prometheus.render`` — standard text exposition behind ``GET /metrics``
  on both the stats and serving servers.

``Telemetry`` bundles all three plus a ``Counters`` instance so the worker,
engine, and both HTTP servers share one hub object.
"""

from __future__ import annotations

import threading

from skyline_tpu.metrics.collector import Counters
from skyline_tpu.metrics.tracing import NULL_TRACER, Tracer
from skyline_tpu.telemetry.histogram import DEFAULT_EDGES, Histogram
from skyline_tpu.telemetry.prometheus import (
    CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE,
)
from skyline_tpu.telemetry.audit import AuditRecorder
from skyline_tpu.telemetry.explain import ExplainRecorder, QueryPlan
from skyline_tpu.telemetry.fleet import FleetStats, fleet_doc
from skyline_tpu.telemetry.freshness import FreshnessTracker
from skyline_tpu.telemetry.profiler import FlightRecorder, KernelProfiler
from skyline_tpu.telemetry.prometheus import flatten_gauges
from skyline_tpu.telemetry.prometheus import render as render_prometheus
from skyline_tpu.telemetry.slo import SloEngine
from skyline_tpu.telemetry.spans import SpanRecorder, mint_trace_id
from skyline_tpu.telemetry.workload import WorkloadCharacterizer


def _extend_labeled(dst: dict | None, src: dict) -> dict:
    """Merge labeled-series maps by EXTENDING each family's series list —
    two replicas both exporting ``replica_lag_ms`` must coexist in one
    family, which the plain dict union cannot express."""
    out = {k: list(v) for k, v in (dst or {}).items()}
    for family, series in src.items():
        out.setdefault(family, []).extend(series)
    return out


class Telemetry:
    """One shared hub: counters + named histograms + the span ring.

    The worker owns one and threads it through the engine and both HTTP
    servers; everything on it is safe from any thread. ``histogram`` is
    get-or-create so call sites never coordinate registration.
    """

    def __init__(self, span_capacity: int = 4096):
        from skyline_tpu.analysis.registry import env_int

        self.counters = Counters()
        self.spans = SpanRecorder(span_capacity)
        self._hists: dict[tuple, Histogram] = {}
        self._lock = threading.Lock()
        # observability companions (ISSUE 8): the per-kernel dispatch
        # profiler, the decision flight recorder, and the SLO burn-rate
        # engine all hang off the hub so both HTTP servers can serve
        # /profile, /debug/flight and /slo from whatever they were handed
        self.profiler = KernelProfiler(spans=self.spans)
        self.flight = FlightRecorder(env_int("SKYLINE_FLIGHT_RING", 256))
        self.slo = SloEngine(self)
        # per-query EXPLAIN plans (ISSUE 9): the bounded ring behind
        # GET /explain on both HTTP surfaces and /skyline?explain=1
        self.explain = ExplainRecorder(env_int("SKYLINE_EXPLAIN_RING", 256))
        # audit plane (ISSUE 10): the shadow-verification verdict ring
        # behind GET /audit on both HTTP surfaces
        self.audit = AuditRecorder(env_int("SKYLINE_AUDIT_RING", 256))
        # fleet/workload planes (ISSUE 13): attached by the sharded facade
        # and the engine respectively (None on flat/ungated workers); both
        # HTTP surfaces read them through the hub — /fleet, the workload
        # block, and the skyline_chip_*{chip=...} metric families
        self.fleet = None
        self.workload = None
        # dispatch-tuner plane (ISSUE 20): the closed-loop controller
        # over the cascade table (``telemetry/tuner.py``), attached by
        # the engine when SKYLINE_TUNER is on; both HTTP surfaces serve
        # GET /dispatch (table + tuner decisions) through this slot
        self.tuner = None
        # chip-health plane (RUNBOOK §2p): attached by the sharded engine
        # (None on flat workers); serves the /health chip block and the
        # quarantine state on /fleet
        self.health = None
        # cluster plane (RUNBOOK §2r): a ``cluster.lease.ClusterStatus``
        # attached by the cluster engine / the worker's lease wiring
        # (None outside a cluster); serves GET /cluster on both HTTP
        # surfaces and the skyline_host_*{host=...} metric families
        self.cluster = None
        # ops plane (RUNBOOK §2s): the durable cross-process control-plane
        # journal (``telemetry.opslog.OpsLog``) attached by whichever
        # process opened one beside the WAL; serves GET /ops on both HTTP
        # surfaces. ``replication`` is a LIST of labeled-series providers
        # (each a callable or object with ``labeled_series() ->
        # (counters, gauges)``) — replicas and the WAL plane register here
        # so skyline_replica_*{replica=...} / wal families reach /metrics.
        # ``clusterview`` is an optional ``telemetry.clusterview.
        # ClusterView`` behind GET /cluster/overview.
        self.opslog = None
        self.replication: list = []
        self.clusterview = None

    def inc(self, name: str, n: int = 1) -> None:
        """Bump a named monotonic counter (shorthand for
        ``self.counters.inc`` so call sites holding only the hub don't
        reach through it)."""
        self.counters.inc(name, n)

    def histogram(
        self,
        name: str,
        unit: str = "ms",
        labels: tuple[tuple[str, str], ...] | None = None,
    ) -> Histogram:
        """Get-or-create a histogram; ``labels`` (a ``((key, value), ...)``
        tuple) keys a distinct series inside the same Prometheus family."""
        key = (name, tuple(labels) if labels else None)
        h = self._hists.get(key)
        if h is None:
            with self._lock:
                h = self._hists.get(key)
                if h is None:
                    h = Histogram(name, unit=unit, labels=labels)
                    self._hists[key] = h
        return h

    def histograms(self) -> list[Histogram]:
        with self._lock:
            return list(self._hists.values())

    def mint_trace_id(self) -> str:
        return mint_trace_id()

    def latency_snapshot(self) -> dict[str, dict]:
        """{hist name: {count, mean, p50, p90, p99, ...}} for /stats and
        the dashboard's percentile tiles. Labeled series get a
        ``name{k=v}`` display key so families don't collide."""
        out = {}
        for h in self.histograms():
            key = h.name
            if h.labels:
                key += "{" + ",".join(f"{k}={v}" for k, v in h.labels) + "}"
            out[key] = h.snapshot()
        return out

    def render_prometheus(
        self,
        gauges: dict[str, float] | None = None,
        extra_counters: dict[str, float] | None = None,
        prefix: str = "skyline",
        extra_labeled_counters: dict | None = None,
    ) -> str:
        counters = dict(self.counters.snapshot())
        # span-ring overwrites are silent data loss for /trace readers;
        # always expose the drop counter (zero included) so dashboards can
        # alert on the first overwrite
        counters["telemetry.spans_dropped"] = self.spans.dropped
        # honest-degradation signal (RUNBOOK §2p): always exposed, zero
        # included — a scrape must distinguish "no degraded answers" from
        # "the series doesn't exist", and the mesh smoke asserts presence
        counters.setdefault("degraded_answers", 0)
        # persistent-compile-cache effectiveness (utils/compile_cache.py):
        # a rising miss count on a warm cache is a retrace regression
        # visible without the jaxpr audit
        from skyline_tpu.utils.compile_cache import compile_cache_stats

        cc = compile_cache_stats()
        counters["compile_cache.hits"] = cc["hits"]
        counters["compile_cache.misses"] = cc["misses"]
        if extra_counters:
            counters.update(extra_counters)
        labeled_counters = labeled_gauges = None
        if self.fleet is not None:
            labeled_counters, labeled_gauges = self.fleet.labeled_series()
        if self.cluster is not None:
            host_counters, host_gauges = self.cluster.labeled_series()
            if host_counters:
                labeled_counters = {**(labeled_counters or {}), **host_counters}
            if host_gauges:
                labeled_gauges = {**(labeled_gauges or {}), **host_gauges}
        # replication providers (RUNBOOK §2s): several replicas can share
        # one hub, each contributing series to the SAME family
        # (skyline_replica_lag_ms{replica=...}), so the merge must EXTEND
        # family lists rather than replace them like the dict unions above
        for provider in list(self.replication):
            try:
                fn = getattr(provider, "labeled_series", provider)
                repl_counters, repl_gauges = fn()
            except Exception:
                continue  # a dying replica must not break /metrics
            if repl_counters:
                labeled_counters = _extend_labeled(
                    labeled_counters, repl_counters
                )
            if repl_gauges:
                labeled_gauges = _extend_labeled(labeled_gauges, repl_gauges)
        if extra_labeled_counters:
            # per-tenant admission series from the serve plane ride along
            # the fleet's per-chip families
            labeled_counters = {
                **(labeled_counters or {}), **extra_labeled_counters
            }
        return render_prometheus(
            counters=counters,
            gauges=gauges,
            histograms=self.histograms(),
            prefix=prefix,
            labeled_counters=labeled_counters,
            labeled_gauges=labeled_gauges,
        )


__all__ = [
    "AuditRecorder",
    "Counters",
    "DEFAULT_EDGES",
    "ExplainRecorder",
    "FleetStats",
    "FlightRecorder",
    "FreshnessTracker",
    "Histogram",
    "KernelProfiler",
    "NULL_TRACER",
    "PROMETHEUS_CONTENT_TYPE",
    "QueryPlan",
    "SloEngine",
    "SpanRecorder",
    "Telemetry",
    "Tracer",
    "WorkloadCharacterizer",
    "flatten_gauges",
    "fleet_doc",
    "mint_trace_id",
    "render_prometheus",
]
