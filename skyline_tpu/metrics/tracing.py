"""Per-phase tracing: host time per named phase, on the profiler's clock.

The reference's tracing is manual nanoTime deltas around BNL work plus the
aggregator's ingestion/local/global decomposition, surfaced as a product
feature in the result JSON (SURVEY.md §5). This module generalizes that into
named phase timers the engine/worker/bench can nest, with the same
"breakdown is a feature" stance: ``report()`` returns totals suitable for
logging or embedding in results.

Every phase also belongs to one layer (``LAYERS``). While a JAX profiler
capture is on (``jax.profiler.trace``, or a capture of a
``jax.profiler.start_server`` port), each phase entry is written into the
capture as a ``TraceAnnotation`` of the same name with a ``layer`` stat, on
the host plane beside the device ops, so every device idle gap can be read
against the host phase open in it. Off a capture a phase costs one
``TraceMe.is_enabled()`` check more than its two clock reads.

Device timing caveat: JAX dispatch is async, so a phase that launches
device work times the enqueue; the device time lands in whichever later
phase blocks on the result (the ``…_sync`` / ``…_transfer`` phases). The
device side of a window is read from the capture's device plane.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict

from jax.profiler import TraceAnnotation

capturing = TraceAnnotation.is_enabled

# Phase name -> layer. A key ending in "/" covers every phase whose name
# starts with it; an exact name wins over its prefix. Phase names are read
# by /stats ``phase_breakdown_ms`` and the worker's stderr breakdown, so
# they keep their spelling; a phase the table does not cover is a test
# failure (tests/test_tracing.py), never a silent "other".
LAYERS: dict[str, str] = {
    "worker/poll": "transport",
    "worker/parse": "parse",
    "ingest/": "ingest",
    "partition_ids": "ingest",
    "route": "ingest",
    "flush/": "flush",
    "slide/": "flush",
    "global_merge": "merge",
    "global_merge/": "merge",
    "query/": "merge",
    # the sharded engine's two-level chip tournament (distributed/
    # sharded.py): what its levels do beyond each chip's own merge phases
    "cross_chip/": "cross_chip",
    "publish": "publish",
    "publish/": "publish",
    "audit/": "audit",
    "worker/build": "build",
    # containers: what is left in them once their children are taken out
    # is the worker loop's own glue
    "worker/ingest": "worker",
    "worker/query": "worker",
}


def layer_of(name: str) -> str:
    """The layer of phase ``name``; ``KeyError`` for a name ``LAYERS``
    does not cover."""
    layer = LAYERS.get(name)
    if layer is not None:
        return layer
    head, sep, _ = name.partition("/")
    if sep and head + sep in LAYERS:
        return LAYERS[head + sep]
    raise KeyError(f"phase {name!r} has no layer in metrics.tracing.LAYERS")


class LayerClock:
    """Self time per layer of the phases entered while a profiler capture
    is on, over every tracer in the process: a phase entry's duration less
    the part its child phases cover, so each instant counts once, for the
    innermost phase open on its thread. It is the split a reduction of the
    capture's host plane gives, readable without parsing the capture.

    Beside the times it keeps named integer counts (``count``), added only
    while a capture is on: work the program does that a time cannot show,
    such as the padded dominance pairs of a vmapped merge."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ns: dict[str, int] = defaultdict(int)  # guarded-by: self._lock
        self._counts: dict[str, int] = defaultdict(int)  # guarded-by: self._lock
        self._open = threading.local()  # innermost captured phase per thread

    def _enter(self, ph: "_Phase") -> None:
        ph._parent = getattr(self._open, "top", None)
        ph._child_ns = 0
        self._open.top = ph

    def _leave(self, ph: "_Phase", layer: str) -> None:
        dur = ph.t1 - ph.t0
        self._open.top = ph._parent
        if ph._parent is not None:
            ph._parent._child_ns += dur
        with self._lock:
            self._ns[layer] += dur - ph._child_ns

    def self_ms(self) -> dict[str, float]:
        with self._lock:
            return {k: v / 1e6 for k, v in sorted(self._ns.items())}

    def count(self, name: str, n: int) -> None:
        """Adds ``n`` to count ``name`` while a capture is on; off a capture
        it is one check and nothing else."""
        if capturing():
            with self._lock:
                self._counts[name] += int(n)

    def counts(self) -> dict[str, int]:
        with self._lock:
            return dict(sorted(self._counts.items()))

    def reset(self) -> None:
        with self._lock:
            self._ns.clear()
            self._counts.clear()


CAPTURED = LayerClock()


class _Phase:
    """One entry of a phase: timed once, the time going to its tracer's
    totals, to a span ring when given one (``spans.record(span, ...)``
    with ``trace_id``/``tid``/``args``, which the caller may still set
    inside the block), and to the profiler capture when one is on."""

    __slots__ = (
        "_tracer", "name", "_spans", "span", "trace_id", "tid", "args",
        "t0", "t1", "_ann", "_parent", "_child_ns",
    )

    def __init__(self, tracer, name, spans=None, span=None, trace_id=None,
                 tid=0, args=None):
        self._tracer = tracer
        self.name = name
        self._spans = spans
        self.span = span
        self.trace_id = trace_id
        self.tid = tid
        self.args = args
        self._ann = None

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) / 1e6

    def __enter__(self) -> "_Phase":
        if capturing():
            ann = TraceAnnotation(self.name, layer=layer_of(self.name))
            ann.__enter__()
            self._ann = ann
            CAPTURED._enter(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = t1 = time.perf_counter_ns()
        tr = self._tracer
        if tr is not None:
            tr._total_ns[self.name] += t1 - self.t0
            tr._count[self.name] += 1
        if self._spans is not None:
            self._spans.record(
                self.span, self.t0, t1, trace_id=self.trace_id, tid=self.tid,
                args=self.args,
            )
        if self._ann is not None:
            CAPTURED._leave(self, layer_of(self.name))
            self._ann.__exit__(*exc)


class Tracer:
    """Named phase timers (host wall per phase name, nested phases
    overlapping their parents, like the reference's ingestion = wall -
    local arithmetic)."""

    def __init__(self):
        self._total_ns: dict[str, int] = defaultdict(int)
        self._count: dict[str, int] = defaultdict(int)

    def phase(self, name: str) -> _Phase:
        """``with tracer.phase(name):`` accumulates host wall under ``name``."""
        return _Phase(self, name)

    def span_phase(self, name: str, spans, span: str, trace_id=None, tid=0,
                   args=None) -> _Phase:
        """``phase(name)`` whose one timing also lands in the span ring
        ``spans`` (a ``telemetry.spans.SpanRecorder``, or None for none) as
        span ``span``: one clock pair per boundary for both."""
        return _Phase(self, name, spans, span, trace_id, tid, args)

    def report(self) -> dict[str, dict[str, float]]:
        return {
            name: {
                "total_ms": self._total_ns[name] / 1e6,
                "count": self._count[name],
                "mean_ms": self._total_ns[name] / 1e6 / max(1, self._count[name]),
            }
            for name in sorted(self._total_ns)
        }

    def reset(self) -> None:
        self._total_ns.clear()
        self._count.clear()


class _NullPhase:
    __slots__ = ()

    def __enter__(self) -> "_NullPhase":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_PHASE = _NullPhase()


class _NullTracer:
    """Zero-overhead stand-in so hot paths can call ``tracer.phase(...)``
    unconditionally; ``SkylineEngine``/``PartitionSet`` default to this.
    A ``span_phase`` still times its boundary (the engine's clock
    discipline and the span ring read it), outside any totals."""

    def phase(self, name: str) -> _NullPhase:
        return _NULL_PHASE

    def span_phase(self, name: str, spans, span: str, trace_id=None, tid=0,
                   args=None) -> _Phase:
        return _Phase(None, name, spans, span, trace_id, tid, args)

    def report(self) -> dict:
        return {}

    def reset(self) -> None:
        pass


NULL_TRACER = _NullTracer()
