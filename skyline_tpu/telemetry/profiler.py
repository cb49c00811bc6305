"""Per-kernel device profiling + a flight recorder of engine decisions.

``flush/merge_kernel`` was ~98% of a profiled 8-D anti-correlated window but
the phase tracer reports it as one opaque total. ``KernelProfiler`` splits that
wall time by *dispatch signature* — (variant, d, N-bucket, backend, mp) —
the tuple that determines which compiled XLA executable actually ran. Each
signature accumulates call count, wall-time total and EMA, a first-call
wall time (compile + run, the retrace canary), and optionally XLA
``cost_analysis()`` FLOPs/bytes captured once per signature via an
ahead-of-time lower+compile (``SKYLINE_PROFILE_COST``, default off — AOT
compilation is expensive and its executable is discarded).

Attribution is *post-hoc and host-side*: the engine wraps each dispatch
site's existing ``flush/merge_kernel`` tracer phase with
``profiler.record(...)`` — two extra ``perf_counter_ns`` reads and a lock
per dispatch, nothing inside jit. Because the profiler times the same
region the phase tracer does, the /profile endpoint can attribute the
phase total to named signatures (the ISSUE-8 >=90% acceptance bar holds by
construction).

Kernel slices also land in the shared ``SpanRecorder`` ring (``kernel/<
variant>`` spans, tid 2), so the Chrome-trace export shows which variant
ran inside each phase.

``FlightRecorder`` is the companion black box: a bounded ring of
structured dispatch/cascade/prune/cache decisions (``note(kind, **fields)``)
served at ``/debug/flight`` and dumped to stderr on crash by the
resilience supervisor — the last N decisions before a crash are usually
the story of the crash.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager

_EMA_ALPHA = 0.2


def n_bucket(n: int) -> int:
    """Bucket a row count to the next power of two (0 stays 0) — the same
    granularity XLA shapes actually vary on after the active-row ladder."""
    n = int(n)
    if n <= 0:
        return 0
    b = 1
    while b < n:
        b <<= 1
    return b


class _Entry:
    __slots__ = (
        "calls", "wall_ms", "ema_ms", "first_call_ms", "cost", "last_ms",
    )

    def __init__(self):
        self.calls = 0
        self.wall_ms = 0.0
        self.ema_ms = 0.0
        self.first_call_ms = None
        self.cost = None
        self.last_ms = 0.0


class KernelProfiler:
    """Thread-safe registry of per-dispatch-signature timing/cost."""

    def __init__(self, spans=None, backend: str | None = None):
        self._lock = threading.Lock()
        self._entries: dict[tuple, _Entry] = {}  # guarded-by: self._lock
        self._claimed: set[tuple] = set()  # guarded-by: self._lock
        self.spans = spans  # optional SpanRecorder for kernel slices
        self._backend = backend
        self.dispatches = 0  # guarded-by: self._lock

    def _backend_name(self) -> str:
        if self._backend is None:
            try:
                import jax

                self._backend = jax.default_backend()
            except Exception:
                self._backend = "unknown"
        return self._backend

    @contextmanager
    def record(
        self,
        variant: str,
        d: int,
        n: int,
        mp: bool = False,
        cost_thunk=None,
    ):
        """Time one kernel dispatch under signature (variant, d, bucket(n),
        backend, mp). ``cost_thunk`` (optional, called at most once per
        signature, only when SKYLINE_PROFILE_COST is on) returns an XLA
        ``cost_analysis()`` dict."""
        key = (variant, int(d), n_bucket(n), self._backend_name(), bool(mp))
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            dt_ms = (time.perf_counter_ns() - t0) / 1e6
            with self._lock:
                e = self._entries.get(key)
                if e is None:
                    e = self._entries[key] = _Entry()
                first = e.calls == 0
                e.calls += 1
                self.dispatches += 1
                e.wall_ms += dt_ms
                e.last_ms = dt_ms
                if first:
                    # first dispatch of a fresh signature pays the trace +
                    # compile; keep it as the retrace canary
                    e.first_call_ms = dt_ms
                    e.ema_ms = dt_ms
                else:
                    e.ema_ms += _EMA_ALPHA * (dt_ms - e.ema_ms)
            if first and cost_thunk is not None:
                cost = self._try_cost(cost_thunk)
                if cost is not None:
                    with self._lock:
                        e.cost = cost
            if self.spans is not None:
                self.spans.record(
                    f"kernel/{variant}",
                    t0,
                    t0 + int(dt_ms * 1e6),
                    tid=2,
                    args={"d": int(d), "n_bucket": key[2], "mp": bool(mp)},
                )

    @staticmethod
    def _try_cost(cost_thunk):
        """Run an AOT cost thunk defensively: cost_analysis is best-effort
        across backends and must never take a dispatch down."""
        try:
            cost = cost_thunk()
        except Exception:
            return None
        if isinstance(cost, (list, tuple)) and cost:
            cost = cost[0]  # older jaxlibs return [dict] per computation
        if not isinstance(cost, dict):
            return None
        out = {}
        for k in ("flops", "bytes accessed", "bytes_accessed"):
            v = cost.get(k)
            if isinstance(v, (int, float)):
                out[k.replace(" ", "_")] = float(v)
        return out or None

    def claim_explore(
        self, variant: str, d: int, n: int, mp: bool = False
    ) -> bool:
        """One-shot exploration claim for signature (variant, d,
        bucket(n), backend, mp): returns True exactly once while the
        signature has no measured data — ``dispatch.choose_variant``'s
        sticky-explore handshake. Without it, every call between the
        first dispatch of an unmeasured candidate and its record landing
        re-runs the cold path (compile + first-run wall) on a hot loop;
        with it, the second caller immediately falls back to measured
        data. A signature that records later keeps winning or losing on
        its EMA as usual; a claim whose dispatch never records leaves
        the candidate unexplored by design (no retry storms)."""
        key = (variant, int(d), n_bucket(n), self._backend_name(), bool(mp))
        with self._lock:
            if key in self._entries or key in self._claimed:
                return False
            self._claimed.add(key)
            return True

    def ema_ms(self, variant: str, d: int, n: int, mp: bool = False):
        """EMA wall of one signature, or None if it never dispatched —
        the read side of profiler-driven dispatch (``dispatch.
        choose_variant`` compares candidate variants' measured EMAs under
        the same (d, N-bucket, backend) instead of a hand-tuned gate)."""
        key = (variant, int(d), n_bucket(n), self._backend_name(), bool(mp))
        with self._lock:
            e = self._entries.get(key)
            return None if e is None else e.ema_ms

    def total_wall_ms(self) -> float:
        with self._lock:
            return sum(e.wall_ms for e in self._entries.values())

    def export_state(self) -> dict:
        """JSON-safe snapshot of every signature's accumulators — the
        checkpoint payload that lets a restarted worker keep its learned
        dispatch (a restored EMA means ``claim_explore`` never re-runs a
        losing variant's cold path: the PR 18 cold-boot regression).
        In-flight exploration claims are deliberately NOT exported — a
        claim whose record never landed must not survive a restart, or
        the candidate would stay unexplored forever."""
        with self._lock:
            entries = [
                {
                    "variant": k[0], "d": k[1], "n_bucket": k[2],
                    "backend": k[3], "mp": k[4],
                    "calls": e.calls,
                    "wall_ms": round(e.wall_ms, 6),
                    "ema_ms": round(e.ema_ms, 6),
                    "first_call_ms": (
                        None if e.first_call_ms is None
                        else round(e.first_call_ms, 6)
                    ),
                    "last_ms": round(e.last_ms, 6),
                }
                for k, e in self._entries.items()
            ]
        return {"version": 1, "entries": entries}

    def restore_state(self, doc) -> int:
        """Adopt signatures from an ``export_state`` document. LIVE data
        wins: a signature this process already measured is left alone
        (fresher than anything a checkpoint carries). Returns the number
        of signatures adopted. Malformed rows are skipped — a corrupt
        checkpoint extra must not take the profiler down."""
        if not isinstance(doc, dict):
            return 0
        adopted = 0
        for row in doc.get("entries") or []:
            try:
                key = (
                    str(row["variant"]), int(row["d"]),
                    int(row["n_bucket"]), str(row["backend"]),
                    bool(row["mp"]),
                )
                calls = int(row["calls"])
                wall = float(row["wall_ms"])
                ema = float(row["ema_ms"])
                first = row.get("first_call_ms")
                first = None if first is None else float(first)
                last = float(row.get("last_ms", 0.0))
            except (KeyError, TypeError, ValueError):
                continue
            if calls <= 0:
                continue
            with self._lock:
                if key in self._entries:
                    continue
                e = self._entries[key] = _Entry()
                e.calls = calls
                e.wall_ms = wall
                e.ema_ms = ema
                e.first_call_ms = first
                e.last_ms = last
                self._claimed.discard(key)
            adopted += 1
        return adopted

    def reset_signatures(self, variants=None) -> int:
        """Drop measured entries (and claims) for the given variant names
        — or every signature when ``variants`` is None — so the next
        ``choose_variant`` race re-explores from scratch. The dispatch
        tuner calls this on a confirmed workload-regime flip: EMAs
        measured under the old regime are evidence about the wrong
        distribution. Returns the number of signatures dropped."""
        names = None if variants is None else set(variants)
        with self._lock:
            keys = [
                k for k in self._entries
                if names is None or k[0] in names
            ]
            for k in keys:
                del self._entries[k]
            self._claimed = {
                k for k in self._claimed
                if names is not None and k[0] not in names
            }
        return len(keys)

    def snapshot_counts(self) -> dict[tuple, tuple[int, float]]:
        """{signature: (calls, wall_ms)} — the cheap mark the EXPLAIN
        plane diffs around one query window to attribute dispatches."""
        with self._lock:
            return {k: (e.calls, e.wall_ms) for k, e in self._entries.items()}

    def doc(self, phase_total_ms: float | None = None) -> dict:
        """The /profile document: per-signature rows sorted by wall time,
        per-variant retrace counts, and (when the caller passes the phase
        tracer's ``flush/merge_kernel`` total) the attribution share."""
        with self._lock:
            items = list(self._entries.items())
            dispatches = self.dispatches
        rows = []
        retraces: dict[str, int] = {}
        total = 0.0
        for (variant, d, bucket, backend, mp), e in items:
            total += e.wall_ms
            retraces[variant] = retraces.get(variant, 0) + 1
            row = {
                "variant": variant,
                "d": d,
                "n_bucket": bucket,
                "backend": backend,
                "mp": mp,
                "calls": e.calls,
                "wall_ms": round(e.wall_ms, 3),
                "ema_ms": round(e.ema_ms, 4),
                "first_call_ms": (
                    round(e.first_call_ms, 3)
                    if e.first_call_ms is not None else None
                ),
            }
            if e.cost is not None:
                row["cost"] = e.cost
            rows.append(row)
        rows.sort(key=lambda r: -r["wall_ms"])
        doc = {
            "kernels": rows,
            "signatures": len(rows),
            "dispatches": dispatches,
            "total_wall_ms": round(total, 3),
            "retraces_per_variant": retraces,
        }
        if phase_total_ms is not None:
            doc["phase_total_ms"] = round(float(phase_total_ms), 3)
            doc["attributed_share"] = (
                round(min(1.0, total / phase_total_ms), 4)
                if phase_total_ms > 0 else None
            )
        return doc


class FlightRecorder:
    """Bounded ring of structured engine decisions — the black box.

    ``note(kind, **fields)`` is one lock + one deque append; entries carry a
    monotonic sequence number and a wall timestamp. ``snapshot()`` backs
    ``/debug/flight``; ``dump(reason)`` writes the ring to stderr as one
    JSON document (called by the resilience supervisor on crash).
    """

    def __init__(self, capacity: int = 256):
        self.capacity = max(1, int(capacity))
        self._ring: deque[dict] = deque(  # guarded-by: self._lock
            maxlen=self.capacity
        )
        self._lock = threading.Lock()
        self._seq = 0  # guarded-by: self._lock
        # current query's trace_id; set/cleared only by the engine thread
        # around trigger work, read here on the same thread — notes from
        # other threads simply go unstamped
        self._trace = None

    def set_trace(self, trace_id: str | None) -> None:
        """Stamp subsequent ``note`` entries with this trace_id (None to
        stop) so /debug/flight rows join against spans and explain
        records instead of being time-correlated by eye."""
        self._trace = trace_id

    def note(self, kind: str, **fields) -> None:
        # the ring backs /debug/flight and the crash dump, so every field
        # must be JSON-serializable; digests and other raw bytes become hex
        for k, v in fields.items():
            if isinstance(v, bytes):
                fields[k] = v.hex()
            elif not isinstance(v, (str, int, float, bool, type(None))):
                fields[k] = repr(v)
        if self._trace is not None and "trace_id" not in fields:
            fields["trace_id"] = self._trace
        with self._lock:
            self._seq += 1
            entry = {"seq": self._seq, "t_ms": round(time.time() * 1000.0, 1),
                     "kind": kind}
            entry.update(fields)
            self._ring.append(entry)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def doc(self) -> dict:
        with self._lock:
            entries = list(self._ring)
            seq = self._seq
        return {
            "entries": entries,
            "recorded_total": seq,
            "ring_capacity": self.capacity,
            "partial": seq > len(entries),
        }

    def dump(self, reason: str, stream=None) -> None:
        """Best-effort crash dump of the ring as one JSON line on stderr."""
        try:
            doc = self.doc()
            doc["reason"] = reason
            print(
                "skyline-flight-recorder: " + json.dumps(doc),
                file=stream if stream is not None else sys.stderr,
            )
        except Exception:
            pass
