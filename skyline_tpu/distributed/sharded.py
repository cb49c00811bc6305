"""Sharded streaming engine: per-chip partition groups + a two-level
tournament merge (ISSUE 12).

The single-device engine keeps all P partitions in one stacked buffer on
one chip. ``ShardedPartitionSet`` splits them into ``chips`` contiguous
groups — chip ``c`` owns global partitions ``[c*G, (c+1)*G)`` with
``G = P / chips`` — and each group is a full single-device
``PartitionSet`` pinned to its own device: its own ingest buffers, flush
cascade (prefilter → bf16 → exact), witness summaries, merge cache, and
epoch subvector. Nothing crosses chips during ingest or flush.

A global query becomes a TWO-LEVEL tournament:

1. intra-chip: each chip runs its existing pruned tournament tree
   (``stream/window.py`` ``tree_pair_merge``) over its resident
   partitions, producing one chip-local skyline root per device;
2. cross-chip: the witness-dominance prefilter (PR 4) runs over CHIP
   summaries — one ``(2d+2)`` row per chip-local root — so a chip whose
   min-corner is strictly dominated by another chip's witness point is
   skipped before any cross-chip transfer; the surviving roots are
   gathered onto chip 0 and merged pairwise in ASCENDING chip order.

Byte identity: chip groups are contiguous in pid, each chip root is
byte-identical to the flat merge over its own partitions (the existing
single-device guarantee), and ``tree_pair_merge``'s stable compaction
preserves (pid, storage-row) order at every cross-chip level — so the
two-level root is byte-identical (rows AND order) to the single-device
flat output. The chip prune is sound for the same reason the partition
prune is: a chip whose every point is strictly dominated by one witness
point contributes nothing to the skyline. Flush CADENCE is part of the
byte contract under the lazy/overlap policies (each flush sum-sorts its
batch), so the facade flushes ALL chips exactly when the single-device
set would flush all partitions — never per-chip.

Everything runs on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — tier-1
exercises the real merge topology without a TPU.
"""

from __future__ import annotations

import hashlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from skyline_tpu.metrics.tracing import CAPTURED, NULL_TRACER
from skyline_tpu.ops import cascade
from skyline_tpu.ops.dispatch import (
    chip_failover_enabled,
    chip_merge_deadline_ms,
    failover_lock_ms,
    fleet_enabled,
)
from skyline_tpu.parallel.chips import chip_devices
from skyline_tpu.resilience.faults import InjectedCrash, fault_point
from skyline_tpu.stream.batched import PartitionSet, PartitionView
from skyline_tpu.stream.engine import SkylineEngine
from skyline_tpu.stream.window import (
    DEFAULT_BUFFER_SIZE,
    _active_bucket,
    _next_pow2,
    partition_summaries_device,
    prune_witness_mask,
    tree_pair_merge,
    tree_points_device,
    tree_stats_device,
)


def epoch_hex(key: bytes) -> str:
    """Short stable digest of an epoch key for WAL barrier records and
    logs (the raw key is a P*8-byte vector — too wide to journal)."""
    return hashlib.sha1(key).hexdigest()[:16]


class _ShardedMergeHandle:
    """An in-flight two-level merge — the sharded analogue of
    ``stream.batched._MergeHandle``. Chip-local merges are harvested at
    launch (their stats syncs size the cross-chip leaves); the cross-chip
    tree and its stats transfer stay async until harvest."""

    __slots__ = (
        "key",
        "emit_points",
        "use_cache",
        "cached",
        "result",
        "stats",
        "root_vals",
        "explain",
        "chip_info",
        "partial",
    )

    def __init__(self):
        self.cached = False
        self.result = None
        self.stats = None
        self.root_vals = None
        self.explain = None
        self.chip_info = None
        # set when chips were EXCLUDED from this merge (deadline/failure):
        # {"excluded_chips", "reasons", "completeness_bound",
        #  "excluded_records"} — rides to the engine as a degraded answer
        self.partial = None

    def ready(self) -> bool:
        if self.cached:
            return True
        try:
            return bool(self.stats.is_ready())
        except AttributeError:
            return False


class ShardedPartitionSet:
    """Facade with the ``PartitionSet`` surface over per-chip groups.

    The engine (and ``PartitionView``, checkpointing, the audit plane)
    talk to this exactly as they talk to a single-device set; global
    partition ``p`` lives on chip ``p // group_size`` at local index
    ``p % group_size``. Barrier/metrics bookkeeping (max ids, record
    counts, pending rows) is kept facade-global so flush-cadence
    decisions see the same state the single-device set would.
    """

    def __init__(
        self,
        num_partitions: int,
        dims: int,
        buffer_size: int = DEFAULT_BUFFER_SIZE,
        *,
        chips: int,
        initial_capacity: int = 0,
        tracer=None,
        flush_policy: str = "incremental",
        overlap_rows: int = 262144,
        window_capacity: int = 0,
        counters=None,
    ):
        if chips < 1:
            raise ValueError(f"chips must be >= 1, got {chips}")
        if num_partitions % chips:
            raise ValueError(
                f"num_partitions {num_partitions} must be divisible by "
                f"chips {chips}"
            )
        self.num_partitions = num_partitions
        self.dims = dims
        self.buffer_size = buffer_size
        self.chips = chips
        self.group_size = num_partitions // chips
        self.flush_policy = flush_policy
        self.overlap_rows = overlap_rows
        # kept so failover can rebuild a group with ctor-identical shape
        self._initial_capacity = initial_capacity
        self._window_capacity = window_capacity
        self.mesh = None  # the engine's mesh-vs-device dispatch stays live
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._devices = chip_devices(chips)
        self._chips: list[PartitionSet] = []
        for c in range(chips):
            with jax.default_device(self._devices[c]):
                self._chips.append(
                    PartitionSet(
                        self.group_size,
                        dims,
                        buffer_size,
                        initial_capacity=initial_capacity,
                        tracer=self.tracer,
                        flush_policy=flush_policy,
                        overlap_rows=overlap_rows,
                        window_capacity=window_capacity,
                        counters=counters,
                    )
                )
        p = num_partitions
        # facade-global bookkeeping: the flush-cadence decision and the
        # engine's barrier checks read THESE, so they match the
        # single-device set bit-for-bit (chips keep their own mirrors)
        self._pending_rows = np.zeros(p, dtype=np.int64)
        self.max_seen_id = np.full(p, -1, dtype=np.int64)
        self.start_time_ms: list[float | None] = [None] * p
        self.records_seen = np.zeros(p, dtype=np.int64)
        self._processing_base_ns = 0
        self._counters = counters
        self._profiler = None
        self._flight = None
        self._explain = None
        self._fleet = None
        self._spans = None
        # facade-level epoch-keyed merge cache over the TWO-LEVEL result
        # (chips additionally keep their own intra-chip caches)
        self._gm_cache: dict | None = None
        self.merge_cache_hits = 0
        self.merge_cache_misses = 0
        # the delta plane is intra-chip only; the facade reports zeros so
        # the engine's stats block keeps its shape
        self.merge_delta_merges = 0
        self.merge_delta_rows = 0
        self.last_dirty_fraction: float | None = None
        self.last_tree_info: dict | None = None
        # two-level merge attribution (sharded_stats / EXPLAIN chips block)
        self.sharded_merges = 0
        self.chips_pruned_total = 0
        self.chips_considered_total = 0
        self.last_chip_info: dict | None = None
        # chip-local WAL plane (resilience/chip_wal.py), worker-attached
        self._chip_wal = None
        self._barrier_seq = 0
        # chip-fault-tolerance plane (RUNBOOK §2p): health scores drive
        # quarantine; the deadline-bounded level 1 runs each chip's merge
        # on a watchdog thread serialized by that chip's lock (a
        # PartitionSet is not thread-safe; an abandoned attempt must
        # never interleave with a retry, a later merge, ingest, flush,
        # checkpoint capture, or failover on the same group — all of
        # which take the chip lock too)
        self._health = None
        self._chip_locks = [threading.Lock() for _ in range(chips)]
        self.degraded_merges = 0
        self.failovers = 0
        self.last_failover: dict | None = None
        # the most recent harvest's partial marker (None = full answer);
        # the engine reads this right after harvest to mark the result
        self.last_partial: dict | None = None

    # -- chip addressing ---------------------------------------------------

    def _dev(self, c: int):
        return jax.default_device(self._devices[c])

    def _loc(self, p: int) -> tuple[int, int]:
        return divmod(p, self.group_size)

    # -- state versioning ---------------------------------------------------

    @property
    def epoch_key(self) -> bytes:
        """Concatenated chip epoch subvectors, ascending chip order — the
        identity of the whole sharded flushed state. Any chip's flush
        changes it, so the merge cache and snapshot dedupe stay exact."""
        return b"".join(c.epoch_key for c in self._chips)

    # -- aggregate bookkeeping ----------------------------------------------

    @property
    def processing_ns(self) -> int:
        return self._processing_base_ns + sum(
            c.processing_ns for c in self._chips
        )

    @processing_ns.setter
    def processing_ns(self, v: int) -> None:
        # checkpoint restore re-applies the saved total through here
        for c in self._chips:
            c.processing_ns = 0
        self._processing_base_ns = int(v)

    @property
    def processing_ms(self) -> float:
        return self.processing_ns / 1e6

    @property
    def merge_tree_merges(self) -> int:
        return sum(c.merge_tree_merges for c in self._chips)

    @property
    def merge_partitions_pruned(self) -> int:
        return sum(c.merge_partitions_pruned for c in self._chips)

    @property
    def device_ingest(self) -> bool:
        return False

    @property
    def has_unsynced_ingest(self) -> bool:
        return False

    def sync_ingest_bookkeeping(self) -> None:  # device-ingest only
        return None

    @property
    def pending_rows_total(self) -> int:
        return int(self._pending_rows.sum())

    def _inc(self, name: str, n: int = 1) -> None:
        if self._counters is not None:
            self._counters.inc(name, n)

    # -- observability hooks -------------------------------------------------

    def attach_observability(
        self, profiler=None, flight=None, fleet=None, spans=None
    ) -> None:
        self._profiler = profiler
        self._flight = flight
        # fleet plane (ISSUE 13): per-chip load/prune/interconnect
        # accounting + the per-chip tournament child spans — host-side
        # bookkeeping only, never inside the merge kernels
        self._fleet = fleet
        self._spans = spans
        for c in self._chips:
            c.attach_observability(profiler=profiler, flight=flight)

    def set_explain(self, plan) -> None:
        self._explain = plan

    def attach_chip_wal(self, plane) -> None:
        """Attach a ``resilience.chip_wal.ChipWalPlane``: per-chip flush
        notes plus the merge-time barrier records crash replay verifies
        group consistency against."""
        self._chip_wal = plane

    def attach_health(self, health) -> None:
        """Attach a ``resilience.health.ChipHealth`` supervisor: merge
        outcomes feed its scores, and quarantine decisions drive the
        deadline-bounded merge's exclusions plus ``maybe_failover``."""
        self._health = health

    def _fnote(self, kind: str, **fields) -> None:
        if self._flight is not None:
            self._flight.note(kind, **fields)

    # -- ingest --------------------------------------------------------------

    def add_batch(
        self, p: int, values: np.ndarray, max_id: int, now_ms: float
    ) -> None:
        n = values.shape[0]
        if n == 0:
            return
        if self.start_time_ms[p] is None:
            self.start_time_ms[p] = now_ms
        self.max_seen_id[p] = max(self.max_seen_id[p], int(max_id))
        self.records_seen[p] += n
        self._pending_rows[p] += n
        c, lp = self._loc(p)
        if self._fleet is not None:
            self._fleet.note_ingest(c, n)
        # a deadline-abandoned merge attempt may still be running inside
        # this chip's lock (see _bounded_level1); a PartitionSet is not
        # thread-safe, so ingest serializes behind it
        with self._chip_locks[c]:
            self._chips[c].add_batch(lp, values, max_id, now_ms)

    def maybe_flush(self) -> bool:
        """The single-device flush-cadence decision verbatim, over the
        facade-global pending state — then a flush of EVERY chip. Flush
        points are part of the byte contract (the lazy policy sum-sorts
        per flush batch), so per-chip thresholds would fork storage order
        from the single-device engine."""
        if self.flush_policy == "lazy":
            return False
        if self.flush_policy == "overlap":
            if self.pending_rows_total >= self.overlap_rows:
                self.flush_all(tighten=False)
                return True
            return False
        if int(self._pending_rows.max()) >= self.buffer_size:
            self.flush_all()
            return True
        return False

    def flush_all(self, tighten: bool = True) -> None:
        """Flush every chip in turn. While a profiler capture is on,
        counts the rows flushed (``flush/chip_rows``) and the fullest
        chip's share of them (``flush/chip_rows_max``)."""
        chip_rows = []
        for c, chip in enumerate(self._chips):
            # the chip lock serializes the flush against any
            # deadline-abandoned merge attempt still in flight on this
            # group (_bounded_level1); uncontended on a healthy fleet
            with self._chip_locks[c]:
                rows = chip.pending_rows_total
                chip_rows.append(rows)
                t0 = time.perf_counter_ns()
                with self._dev(c):
                    chip.flush_all(tighten)
            if self._fleet is not None and rows:
                self._fleet.note_flush(
                    c, rows, (time.perf_counter_ns() - t0) / 1e6
                )
            if self._chip_wal is not None and rows:
                self._chip_wal.note_flush(c, rows, epoch_hex(chip.epoch_key))
            if self._health is not None and rows:
                # a completed flush proves the chip alive between merges:
                # the liveness feed behind ChipHealth's staleness tick
                self._health.note_heartbeat(c)
        self._pending_rows[:] = 0
        CAPTURED.count("flush/chip_rows", sum(chip_rows))
        CAPTURED.count("flush/chip_rows_max", max(chip_rows))

    def flush_cascade_stats(self) -> dict:
        docs = [c.flush_cascade_stats() for c in self._chips]
        seen = sum(d["prefilter_seen"] for d in docs)
        dropped = sum(d["prefilter_dropped"] for d in docs)
        return {
            "prefilter_enabled": docs[0]["prefilter_enabled"],
            "mixed_precision": docs[0]["mixed_precision"],
            "prefilter_seen": seen,
            "prefilter_dropped": dropped,
            "prefilter_drop_fraction": (dropped / seen) if seen else 0.0,
            "prefilter_packed_seen": sum(
                d["prefilter_packed_seen"] for d in docs
            ),
            "bf16_resolved": sum(d["bf16_resolved"] for d in docs),
        }

    # -- two-level tournament merge ------------------------------------------

    def global_merge_stats(self, emit_points: bool = False):
        return self.global_merge_harvest(self.global_merge_launch(emit_points))

    def global_merge_launch(self, emit_points: bool = False):
        """Launch the two-level merge. Level 1 (intra-chip trees) harvests
        synchronously — each chip's stats sync sizes its cross-chip leaf —
        but the level-2 pairwise kernels and the packed stats transfer
        stay in flight until ``global_merge_harvest``.

        With ``SKYLINE_CHIP_MERGE_DEADLINE_MS`` set, each chip's level-1
        merge is deadline-bounded (watchdog thread + retry/hedge ladder,
        see ``_bounded_level1``); a chip that exhausts its budget is
        excluded and the handle carries a ``partial`` marker. The
        degraded answer is the EXACT skyline of the surviving chips'
        records — NOT a subset of the true global skyline: a surviving
        point dominated only by excluded-chip data legitimately
        appears. What it does guarantee: every true-skyline point that
        lives on a surviving chip is present (the global skyline
        decomposes over chip-local skylines), and the missing record
        mass is bounded by the excluded chips' record share
        (RUNBOOK §2p)."""
        # heal before measuring: a quarantined chip's group is re-owned by
        # a healthy chip NOW, so this merge — and every later one — runs
        # full-strength instead of repeatedly degrading
        self.maybe_failover()
        h = _ShardedMergeHandle()
        h.emit_points = emit_points
        h.key = self.epoch_key
        h.explain, self._explain = self._explain, None
        use_cache = cascade.merge_cache_on(False)
        h.use_cache = use_cache
        cache = self._gm_cache if use_cache else None
        if cache is not None and cache["key"] == h.key:
            # no chip flushed since this two-level result: zero launches,
            # zero cross-chip traffic
            self.merge_cache_hits += 1
            self._inc("sharded.cache_hit")
            self._fnote("sharded.cache_hit", key=epoch_hex(h.key))
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
                    "clean": np.flatnonzero(cache["counts"] > 0).tolist(),
                    "skyline_size": int(cache["g"]),
                }
            return h
        self.merge_cache_misses += 1
        P, C, G = self.num_partitions, self.chips, self.group_size
        d = self.dims
        # -- level 1: one intra-chip tournament per device -----------------
        chip_counts: list[np.ndarray] = []
        chip_surv: list[np.ndarray] = []
        chip_g: list[int] = []
        chip_pts: list = []  # (w_c, d) device buffer on chip c, or None
        chip_summary: list[np.ndarray | None] = []
        want_prune = cascade.gate("chip_prune") and C > 1
        trace_id = h.explain.trace_id if h.explain is not None else None
        deadline_ms = chip_merge_deadline_ms()
        bounded = deadline_ms > 0 and C > 1
        failed: list[dict] = []
        for c, chip in enumerate(self._chips):
            # level-1 phase; its span in /trace shows which chip's local
            # tournament the merge wall went to
            with self.tracer.span_phase(
                "cross_chip/level1", self._spans, "chip_merge",
                trace_id=trace_id, tid=c + 1,
            ) as ph:
                t0 = time.perf_counter_ns()
                if bounded:
                    br = self._bounded_level1(
                        c, chip, want_prune, deadline_ms, failed
                    )
                    # the winning attempt's own wall (fault latency +
                    # merge, no backoff sleeps / hedge waits / failed
                    # attempts) — anything else would pollute the
                    # peer-median straggler signal with scheduler overhead
                    r, t0, t1 = br if br is not None else (None, t0, t0)
                else:
                    fault_point("sharded.chip_merge", chip=c)
                    with self._chip_locks[c]:
                        r = self._level1_chip(c, chip, want_prune)
                    t1 = time.perf_counter_ns()
                ph.args = {"chip": c, "level": 1}
                if r is None:
                    ph.args["excluded"] = True
                else:
                    ph.args["skyline"] = int(r[2])
            if r is None:
                # excluded this merge: the group contributes nothing and
                # the answer publishes marked partial (RUNBOOK §2p)
                chip_counts.append(np.zeros(G, dtype=np.int64))
                chip_surv.append(np.zeros(G, dtype=np.int64))
                chip_g.append(0)
                chip_pts.append(None)
                chip_summary.append(None)
                continue
            counts_c, surv_c, g_c, pts, summary = r
            chip_counts.append(counts_c)
            chip_surv.append(surv_c)
            chip_g.append(g_c)
            chip_pts.append(pts)
            chip_summary.append(summary)
            if self._fleet is not None:
                self._fleet.note_level1(c, g_c, (t1 - t0) / 1e6)
            if self._health is not None:
                self._health.note_merge_ok(c, (t1 - t0) / 1e6)
        if failed:
            lost = sum(
                int(self.records_seen[f["chip"] * G : (f["chip"] + 1) * G].sum())
                for f in failed
            )
            total = int(self.records_seen.sum())
            h.partial = {
                "excluded_chips": [f["chip"] for f in failed],
                "reasons": [f["reason"] for f in failed],
                "excluded_records": lost,
                # record-mass bound from the facade ledger: the surviving
                # chips' exact skyline drew on at least this fraction of
                # every record ingested so far (NOT a subset of the full
                # skyline — see global_merge_launch)
                "completeness_bound": (
                    round((total - lost) / total, 6) if total else 1.0
                ),
            }
            self.degraded_merges += 1
            self._inc("sharded.degraded")
            self._fnote(
                "sharded.degraded",
                excluded=h.partial["excluded_chips"],
                reasons=h.partial["reasons"],
                bound=h.partial["completeness_bound"],
            )
        concat_counts = np.concatenate(chip_counts)
        alive = np.array([g > 0 for g in chip_g], dtype=bool)
        considered = int(alive.sum())
        # level-2 phase: the witness prune over chip summaries, the gather
        # onto the root device and the pairwise launches (kernels may still
        # be in flight at harvest); its span in /trace is the interconnect
        with self.tracer.span_phase(
            "cross_chip/level2", self._spans, "cross_chip_merge",
            trace_id=trace_id, tid=0,
        ) as ph:
            pruned = np.zeros(C, dtype=bool)
            witness_of = np.full(C, -1, dtype=np.int64)
            if want_prune and considered > 1:
                rows = [
                    chip_summary[c]
                    if chip_summary[c] is not None
                    else np.full(2 * d + 2, np.inf, dtype=np.float32)
                    for c in range(C)
                ]
                pruned, witness_of = prune_witness_mask(
                    np.stack(rows), alive, d
                )
            npruned = int(pruned.sum())
            survivors = np.flatnonzero(alive & ~pruned)
            self.sharded_merges += 1
            self.chips_pruned_total += npruned
            self.chips_considered_total += considered
            CAPTURED.count("cross_chip/chips_considered", considered)
            CAPTURED.count("cross_chip/chips_pruned", npruned)
            # register the series at the first merge, not the first prune
            self._inc("sharded.merges")
            self._inc("sharded.chips_pruned", npruned)
            self._fnote(
                "sharded.merge", chips=C, alive=considered, pruned=npruned,
                survivors=len(survivors),
            )
            ph.args = {"level": 2, "survivors": len(survivors),
                       "pruned": npruned, "levels": 0}
            if not len(survivors):
                # every chip empty: the zero state needs no kernels
                h.cached = True
                h.result = (
                    concat_counts.astype(np.int64),
                    np.zeros(P, dtype=np.int64),
                    0,
                    np.empty((0, d), dtype=np.float32) if emit_points else None,
                )
                self._note_merge_info(
                    h, chip_g, considered, pruned, witness_of, survivors, 0,
                    [0],
                )
                return h
            # gather survivors onto the root device, ascending chip order
            root_dev = self._devices[0]
            leaves = []
            shipped = 0
            for c in survivors:
                g = chip_g[c]
                w = chip_pts[c].shape[0]
                # the interconnect crossing: the padded root buffer ships
                # to chip 0 — except chip 0's own root, already resident
                crossing = 0 if c == 0 else w
                shipped += crossing
                if self._fleet is not None:
                    self._fleet.note_level2(c, False, crossing)
                vals = jax.device_put(chip_pts[c], root_dev)
                # the chip root carries no pids; rebuild them host-side
                # from the per-partition survivor counts (root rows are
                # ascending local pid with per-partition storage order —
                # the invariant byte identity rides on)
                pid_np = np.zeros(w, dtype=np.int32)
                pid_np[:g] = np.repeat(
                    np.arange(G, dtype=np.int32) + c * G,
                    chip_surv[c].astype(np.int64),
                )
                pids = jax.device_put(pid_np, root_dev)
                cnt = jax.device_put(np.int32(g), root_dev)
                leaves.append((vals, pids, cnt, g))
            CAPTURED.count("cross_chip/rows_shipped", shipped)
            # pairwise tournament, adjacent pairs, odd tail passes through
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
            ph.args["levels"] = levels
            root_vals, root_pids, root_cnt, _ = nodes[0]
            h.root_vals = root_vals
            counts_dev = jax.device_put(
                concat_counts.astype(np.int32), root_dev
            )
            h.stats = tree_stats_device(counts_dev, root_pids, root_cnt, P)
            try:
                h.stats.copy_to_host_async()
            except AttributeError:
                pass
        self._note_merge_info(
            h, chip_g, considered, pruned, witness_of, survivors, levels, cand
        )
        return h

    def _level1_chip(self, c: int, chip, want_prune: bool):
        """One chip's intra-chip tournament, device-pinned: harvest stats,
        materialize the padded root points, and (under the chip prune)
        the (2d+2) summary row. Returns ``(counts, surv, g, pts,
        summary)``."""
        with self._dev(c):
            ch = chip.global_merge_launch(False)
            counts_c, surv_c, g_c, _ = chip.global_merge_harvest(ch)
            pts = None
            summary = None
            if g_c > 0:
                w = _active_bucket(max(g_c, 1))
                pts = chip.merge_points_device(ch, w)
                if want_prune:
                    # the chip root as a one-partition stack: its
                    # (1, 2d+2) [min_corner | witness | sums] summary
                    # is the whole cross-chip prune input — 2d+2 floats
                    # per chip instead of the root buffer
                    summary = np.asarray(
                        partition_summaries_device(
                            pts[None],
                            jnp.asarray(np.array([g_c], dtype=np.int32)),
                            w,
                        )
                    )[0]
        return counts_c, surv_c, g_c, pts, summary

    def _bounded_level1(
        self, c: int, chip, want_prune: bool, deadline_ms: float,
        failed: list,
    ):
        """Deadline-bounded level 1 for one chip: the merge runs on a
        watchdog thread with a per-chip budget, a bounded retry ladder
        (``SKYLINE_CHIP_MERGE_RETRIES`` extra attempts under exponential
        ``SKYLINE_CHIP_MERGE_BACKOFF_MS``), and optional straggler
        hedging (``SKYLINE_CHIP_HEDGE_MS`` > 0 races a second attempt;
        first result wins). Returns ``(level1_tuple, t0_ns, t1_ns)``
        with the WINNING attempt's own perf-counter interval (fault
        latency + merge wall, but no backoff sleeps, hedge waits, or
        failed-attempt time — the health/fleet straggler signal must
        reflect the device, not the rescue ladder), or ``None`` once
        the budget is exhausted — the chip is excluded from THIS answer
        and ChipHealth decides quarantine.

        Thread discipline: ``fault_point`` fires OUTSIDE the chip lock
        (an injected hang parks the attempt thread without wedging the
        lock, so hedges and retries stay live), while the merge itself
        runs INSIDE it — a ``PartitionSet`` is not thread-safe, so an
        abandoned attempt finishing late must never interleave with a
        sibling or a later merge on the same group. On a deadline
        timeout ``done`` is set before the exclusion is returned, so a
        still-parked attempt bows out at the lock check instead of
        merging a group the main thread has moved on from; an attempt
        already computing inside the lock is serialized against later
        ingest/flush/failover, which all take the chip lock. A
        genuinely wedged kernel holds the lock; every rescue then
        blocks behind it and the deadline exclusion is the only way
        out, which is the point.

        An unscoped ``InjectedCrash`` models a PROCESS death and
        re-raises on the calling thread; a chip-scoped one models this
        chip failing and counts against it."""
        from skyline_tpu.analysis.registry import env_float, env_int

        t_end = time.monotonic() + deadline_ms / 1000.0
        retries = max(0, env_int("SKYLINE_CHIP_MERGE_RETRIES", 1))
        backoff_s = (
            max(0.0, env_float("SKYLINE_CHIP_MERGE_BACKOFF_MS", 50.0)) / 1000.0
        )
        hedge_s = max(0.0, env_float("SKYLINE_CHIP_HEDGE_MS", 0.0)) / 1000.0
        attempt = 0
        while True:
            done = threading.Event()
            slot: dict = {}

            def run(done=done, slot=slot):
                s0 = time.perf_counter_ns()
                try:
                    fault_point("sharded.chip_merge", chip=c)
                    with self._chip_locks[c]:
                        if done.is_set():
                            return  # a sibling won or the deadline passed
                        r = self._level1_chip(c, chip, want_prune)
                        s1 = time.perf_counter_ns()
                except BaseException as e:  # InjectedCrash included
                    slot.setdefault("err", e)
                else:
                    slot.setdefault("ok", (r, s0, s1))
                finally:
                    done.set()

            threading.Thread(
                target=run, daemon=True, name=f"chip{c}-merge-a{attempt}"
            ).start()
            remaining = t_end - time.monotonic()
            if hedge_s > 0 and remaining > hedge_s and not done.wait(hedge_s):
                # straggler hedge: whichever attempt takes the chip lock
                # first computes; the loser sees done set and bows out
                threading.Thread(
                    target=run, daemon=True, name=f"chip{c}-merge-hedge"
                ).start()
            finished = done.wait(max(0.0, t_end - time.monotonic()))
            if finished and "ok" in slot:
                return slot["ok"]
            if finished and "err" in slot:
                e = slot["err"]
                if isinstance(e, InjectedCrash) and not e.chip_scoped:
                    raise e  # process death: never absorbed as a chip fault
                attempt += 1
                if attempt <= retries and time.monotonic() + backoff_s < t_end:
                    time.sleep(backoff_s)
                    backoff_s *= 2
                    continue
                reason = f"{type(e).__name__}: {e}"
                if self._health is not None:
                    self._health.note_merge_error(c, reason)
            else:
                # abandon the in-flight attempt(s): a thread still parked
                # at its fault point must see done set when it reaches the
                # lock check, or it would run the full level-1 merge
                # concurrently with whatever the main thread does next on
                # this group (the exact slow-chip race this path targets)
                done.set()
                reason = f"deadline {deadline_ms:.0f}ms exceeded"
                if self._health is not None:
                    self._health.note_merge_timeout(c, deadline_ms)
            failed.append({"chip": c, "reason": reason})
            self._fnote("sharded.chip_excluded", chip=c, reason=reason)
            return None

    # -- online partition-group failover -------------------------------------

    def maybe_failover(self) -> list[int]:
        """Re-own every quarantined chip's partition group onto a healthy
        owner (called at merge-launch entry and from worker idle ticks).
        Returns the chips healed. No-op without an attached ChipHealth,
        with ``SKYLINE_CHIP_FAILOVER=0``, or when nothing is
        quarantined."""
        if self._health is None or not chip_failover_enabled():
            return []
        quarantined = self._health.quarantined()
        if not quarantined:
            return []
        healed = []
        for c in quarantined:
            owner = next(
                (
                    o
                    for o in range(self.chips)
                    if o != c and not self._health.is_quarantined(o)
                ),
                None,
            )
            if owner is None:
                self._fnote("sharded.failover_stalled", quarantined=quarantined)
                break  # no healthy owner left; stay degraded
            try:
                self.failover(c, owner)
            except TimeoutError:
                # a still-running merge attempt holds this chip's lock
                # past the bounded wait: capturing the group's state now
                # would tear it mid-merge, so stay degraded and retry at
                # the next merge launch / idle tick (the flight note is
                # written by failover itself)
                continue
            healed.append(c)
        return healed

    def failover(self, c: int, owner: int | None = None) -> None:
        """Re-own chip ``c``'s partition group on ``owner``'s device —
        chip-local, no stop-the-world: only this group's state moves,
        every other chip keeps serving.

        The group's per-partition state (resident skylines + pending
        rows, exactly what checkpoint restore carries) round-trips
        through ``audit_state`` into a fresh ctor-identical
        ``PartitionSet`` pinned to the owner's device, and
        ``restore_all``'s byte-faithful contract (the crash-replay tests'
        invariant) makes the healed group merge byte-identically to an
        uninterrupted run. The chip WAL supplies the replay-window
        accounting: ``failover_window(c)`` reports the chip's journal
        records since the last common barrier — the chip-local segment a
        physical re-owner must re-apply — and the newest journaled epoch
        digest, recorded in ``last_failover`` for the drill to verify
        currency against.

        The capture + swap run under the chip's merge lock: with
        ``SKYLINE_CHIP_FAIL_THRESHOLD=1`` a single slow merge attempt
        quarantines the chip while that attempt is still computing
        inside the lock, and reading ``audit_state()`` concurrently
        would tear the very state the byte-identical-post-heal
        guarantee rides on. The wait is bounded
        (``SKYLINE_CHIP_FAILOVER_LOCK_MS``) so a truly wedged kernel
        cannot stall failover forever — on timeout this raises
        ``TimeoutError`` and ``maybe_failover`` retries on a later
        tick."""
        if owner is None:
            owner = next(
                (
                    o
                    for o in range(self.chips)
                    if o != c
                    and (
                        self._health is None
                        or not self._health.is_quarantined(o)
                    )
                ),
                None,
            )
            if owner is None:
                raise RuntimeError(f"no healthy owner for chip {c}")
        t0 = time.perf_counter_ns()
        window = None
        if self._chip_wal is not None:
            try:
                window = self._chip_wal.failover_window(c)
            except (OSError, ValueError, KeyError):
                window = None  # journal unreadable: heal without the audit
        lock = self._chip_locks[c]
        wait_ms = failover_lock_ms()
        if not lock.acquire(timeout=wait_ms / 1000.0):
            self._inc("sharded.failover_lock_timeouts")
            self._fnote(
                "sharded.failover_lock_timeout", chip=c, wait_ms=wait_ms
            )
            raise TimeoutError(
                f"chip {c} merge lock still held after {wait_ms:.0f}ms; "
                "failover deferred"
            )
        try:
            old = self._chips[c]
            old_epoch = epoch_hex(old.epoch_key)
            with self._dev(c):
                skies, pendings = old.audit_state()
            with jax.default_device(self._devices[owner]):
                grp = PartitionSet(
                    self.group_size,
                    self.dims,
                    self.buffer_size,
                    initial_capacity=self._initial_capacity,
                    tracer=self.tracer,
                    flush_policy=self.flush_policy,
                    overlap_rows=self.overlap_rows,
                    window_capacity=self._window_capacity,
                    counters=self._counters,
                )
                grp.restore_all(skies, pendings)
            self._chips[c] = grp
            self._devices[c] = self._devices[owner]
        finally:
            lock.release()
        grp.attach_observability(profiler=self._profiler, flight=self._flight)
        self._gm_cache = None  # the cached two-level result is stale now
        wall_ms = (time.perf_counter_ns() - t0) / 1e6
        self.failovers += 1
        self.last_failover = {
            "chip": c,
            "owner": owner,
            "wall_ms": round(wall_ms, 3),
            "epoch": old_epoch,
            "wal_window": window,
        }
        self._inc("sharded.failovers")
        self._fnote(
            "sharded.failover", chip=c, owner=owner,
            wall_ms=round(wall_ms, 3), wal_window=window,
        )
        if self._health is not None:
            self._health.heal(c)

    def _note_merge_info(
        self, h, chip_g, considered, pruned, witness_of, survivors, levels,
        cand,
    ) -> None:
        """Record the two-level merge's attribution: ``last_chip_info``
        for /stats, the chips + merge blocks on the riding EXPLAIN plan,
        and the aggregated ``last_tree_info`` the engine's merge_tree
        stats block reads."""
        C, G = self.chips, self.group_size
        pruned_list = [
            {"chip": int(c), "witness": int(witness_of[c])}
            for c in np.flatnonzero(pruned)
        ]
        per_chip = []
        for c in range(C):
            lo, hi = c * G, (c + 1) * G
            per_chip.append({
                "chip": c,
                "skyline": int(chip_g[c]),
                "records": int(self.records_seen[lo:hi].sum()),
                "pending": int(self._pending_rows[lo:hi].sum()),
                "pruned": bool(pruned[c]),
            })
        info = {
            "chips": C,
            "group_size": G,
            "alive": considered,
            "pruned": pruned_list,
            "survivors": [int(c) for c in survivors],
            "levels": levels,
            "candidates_per_level": cand,
            "per_chip": per_chip,
        }
        if h.partial is not None:
            info["degraded"] = h.partial
        if self._fleet is not None:
            for c in np.flatnonzero(pruned):
                self._fleet.note_level2(int(c), True, 0)
            imb = self._fleet.note_merge_done()
            info["imbalance"] = {
                "imbalance_index": imb["imbalance_index"],
                "skew_score": imb["skew_score"],
            }
        self.last_chip_info = info
        chip_infos = [c.last_tree_info for c in self._chips]
        intra_pruned = sum(
            i["partitions_pruned"] for i in chip_infos if i is not None
        )
        considered_parts = int(
            (np.concatenate([c._count_ub for c in self._chips]) > 0).sum()
        )
        self.last_tree_info = {
            "levels": max(
                (i["levels"] for i in chip_infos if i is not None), default=0
            ) + levels,
            "partitions_pruned": intra_pruned,
            "candidates_per_level": cand,
            "pruned_fraction": (
                intra_pruned / considered_parts if considered_parts else 0.0
            ),
        }
        if h.explain is not None:
            h.explain.merge = {
                "path": "sharded_tree",
                "cached": False,
                "epoch_key": h.key.hex(),
                "dirty_fraction": None,
                "dirty": list(range(self.num_partitions)),
                "clean": [],
            }
            if h.partial is not None:
                h.explain.merge["partial"] = True
            h.explain.chips = info

    def global_merge_harvest(self, handle):
        h = handle
        # the engine reads this right after harvest: None = full answer,
        # a dict = mark the emitted result/snapshot degraded (§2p)
        self.last_partial = h.partial
        if h.cached:
            return h.result
        P = self.num_partitions
        with self.tracer.phase("query/global_stats_sync"):
            svec = np.asarray(h.stats, dtype=np.int64)
        counts = svec[:P].copy()
        surv = svec[P : 2 * P].copy()
        g = int(svec[2 * P])
        if h.explain is not None and h.explain.merge is not None:
            h.explain.merge["skyline_size"] = g
        if self._chip_wal is not None and h.partial is None:
            # a degraded merge never stamps a barrier: barrier records
            # certify an ALL-chips consistent cut, and the failover replay
            # window is measured from the last such cut
            self._barrier_seq += 1
            self._chip_wal.merge_barrier(
                self._barrier_seq,
                epoch_hex(h.key),
                [epoch_hex(c.epoch_key) for c in self._chips],
                [int(x) for x in (counts.reshape(
                    self.chips, self.group_size
                ).sum(axis=1))],
            )
        pts = None
        if h.use_cache and h.partial is None:
            gcap = 2 * _next_pow2(max(g, 1))
            pts_dev = tree_points_device(h.root_vals, gcap)
            self._gm_cache = {
                "key": h.key,
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
                pts = np.asarray(
                    tree_points_device(h.root_vals, out_cap)
                )[:g].copy()
        return counts, surv, g, pts

    def _cached_points(self) -> np.ndarray:
        c = self._gm_cache
        if c["pts_host"] is None:
            with self.tracer.phase("query/points_transfer"):
                c["pts_host"] = np.asarray(c["pts_dev"])[: c["g"]].copy()
        return c["pts_host"].copy()

    def merge_points_device(self, handle, out_cap: int):
        """Device buffer of a harvested two-level merge's skyline points,
        ``(out_cap, d)``, rows past the true count +inf-padded — the same
        contract as ``PartitionSet.merge_points_device``, so the cluster
        plane's host-level tournament (cluster/merge.py) can feed a
        sharded host's root into ``tree_pair_merge`` without a host
        round-trip. Valid between a harvest and the next flush; prefers
        the facade cache buffer when it describes the handle's epoch."""
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
        return tree_points_device(h.root_vals, out_cap)

    # -- snapshots / audit / checkpoint --------------------------------------

    def sky_counts(self) -> np.ndarray:
        return np.concatenate([c.sky_counts() for c in self._chips])

    def snapshot(self, p: int) -> np.ndarray:
        # flush ALL chips (cadence parity with the single-device set —
        # its snapshot() flushes every partition), then read one
        self.flush_all()
        t0 = time.perf_counter_ns()
        c, lp = self._loc(p)
        with self._dev(c):
            out = self._chips[c].skyline_host(lp)
        self._processing_base_ns += time.perf_counter_ns() - t0
        return out

    def skyline_host(self, p: int) -> np.ndarray:
        c, lp = self._loc(p)
        with self._dev(c):
            return self._chips[c].skyline_host(lp)

    def pending_rows_of(self, p: int) -> np.ndarray:
        c, lp = self._loc(p)
        return self._chips[c].pending_rows_of(lp)

    def audit_state(self) -> tuple[list[np.ndarray], list[np.ndarray]]:
        skies: list[np.ndarray] = []
        pendings: list[np.ndarray] = []
        for c, chip in enumerate(self._chips):
            # serialized against any deadline-abandoned merge attempt
            # still computing on this group (_bounded_level1)
            with self._chip_locks[c], self._dev(c):
                s, pd = chip.audit_state()
            skies.extend(s)
            pendings.extend(pd)
        return skies, pendings

    def restore_all(
        self, skies: list[np.ndarray], pendings: list[np.ndarray]
    ) -> None:
        assert len(skies) == len(pendings) == self.num_partitions
        G = self.group_size
        for c, chip in enumerate(self._chips):
            with self._chip_locks[c], self._dev(c):
                chip.restore_all(
                    skies[c * G : (c + 1) * G],
                    pendings[c * G : (c + 1) * G],
                )
        self.max_seen_id[:] = -1
        self.start_time_ms = [None] * self.num_partitions
        self.records_seen[:] = 0
        self._processing_base_ns = 0
        for p, pending in enumerate(pendings):
            self._pending_rows[p] = pending.shape[0]
        self._gm_cache = None

    # -- stats ---------------------------------------------------------------

    def sharded_stats(self) -> dict:
        out = {
            "chips": self.chips,
            "group_size": self.group_size,
            "merges": self.sharded_merges,
            "chips_pruned": self.chips_pruned_total,
            "chips_considered": self.chips_considered_total,
            "pruned_chip_fraction": (
                self.chips_pruned_total / self.chips_considered_total
                if self.chips_considered_total
                else 0.0
            ),
            "cache": {
                "hits": self.merge_cache_hits,
                "misses": self.merge_cache_misses,
            },
            "devices": [str(d) for d in self._devices],
            "last": self.last_chip_info,
            "degraded_merges": self.degraded_merges,
            "failovers": self.failovers,
            "last_failover": self.last_failover,
        }
        if self._fleet is not None:
            out["fleet"] = self._fleet.doc()
        if self._chip_wal is not None:
            out["chip_wal"] = self._chip_wal.stats()
        if self._health is not None:
            out["health"] = self._health.doc()
        return out


class ShardedEngine(SkylineEngine):
    """``SkylineEngine`` with the partition set sharded into per-chip
    groups and queries answered by the two-level tournament. Drop-in:
    same config, same wire results, same serving/audit planes — the
    published skyline is byte-identical to the single-device engine's.
    """

    def __init__(self, config, chips: int, tracer=None, telemetry=None):
        if config.ingest == "device":
            raise ValueError(
                "ingest='device' is single-device only; the sharded "
                "engine routes on host"
            )
        self.mesh_chips = int(chips)
        super().__init__(config, mesh=None, tracer=tracer, telemetry=telemetry)
        # swap the single-device set for the sharded facade (the tiny
        # just-built empty set is dropped before any row reaches it)
        self.pset = ShardedPartitionSet(
            config.num_partitions,
            config.dims,
            config.buffer_size,
            chips=self.mesh_chips,
            initial_capacity=config.initial_capacity,
            tracer=self.tracer,
            flush_policy=config.flush_policy,
            overlap_rows=config.overlap_rows,
            window_capacity=config.window_capacity,
            counters=telemetry.counters if telemetry is not None else None,
        )
        self.partitions = [
            PartitionView(self.pset, i) for i in range(config.num_partitions)
        ]
        fleet = None
        if telemetry is not None and fleet_enabled():
            from skyline_tpu.telemetry.fleet import FleetStats

            fleet = FleetStats(self.mesh_chips, flight=telemetry.flight)
            # hang it on the hub: both HTTP surfaces serve /fleet and the
            # skyline_chip_*{chip=...} families straight from there
            telemetry.fleet = fleet
        self.pset.attach_observability(
            profiler=self.profiler,
            flight=telemetry.flight if telemetry is not None else None,
            fleet=fleet,
            spans=telemetry.spans if telemetry is not None else None,
        )
        # chip-fault-tolerance plane (RUNBOOK §2p): merge outcomes feed
        # the scores, quarantine drives exclusion + online failover; the
        # hub reference serves the /health chip block
        from skyline_tpu.resilience.health import ChipHealth

        self.health = ChipHealth(self.mesh_chips, telemetry=telemetry)
        self.pset.attach_health(self.health)
        if telemetry is not None:
            telemetry.health = self.health

    def stats(self, include_skyline_counts: bool = False) -> dict:
        out = super().stats(include_skyline_counts)
        out["sharded"] = self.pset.sharded_stats()
        return out
