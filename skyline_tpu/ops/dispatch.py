"""Backend dispatch for the dominance hot ops.

On TPU the Pallas kernel (VMEM-tiled, min/max cascade, triangular skip) is
the fast path — see artifacts/kernels_tpu.json (benchmarks/kernels.py) for
the measured Pallas-vs-scan table at several N. On CPU (tests, virtual
meshes) Pallas would need interpret mode, so the scan kernel is used.
Resolution happens once at first call.
"""

from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def rank_cascade() -> bool:
    """``SKYLINE_RANK_CASCADE`` selects the dense-rank dominance cascade
    for the self-skyline passes (ops/pallas_dominance.py rank kernels).
    Default OFF until the hardware A/B lands: the op-count argument (2 vs 3
    VPU ops/dim) favors ranks, but rank_transform's two sorts + searchsorted
    per pass are unmeasured on TPU — run ``benchmarks/rank_cascade.py``
    on the chip (writes artifacts/rank_cascade_ab.json) and flip the default only on a >=1.15x
    measured win. Read lazily at trace time; already-compiled executables
    are unaffected by later changes."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_RANK_CASCADE", False)


def merge_cache_enabled() -> bool:
    """``SKYLINE_MERGE_CACHE`` gates the epoch-keyed global-merge cache in
    ``stream/batched.py``: repeated query triggers between flushes reuse the
    previous merge's result (zero kernel launches), and partially-dirty
    states merge ``cached_global ∪ dirty skylines`` instead of the full
    union. Default ON — results are provably identical (merge law +
    transitivity, see PartitionSet.global_merge_stats); set ``0`` to force
    the from-scratch full merge on every trigger (the A/B baseline the
    equivalence tests and benchmarks/merge_cache.py compare against). Read
    lazily per query, so tests can flip it per-case."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_MERGE_CACHE", True)


def delta_dirty_cutoff() -> float:
    """``SKYLINE_DELTA_CUTOFF``: max dirty-partition fraction for the
    delta-merge path. Above it the full union merge runs instead — once
    most partitions changed, ``cached_global ∪ dirty`` approaches the full
    union anyway and the delta assembly's extra executable shapes (one per
    dirty pattern) buy nothing. Default 0.75; ``0`` disables delta merges
    while keeping the exact-hit cache."""
    from skyline_tpu.analysis.registry import env_float

    return env_float("SKYLINE_DELTA_CUTOFF", 0.75)


def flush_stage_depth() -> int:
    """``SKYLINE_STAGE_DEPTH``: how many flush rounds the host stages ahead
    of the in-flight merge kernel (assemble + device_put issued before the
    previous round's kernel is awaited). 1 = double buffering (default);
    higher values deepen the pipeline at the cost of that many staged
    micro-batches resident in host+device memory; 0 disables staging
    (assemble-then-dispatch strictly in order, the pre-pipelining
    behavior)."""
    from skyline_tpu.analysis.registry import env_int

    return max(0, env_int("SKYLINE_STAGE_DEPTH", 1))


def merge_tree_enabled() -> bool:
    """``SKYLINE_MERGE_TREE`` gates the pruned tournament-tree global merge
    in ``stream/batched.py``: non-empty partitions (minus bound-pruned ones)
    merge pairwise up a binary tree so each level's quadratic kernel runs on
    a halved, already-pruned candidate set instead of one O(U²) pass over
    the full union. Default ON for d > 2 (d <= 2 keeps the sort-sweep flat
    path, which is strictly cheaper); set ``0`` to force the flat union
    merge — the A/B baseline tests/test_merge_tree.py and
    benchmarks/merge_cache.py compare against. Results are byte-identical
    either way (merge law + stable compaction order). Read lazily per
    query."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_MERGE_TREE", True)


def merge_prune_enabled() -> bool:
    """``SKYLINE_MERGE_PRUNE`` gates the O(P²·d) partition prefilter ahead
    of the tree merge: partition B is dropped wholesale when another
    partition's witness point (its min-row-sum survivor) dominates B's
    min-corner — then it dominates every point of B. The prune relation is
    a strict partial order (witness chains cannot cycle), so simultaneous
    pruning is sound and at least one partition always survives. Default
    ON; set ``0`` to feed every non-empty partition into the tree (the
    digest check in scripts/obs_smoke.sh compares both settings). Read
    lazily per query."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_MERGE_PRUNE", True)


def chip_prune_enabled() -> bool:
    """``SKYLINE_CHIP_PRUNE`` gates the CHIP-level witness prefilter in the
    sharded engine's two-level merge (``distributed/sharded.py``): each
    chip-local tournament root is summarized as one
    ``[min_corner | witness | sums]`` row and a chip whose min-corner is
    strictly dominated by another chip's witness point is skipped before
    any cross-chip transfer — whole device results never cross the
    interconnect. The soundness argument is the partition prune's
    (``merge_prune_enabled``) applied one level up, so the published bytes
    are identical either way. Default ON; set ``0`` to gather every
    non-empty chip (the A/B baseline benchmarks/sharded_engine.py and
    scripts/mesh_smoke.sh compare against). Read lazily per query."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_CHIP_PRUNE", True)


def host_prune_enabled() -> bool:
    """``SKYLINE_CLUSTER_HOST_PRUNE`` gates the HOST-level witness
    prefilter in the cluster coordinator's three-level merge
    (``cluster/merge.py``): each host's tournament root is summarized as
    one ``[min_corner | witness | sums]`` row, and a host whose
    min-corner is strictly dominated by another host's witness ships
    ZERO point rows to the coordinator — the chip prune
    (``chip_prune_enabled``) applied one level up, same soundness
    argument, so the published bytes are identical either way. Default
    ON; set ``0`` to gather every non-empty host (the A/B baseline
    benchmarks/cluster.py compares against). Read lazily per query."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_CLUSTER_HOST_PRUNE", True)


def chip_barrier_policy() -> str:
    """``SKYLINE_CHIP_BARRIER`` picks when the sharded engine writes its
    chip-consistency barrier records (``resilience/chip_wal.py``):
    ``merge`` (default) stamps every completed two-level merge with each
    chip's epoch digest so crash replay can verify all groups reconstruct
    the same global state; ``checkpoint`` writes barriers only at
    checkpoint time (fewer records, coarser replay verification);
    ``off`` disables the chip WAL plane entirely. Read lazily per
    attach/harvest."""
    from skyline_tpu.analysis.registry import env_str

    v = env_str("SKYLINE_CHIP_BARRIER", "merge")
    return v if v in ("merge", "checkpoint", "off") else "merge"


def chip_merge_deadline_ms() -> float:
    """``SKYLINE_CHIP_MERGE_DEADLINE_MS``: per-chip budget for one level-1
    tournament inside the sharded two-level merge. ``0`` (default)
    disables the bound — the historical synchronous loop, where one sick
    chip wedges the query. With a deadline the facade runs each chip's
    merge on a watchdog thread: a chip that misses the budget (after the
    ``SKYLINE_CHIP_MERGE_RETRIES``/``SKYLINE_CHIP_HEDGE_MS`` ladder) is
    EXCLUDED from this answer, the surviving-chips skyline publishes
    marked ``partial`` (RUNBOOK §2p), and ChipHealth quarantines the
    offender. Read lazily per merge launch."""
    from skyline_tpu.analysis.registry import env_float

    return max(0.0, env_float("SKYLINE_CHIP_MERGE_DEADLINE_MS", 0.0))


def failover_lock_ms() -> float:
    """``SKYLINE_CHIP_FAILOVER_LOCK_MS``: bounded wait for a chip's merge
    lock before ``failover`` captures the group's state. A slow merge
    attempt may still be computing inside the lock when its chip
    quarantines (``SKYLINE_CHIP_FAIL_THRESHOLD=1`` makes this the COMMON
    case); failover must wait it out — ``audit_state`` read concurrently
    would tear the state byte-identical healing rides on — but a truly
    wedged kernel must not stall failover forever, so past this bound
    the attempt is abandoned for this tick and retried at the next
    merge launch / worker idle tick. Read lazily per failover."""
    from skyline_tpu.analysis.registry import env_float

    return max(0.0, env_float("SKYLINE_CHIP_FAILOVER_LOCK_MS", 5000.0))


def chip_failover_enabled() -> bool:
    """``SKYLINE_CHIP_FAILOVER`` gates online partition-group failover
    (``distributed/sharded.py`` ``maybe_failover``): at merge-launch (and
    worker idle ticks) a quarantined chip's partition group is re-owned
    by a healthy chip — state carried over byte-faithfully, currency
    checked against the chip's WAL window since the last common barrier —
    and the slot heals, no stop-the-world restart. Default ON; set ``0``
    to leave quarantined chips excluded until an operator intervenes
    (answers stay degraded). Read lazily per launch."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_CHIP_FAILOVER", True)


def flush_prefilter_enabled() -> bool:
    """``SKYLINE_FLUSH_PREFILTER`` gates the quantized grid prefilter ahead
    of the flush merge path (``stream/batched.py``): each partition keeps a
    device-computed grid summary of its resident skyline (per-dim boundary
    ladder + representative-cell codes, refreshed async at flush tails), and
    incoming batch rows whose cell is strictly dominated by a representative
    cell are dropped on the host before any merge kernel launches — an
    O(B·C) byte-compare pass with C ≪ S. Sound by construction: a cell-level
    strict dominance certificate implies strict f32 dominance (see RUNBOOK
    §2g), and a stale summary only under-drops (skyline evolution preserves
    transitive dominators). Default ON; set ``0`` for the exact-only
    baseline (byte-identical output, asserted in tests/test_flush_cascade.py
    and scripts/obs_smoke.sh). Read lazily per flush."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_FLUSH_PREFILTER", True)


def mixed_precision_enabled() -> bool:
    """``SKYLINE_MIXED_PRECISION`` gates the bf16 margin pass inside the
    flush dominance kernels (``ops/sfs.py``, ``ops/pallas_dominance.py``,
    ``stream/window.py`` merge steps): pairs decided OUTSIDE an explicit
    bf16 error margin are final, only ambiguous pairs re-run in f32, so the result is bit-exact vs the pure
    f32 kernels (margin-correctness argument in RUNBOOK §2g). v5e has no
    bf16 VPU, so there the pass rounds to bf16 and compares in f32; whether
    it pays on the chip is not measured yet (PERF.md). Default: ON
    on TPU, OFF elsewhere — XLA's CPU backend EMULATES bf16 (upcast +
    round-trip per op), which turns the "cheap" margin pass into a ~4×
    merge-kernel pessimization on the fallback (measured at n=128K 8D:
    6.1s → 23.1s). An explicit ``SKYLINE_MIXED_PRECISION=0``/``1`` always
    wins, on any backend. Threaded as a static jit argument from the flush
    orchestration, so flipping it per-call really switches executables
    (unlike trace-time env reads)."""
    from skyline_tpu.analysis.registry import env_bool

    # env_bool falls back to the default for unset/empty/unrecognized, so
    # the backend-derived default applies exactly when no explicit value set
    return env_bool("SKYLINE_MIXED_PRECISION", on_tpu())


def query_overlap_enabled() -> bool:
    """``SKYLINE_QUERY_OVERLAP`` gates the overlapped query sync in
    ``stream/engine.py``: a trigger launches the global merge and returns
    immediately, ingestion continues while the merge kernels run, and the
    result is harvested (the only blocking sync) at emission —
    ``poll_results`` / the next trigger / ``stats()``. Default ON for
    single-host engines; set ``0`` to restore the blocking
    launch-then-sync trigger path. Emitted results are identical either
    way. Read lazily per trigger."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_QUERY_OVERLAP", True)


def freshness_enabled() -> bool:
    """``SKYLINE_FRESHNESS`` gates the event-time freshness lineage
    (``telemetry/freshness.py``): per-batch event-time stamps carried
    host-side through ingest → flush → merge → publish → read, the
    ``skyline_freshness_lag_ms{stage=...}`` histograms, and the
    ``staleness_ms`` field on ``/skyline``. Pure host bookkeeping — a few
    float compares per micro-batch, nothing inside jit — so default ON;
    set ``0`` to drop even that (the A/B baseline in
    ``benchmarks/freshness.py``). Read lazily at engine construction."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_FRESHNESS", True)


def kernel_profile_enabled() -> bool:
    """``SKYLINE_KERNEL_PROFILE`` gates the per-dispatch-signature kernel
    profiler (``telemetry/profiler.py``): every ``flush/merge_kernel``
    dispatch is additionally timed under its (variant, d, N-bucket,
    backend, mp) signature and a ``kernel/<variant>`` span lands in the
    trace ring. Two ``perf_counter_ns`` reads + one lock per dispatch,
    host-side only; default ON, set ``0`` for the unprofiled baseline
    (``benchmarks/freshness.py`` A/B). Read lazily at engine
    construction."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_KERNEL_PROFILE", True)


def explain_enabled() -> bool:
    """``SKYLINE_EXPLAIN`` gates the per-query EXPLAIN plane
    (``telemetry/explain.py``): one ``QueryPlan`` minted per trigger and
    annotated host-side along launch → tree/prune → harvest → publish,
    served at ``GET /explain`` and inline via ``/skyline?explain=1``.
    Cost is a handful of counter snapshots and small dict writes per
    QUERY (zero per ingest batch, nothing inside jit), so default ON;
    set ``0`` for the no-plan baseline (``benchmarks/explain.py`` A/B).
    Read lazily at engine construction."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_EXPLAIN", True)


def audit_enabled() -> bool:
    """``SKYLINE_AUDIT`` gates the online audit plane (``audit/``): a
    sampled fraction of published snapshots (``SKYLINE_AUDIT_SAMPLE``)
    is recomputed from partition state through the independent host
    oracle and compared byte-for-byte, with divergences frozen into
    repro bundles under ``SKYLINE_AUDIT_DIR``. Checks run host-side
    after the answer is already published — nothing enters jit and the
    hot path only pays a sampling-accumulator update — so default ON;
    set ``0`` for the unaudited baseline (``benchmarks/audit.py`` A/B).
    Read lazily at engine construction."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_AUDIT", True)


def fleet_enabled() -> bool:
    """``SKYLINE_FLEET`` gates the per-chip fleet plane
    (``telemetry/fleet.py``) on the sharded engine: ingest/flush/merge
    accounting per partition group, level-2 prune outcomes, interconnect
    row counts, the imbalance index + skew ring, the per-chip child spans
    under the tournament merge, and ``GET /fleet``. Cost is a few list
    adds per flush/merge on the HOST side of an already host-orchestrated
    tournament (nothing inside jit; the identity law is unaffected —
    ``benchmarks/fleet.py`` asserts byte-identity), so default ON; set
    ``0`` for the unobserved baseline. No-op on flat (non-sharded)
    engines. Read lazily at engine construction."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_FLEET", True)


def workload_enabled() -> bool:
    """``SKYLINE_WORKLOAD`` gates the streaming workload characterizer
    (``telemetry/workload.py``): a bounded per-batch sample feeds
    per-dimension quantile sketches, a correlation estimate, and drift
    detection, classifying the stream uniform/correlated/anti_correlated
    — the regime tag EXPLAIN stamps on every answered query and the
    substrate the ROADMAP's auto-tuner will read. Cost is one numpy pass
    over at most ``SKYLINE_WORKLOAD_SAMPLE_CAP`` rows per ingest batch
    (host-side, nothing inside jit, skyline bytes untouched), so default
    ON; set ``0`` for the uncharacterized baseline
    (``benchmarks/fleet.py`` A/B). Read lazily at engine construction."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_WORKLOAD", True)


def tuner_enabled() -> bool:
    """``SKYLINE_TUNER`` gates the closed-loop dispatch tuner
    (``telemetry/tuner.py``): an online controller consuming the
    WorkloadCharacterizer regime + drift events, KernelProfiler EMAs, and
    SLO burn, and retuning cascade-table pins/knobs per (regime,
    signature) with bounded per-epoch moves. Safe by construction — it
    may only select table rows whose byte-identity oracle is registered
    (``ops/cascade.py``), explicit env knobs always beat its overrides,
    and it stays passive until a workload epoch closes AND
    ``SKYLINE_TUNER_EPOCH_S`` elapses — so default ON; set ``0`` for the
    static-dispatch baseline (``benchmarks/tuner.py`` A/B). Read lazily
    at engine construction."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_TUNER", True)


def profile_cost_enabled() -> bool:
    """``SKYLINE_PROFILE_COST`` additionally captures XLA
    ``cost_analysis()`` FLOPs/bytes per dispatch signature via a one-shot
    ahead-of-time lower+compile the first time each signature is seen.
    The AOT compile is seconds-expensive and its executable is discarded,
    so default OFF — flip on for a profiling session when ``/profile``
    should carry arithmetic-intensity columns. Read lazily per
    signature."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_PROFILE_COST", False)


def sorted_sfs_mode() -> str:
    """``SKYLINE_SORTED_SFS``: the sorted-order SFS dominance cascade for
    d > 2 (``ops/sorted_sfs.py`` — dedup + f64 sum-sort + blocked scan
    with exact in-block tiles for the ambiguous equal-sum band;
    byte-identical masks, see RUNBOOK §2m). ``auto`` (default) picks per
    (d, N, backend) signature from measured KernelProfiler wall data —
    each candidate runs once to seed its EMA, then the cheaper one wins;
    ``on`` forces the sorted host path, ``off`` keeps the device kernels
    only. Host NumPy, so it only ever applies to concrete (non-traced)
    arrays on non-TPU backends — inside jit and on TPU the device kernels
    always run. Read lazily per call."""
    from skyline_tpu.analysis.registry import env_str

    v = env_str("SKYLINE_SORTED_SFS", "auto")
    return v if v in ("auto", "on", "off") else "auto"


def device_cascade_mode() -> str:
    """``SKYLINE_DEVICE_CASCADE``: the device-side sorted dominance
    cascade (``ops/device_cascade.py`` — on-device dedup + f32 sum-key
    sort with a certified error radius + blocked buffer/band scans;
    byte-identical masks, see RUNBOOK §2t). Unlike the §2m host cascade
    it is pure lax over static shapes, so it applies ON TPU and INSIDE
    jit. ``auto`` (default) picks per (variant, d, N-bucket, backend,
    mp) signature from measured KernelProfiler wall data — concrete
    calls explore and record, traced call sites only swap it in on
    existing measured evidence (nothing records under a tracer); ``on``
    forces the cascade everywhere including under trace; ``off`` keeps
    the quadratic device kernels. Read lazily per call (trace time for
    jitted callers)."""
    from skyline_tpu.analysis.registry import env_str

    v = env_str("SKYLINE_DEVICE_CASCADE", "auto")
    return v if v in ("auto", "on", "off") else "auto"


def choose_variant(profiler, candidates, d: int, n: int, mp: bool = False):
    """Profiler-driven dispatch: pick among ``candidates`` (variant-name
    strings, preference-ordered) under signature (d, N-bucket, backend).

    Any candidate without measured wall data runs next (first listed
    wins), so each variant seeds its EMA exactly once per signature;
    after that the minimum EMA wins every time. Exploration is
    per-signature STICKY (``KernelProfiler.claim_explore``): the first
    caller to claim an unmeasured candidate runs it; until its record
    lands, other calls under the same signature fall back to the best
    measured candidate instead of re-paying the cold path — adding a new
    candidate row can no longer stall a hot flush loop repeatedly. With
    no profiler at all, the first candidate is the standing choice."""
    if profiler is None:
        return candidates[0]
    claim = getattr(profiler, "claim_explore", None)
    emas = []
    unmeasured = []
    for c in candidates:
        e = profiler.ema_ms(c, d, n, mp)
        if e is None:
            unmeasured.append(c)
        else:
            emas.append((e, c))
    if not unmeasured:
        return min(emas)[1]
    if claim is None:
        # foreign profiler without the claim API: legacy explore-first
        return unmeasured[0]
    for c in unmeasured:
        if claim(c, d, n, mp):
            return c
    # every unmeasured candidate is already claimed by an in-flight
    # exploration: serve measured data rather than stalling again
    if emas:
        return min(emas)[1]
    return candidates[0]


# the profiler skyline_mask_auto's host-path records into / chooses from;
# the engine shares its telemetry profiler here so /profile and EXPLAIN
# see mask dispatches too (tests and bare callers get a private default)
_MASK_PROFILER = None


def register_profiler(profiler) -> None:
    """Share an engine's KernelProfiler with the dispatch chooser (last
    registration wins — profiler data is observability, not state)."""
    global _MASK_PROFILER
    _MASK_PROFILER = profiler


def _mask_profiler():
    global _MASK_PROFILER
    if _MASK_PROFILER is None:
        from skyline_tpu.telemetry.profiler import KernelProfiler

        _MASK_PROFILER = KernelProfiler()
    return _MASK_PROFILER


def _is_concrete(x) -> bool:
    """True when ``x`` is a real array (host or committed device), not a
    tracer — the jit boundary the host path must never cross."""
    import jax

    return jax.core.is_concrete(x)


def skyline_mask_auto(x, valid=None):
    """Survivor mask with the fastest kernel for the active backend.

    The variant decision lives in the declarative cascade table
    (``ops/cascade.py resolve_mask`` — env modes force/exclude first,
    ``auto`` races measured EMAs, traced calls swap only on evidence,
    tuner pins short-circuit the race); this function only EXECUTES the
    chosen row, with the historical recording discipline (auto races
    over concrete arrays sync + record for honest EMA walls, forced
    device paths and traced calls dispatch bare)."""
    if x.shape[1] <= 2:
        # d <= 2 needs no pairwise work at all: sort + prefix-min sweep
        # (ops/sweep2d.py), O(n log n) on every backend — at the 262k-row
        # union bucket that replaces ~69G pair-ops with one sort
        from skyline_tpu.ops.sweep2d import skyline_mask_sweep

        return skyline_mask_sweep(x, valid)
    from skyline_tpu.ops import cascade

    n, d = x.shape
    concrete = _is_concrete(x) and (valid is None or _is_concrete(valid))
    # mp only keys TPU signatures (the host races always recorded under
    # mp=False, even with SKYLINE_MIXED_PRECISION exported)
    mp = mixed_precision_enabled() if on_tpu() else False
    prof = _mask_profiler()
    variant, rec = cascade.resolve_mask(d, n, concrete, prof, mp=mp)

    if variant in ("mask_pallas", "mask_rank_pallas"):
        from skyline_tpu.ops.pallas_dominance import (
            skyline_mask_pallas,
            skyline_mask_rank_pallas,
        )

        kern = (
            skyline_mask_rank_pallas
            if variant == "mask_rank_pallas"
            else skyline_mask_pallas
        )
        if not rec:
            return kern(x, valid)
        with prof.record(variant, d, n, mp):
            out = kern(x, valid)
            out.block_until_ready()  # honest wall for the EMA compare
        return out
    if variant == "mask_device_cascade":
        from skyline_tpu.ops.device_cascade import device_cascade_mask

        if not rec:
            return device_cascade_mask(x, valid)
        with prof.record("mask_device_cascade", d, n, mp):
            out = device_cascade_mask(x, valid)
            out.block_until_ready()
        return out
    if variant == "sorted_sfs_mask":
        import jax.numpy as jnp
        import numpy as np

        from skyline_tpu.ops.sorted_sfs import sorted_skyline_mask_np

        with prof.record("sorted_sfs_mask", d, n):
            out = jnp.asarray(
                sorted_skyline_mask_np(
                    np.asarray(x),
                    None if valid is None else np.asarray(valid),
                )
            )
        return out
    from skyline_tpu.ops.block_skyline import skyline_mask_scan

    if not rec:
        return skyline_mask_scan(x, valid)
    with prof.record("mask_scan", d, n):
        out = skyline_mask_scan(x, valid)
        out.block_until_ready()  # honest wall for the EMA compare
    return out


def skyline_keep_np(x):
    """Survivor mask of a host (n, d) array via the backend's best kernel:
    pad to a tile-friendly power-of-two capacity, mask on device, slice
    back. The one shared implementation of the pad/mask/slice idiom (engine
    global merge, sliding-window buckets)."""
    import jax.numpy as jnp
    import numpy as np

    from skyline_tpu.utils.buckets import next_pow2

    n, d = x.shape
    if n == 0:
        return np.zeros((0,), dtype=bool)
    cap = next_pow2(n, min_cap=1024)
    pad = np.full((cap, d), np.inf, dtype=np.float32)
    pad[:n] = x
    valid = np.arange(cap) < n
    return np.asarray(skyline_mask_auto(jnp.asarray(pad), jnp.asarray(valid)))[:n]


def skyline_of_np(x, dims: int):
    """Exact skyline points of a host (n, d) array (see skyline_keep_np)."""
    import numpy as np

    if x.shape[0] == 0:
        return np.empty((0, dims), dtype=np.float32)
    return x[skyline_keep_np(x)]
