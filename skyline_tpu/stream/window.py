"""Incremental windowed-merge kernels for streaming skyline maintenance.

The merge step is the flush-time replacement for the reference's BNL
buffer-vs-skyline loop (``SkylineLocalProcessor.processBuffer``,
FlinkSkyline.java:417-444): one jitted masked dominance pass folds a new
micro-batch into a running skyline buffer. The stateful owner of these
kernels is ``skyline_tpu.stream.batched.PartitionSet``, which stacks all
logical partitions and calls the *batched* variants — one device launch per
flush for the whole set.

TPU residency: running skylines live on device as padded
power-of-two-capacity buffers; each flush ships only the new micro-batch up
and survivor counts back, so steady-state streaming never transfers the
skyline itself. Capacities are bucketed so XLA compiles a bounded number of
executables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from skyline_tpu.ops.dominance import (
    compact,
    dominated_by,
    skyline_mask,
    strictly_dominated_bf16,
)
from skyline_tpu.ops.sfs import (  # noqa: F401  (re-exported: the SFS
    _MP_PREFIX,  # kernels moved to the ops layer)
    pallas_interpret as _pallas_interpret,
    sfs_cleanup,
    sfs_round,
    sfs_round_single,
)
from skyline_tpu.utils.buckets import next_pow2

# Reference flushes its input buffer at 5000 tuples (BUFFER_SIZE,
# FlinkSkyline.java:232); we default to the nearest power of two.
DEFAULT_BUFFER_SIZE = 4096

# Dispatch-signature variant names for the kernel profiler
# (telemetry/profiler.py): every ``flush/merge_kernel`` tracer site in
# stream/batched.py attributes its wall time to one of these. The mapping
# is documentation + a closed vocabulary for /profile consumers; the
# profiler itself accepts any string.
KERNEL_VARIANTS = {
    "merge_step": "batched merge of one micro-batch into all partitions",
    "meshed_merge_step": "shard_map merge across a device mesh",
    "sfs_vmapped": "vmapped sort-filter-skyline flush round",
    "meshed_sfs_round": "shard_map SFS flush round",
    "sfs_sequential": "single-partition SFS flush round",
    "sfs_rank": "device-resident SFS round (per-rank / vmapped dw paths)",
    "sfs_cleanup": "lazy-flush cleanup pass",
    "sorted_sfs": "host sorted-order SFS cascade, one partition's flush "
                  "(ops/sorted_sfs.py: dedup + f64 sum-sort + blocked scan)",
    "device_cascade": "device sorted dominance cascade, one partition's "
                      "flush (ops/device_cascade.py: on-device dedup + f32 "
                      "sum-key sort + blocked prefix/band scan, jit-safe)",
    # dispatch-chooser signatures (recorded into PartitionSet._flush_prof
    # and dispatch._MASK_PROFILER, not the engine profiler — whole-path
    # aggregates that would double-count the per-round rows above)
    "flush_sorted_sfs": "whole lazy flush via the host sorted cascade",
    "flush_sfs_sequential": "whole lazy flush via per-partition SFS rounds",
    "flush_sfs_vmapped": "whole lazy flush via vmapped SFS rounds",
    "flush_device_cascade": "whole lazy flush via the device sorted "
                            "dominance cascade",
    "sorted_sfs_mask": "skyline_mask_auto host path (concrete non-TPU d>2)",
    "mask_scan": "skyline_mask_auto device scan kernel (concrete arrays)",
    "mask_device_cascade": "skyline_mask_auto device sorted dominance "
                           "cascade (jit-safe, all backends)",
    "mask_pallas": "skyline_mask_auto Pallas sum-sorted tiles (TPU)",
    "mask_rank_pallas": "skyline_mask_auto Pallas rank-cascade tiles (TPU)",
}

# Minimum buffer capacity. Power-of-two buckets >= this always divide the
# Pallas tile sizes after the kernels' min(tile, n) clamp
# (ops/pallas_dominance.py), which is what keeps sub-COL_TILE buffers legal.
_MIN_CAP = 1024


def _next_pow2(n: int) -> int:
    return next_pow2(n, min_cap=_MIN_CAP)


# the ladder only engages when its step (p//8) is a whole number of Pallas
# victim tiles: derived from the kernel's tile constants so a future tile
# sweep can't silently strand victims past a truncated grid division
# (dominated_by_pallas computes grid = n // tile with no remainder handling)
@functools.cache
def _ladder_min() -> int:
    import math

    from skyline_tpu.ops.pallas_dominance import COL_TILE, ROW_TILE

    return 8 * math.lcm(ROW_TILE, COL_TILE)


def _active_bucket(n: int) -> int:
    """Quarter-pow2 ladder for ACTIVE (compute-prefix) buckets:
    {1, 1.25, 1.5, 1.75} x 2^k. ``active`` sets the dominator-prefix width
    of every SFS/merge dominance pass, so the power-of-two bucket's average
    ~1.33x overshoot of the true survivor count is directly wasted pairwise
    work; the finer ladder cuts the overshoot to ~1.11x for at most 3 extra
    executables per octave (cached across windows, persistent via the
    compile cache). Storage capacities stay power-of-two (`_next_pow2`) —
    only compute prefixes use this ladder.

    The ladder only runs when the pow2 bucket ``p`` is >= ``_ladder_min()``
    (8 * lcm(ROW_TILE, COL_TILE) = 16384 at the current tiles, so p//8 is
    a whole number of victim tiles): the Pallas grids divide the victim
    extent by the column tile with no remainder handling
    (ops/pallas_dominance.py), and this guard makes every returned value
    either a power of two (below the guard) or a tile multiple (at or
    above it). Note the guard is on ``p``, not the returned value —
    n=9000 returns 10240, a non-pow2 value below 16384 (still a
    tile-multiple). Returned values are always >= n and <=
    _next_pow2(n), so callers' capacity invariants are unaffected."""
    p = _next_pow2(n)
    if p < _ladder_min():
        return p
    # p is the true next pow2 here (the guard keeps n above the _MIN_CAP
    # floor), so p/2 < n and the 1.0x(p/2) rung can never be selected
    step = p // 8
    for num in (5, 6, 7):
        if step * num >= n:
            return step * num
    return p


def _mp_predrop(sky, sky_valid, batch, batch_valid):
    """bf16-margin pre-drop of batch rows certainly strictly-dominated by a
    skyline prefix row (mixed-precision stage 2, shared by both merge cores).

    Bit-exact vs skipping it: a certified row y has a valid sky dominator x
    with x < y strictly in every dim, so the exact sky-vs-batch pass drops y
    anyway, and any batch row q that y would have pruned from the
    batch-local pass satisfies x < y <= q per-dim — x strictly dominates q
    too (transitivity), so q is dropped by the sky pass either way. Masking
    y to +inf only moves its coordinate sum UP, so sum-sorted invariants of
    callers are preserved. Returns (batch', batch_valid', resolved)."""
    limit = min(sky.shape[0], _MP_PREFIX)
    d = sky.shape[1]
    pre = strictly_dominated_bf16(
        batch, lax.slice(sky, (0, 0), (limit, d)), sky_valid[:limit]
    )
    pre = pre & batch_valid
    resolved = jnp.sum(pre, dtype=jnp.int32)
    batch_valid = batch_valid & ~pre
    batch = jnp.where(batch_valid[:, None], batch, jnp.inf)
    return batch, batch_valid, resolved


def _merge_tiles_run(sky, sky_valid, batch, batch_valid, keep_batch):
    """Tile pairs of the Pallas core's three passes whose compute body runs
    (``ops.pallas_dominance.tiles_run``, each pass with the validity the
    core hands its kernel), int32. Reckoned beside either core, so the
    count reads the same wherever the merge ran."""
    from skyline_tpu.ops.pallas_dominance import tiles_run

    sky_t, batch_t = sky.T, batch.T
    return (
        tiles_run(batch_t, batch_valid, batch_t, batch_valid)
        + tiles_run(sky_t, sky_valid, batch_t, batch_valid)
        + tiles_run(batch_t, keep_batch, sky_t, sky_valid)
    )


def _merge_step_core(sky, sky_valid, batch, batch_valid, out_cap: int, mp: bool = False):
    """One windowed-BNL step: merge a new batch into a running skyline and
    compact survivors into a fresh ``out_cap`` buffer.

    sky is assumed to already be a skyline (mutually non-dominated):

    - a batch point survives iff it is not dominated within its batch nor by
      the running skyline (dominated dominators prune correctly by
      transitivity, so the full sky buffer is a valid dominator set);
    - a sky point survives iff no *surviving* batch point dominates it
      (a dropped batch dominator's own dominator chain ends at a kept point
      that also dominates the victim, so kept batch points suffice).

    ``mp`` (static) enables the bf16 margin pre-drop (``_mp_predrop``) —
    bit-exact either way. Returns (values (out_cap, d), valid (out_cap,),
    count, resolved, tiles); ``resolved`` is the int32 count of
    bf16-certified drops (0 when ``mp=False``), ``tiles`` the Pallas core's
    tile pairs that would run (``_merge_tiles_run``). ``out_cap`` must be
    >= current survivor count + batch rows, so overflow cannot occur.
    """
    resolved = jnp.zeros((), dtype=jnp.int32)
    if mp:
        batch, batch_valid, resolved = _mp_predrop(
            sky, sky_valid, batch, batch_valid
        )
    batch_local = skyline_mask(batch, batch_valid)
    keep_batch = batch_local & ~dominated_by(batch, sky, x_valid=sky_valid)
    keep_sky = sky_valid & ~dominated_by(sky, batch, x_valid=keep_batch)
    x = jnp.concatenate([sky, batch], axis=0)
    keep = jnp.concatenate([keep_sky, keep_batch], axis=0)
    vals, valid, cnt = compact(x, keep, out_cap)
    tiles = _merge_tiles_run(sky, sky_valid, batch, batch_valid, keep_batch)
    return vals, valid, cnt, resolved, tiles


def _merge_step_pallas_core(sky, sky_valid, batch, batch_valid, out_cap: int, mp: bool = False):
    """TPU fast path of ``_merge_step_core``: the three dominance passes run
    in the Pallas VMEM-tiled kernel (same mask logic, same transitivity
    arguments; ``mp`` additionally threads the in-kernel bf16 first pass).
    Each pass hands its kernel the victims' validity, so tiles of padding
    skip; the verdicts of invalid victims are masked by ``batch_local`` /
    ``sky_valid``. Requires sky/batch extents to be tile multiples — the
    _MIN_CAP floor plus pow2 capacities / pow2-or-tile-multiple active
    prefixes (``_active_bucket``) guarantee that."""
    from skyline_tpu.ops.pallas_dominance import dominated_by_pallas

    interp = _pallas_interpret()
    resolved = jnp.zeros((), dtype=jnp.int32)
    if mp:
        batch, batch_valid, resolved = _mp_predrop(
            sky, sky_valid, batch, batch_valid
        )
    sky_t = sky.T
    batch_t = batch.T
    batch_local = batch_valid & ~dominated_by_pallas(
        batch_t, batch_valid, batch_t, interpret=interp, mp=mp,
        y_valid=batch_valid,
    )
    keep_batch = batch_local & ~dominated_by_pallas(
        sky_t, sky_valid, batch_t, interpret=interp, mp=mp,
        y_valid=batch_valid,
    )
    keep_sky = sky_valid & ~dominated_by_pallas(
        batch_t, keep_batch, sky_t, interpret=interp, mp=mp,
        y_valid=sky_valid,
    )
    x = jnp.concatenate([sky, batch], axis=0)
    keep = jnp.concatenate([keep_sky, keep_batch], axis=0)
    vals, valid, cnt = compact(x, keep, out_cap)
    tiles = _merge_tiles_run(sky, sky_valid, batch, batch_valid, keep_batch)
    return vals, valid, cnt, resolved, tiles


# Batched merge: P partitions' flushes in ONE device launch
# (sky (P, cap, d), batch (P, B, d) -> (P, out_cap, d)). Collapsing P
# per-partition merges into one vmapped executable is the difference between
# ~P*3 launches per micro-batch and ~1.
_merge_step_batched = jax.jit(
    jax.vmap(_merge_step_core, in_axes=(0, 0, 0, 0, None)),
    static_argnames=("out_cap",),
)
_merge_step_pallas_batched = jax.jit(
    jax.vmap(_merge_step_pallas_core, in_axes=(0, 0, 0, 0, None)),
    static_argnames=("out_cap",),
)


@functools.partial(
    jax.jit,
    static_argnames=("active", "out_active", "mp"),
    donate_argnums=(0, 1),
)
def merge_step_active(
    sky, sky_valid, batch, bvalid, active: int, out_active: int, mp: bool = False
):
    """Incremental flush step over the ACTIVE capacity prefix only.

    A pre-sized or previously-grown buffer makes the plain batched merge pay
    full-capacity dominance passes and a full-buffer compact argsort on
    every flush, even when the live skylines are a fraction of capacity.
    This variant slices the dominator/compact work to ``active`` (the
    capacity bucket of the current max count; rows past it are guaranteed
    invalid) and compacts into ``out_active`` (the bucket covering counts +
    this batch), then pads back out to the storage capacity — one fused
    launch, same storage shape out. Requires out_active >= active and
    out_active >= per-partition count + batch rows (the caller's capacity
    bookkeeping guarantees both). Single-device only (the meshed path keeps
    ``meshed_merge_step``).

    The stacked sky/valid buffers are donated (the ops/sfs.py idiom): the
    steady-state same-shape flush updates in place instead of allocating a
    fresh (P, cap, d) buffer per round, which is what lets the staged
    pipeline keep two rounds in flight without doubling residency. Growth
    rounds (out_cap > cap) can't reuse the buffer and fall back to a fresh
    allocation with jax's "donated buffers not usable" warning (filtered in
    tests/conftest.py, log-bounded in production by the doubling schedule).

    ``mp`` (static, a jit cache key) threads the bf16 margin pass; the
    fourth return is the per-partition bf16-resolved count (P,) int32, the
    fifth the tile pairs of the three passes whose body runs, summed over
    partitions (int32 scalar, left on the device).
    """
    from skyline_tpu.ops.dispatch import on_tpu

    P, cap, d = sky.shape
    core = _merge_step_pallas_core if on_tpu() else _merge_step_core
    sky_a = lax.slice(sky, (0, 0, 0), (P, active, d))
    val_a = lax.slice(sky_valid, (0, 0), (P, active))
    vals, valid, cnt, res, tiles = jax.vmap(
        lambda s, sv, b, bv: core(s, sv, b, bv, out_active, mp)
    )(sky_a, val_a, batch, bvalid)
    out_cap = max(cap, out_active)
    if out_active < out_cap:
        vals = jnp.concatenate(
            [
                vals,
                jnp.full((P, out_cap - out_active, d), jnp.inf, vals.dtype),
            ],
            axis=1,
        )
        valid = jnp.concatenate(
            [valid, jnp.zeros((P, out_cap - out_active), dtype=bool)], axis=1
        )
    return vals, valid, cnt.astype(jnp.int32), res, jnp.sum(tiles, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("active", "union_cap"))
def global_merge_stats_device(sky, counts, active: int, union_cap: int):
    """Device-side two-phase finish over the stacked state: gather every
    partition's live prefix into ONE contiguous union buffer, then a single
    triangular pass — instead of pulling buffers to host, merging there,
    and re-uploading (GlobalSkylineAggregator's role,
    FlinkSkyline.java:547-608, minus the host round-trip).

    ``active`` (static) bounds each partition's copied prefix (the bucket
    of the max count); ``union_cap`` (static) is the bucket of the summed
    counts — the dominance pass runs over the union's size, NOT P x active.
    Under routing skew (mr-angle at 8D sends ~96% of rows to 2 of 8
    partitions) the flattened-padded formulation pays (P*active)^2 while
    the union is barely bigger than one partition — a 16x difference at the
    north-star window.

    The sequential gather writes each partition's full ``active`` slice at
    the running count offset: rows >= count are +inf padding under BOTH
    flush policies (compact/SFS-append invariants), each write's garbage
    tail is overwritten by the next partition's rows, and the buffer keeps
    an ``active``-row scratch tail so no write ever clamps.

    Returns (union (union_cap, d) — still on device for the points path —
    keep (union_cap,) bool, and a packed stats vector [counts (P,),
    survivors_per_partition (P,), global_count] so the caller syncs ONE
    small transfer)."""
    from skyline_tpu.ops.dispatch import skyline_mask_auto

    P, cap, d = sky.shape
    scratch = union_cap + active
    u = jnp.full((scratch, d), jnp.inf, dtype=sky.dtype)
    uo = jnp.zeros((scratch,), dtype=jnp.int32)
    off = jnp.zeros((), jnp.int32)
    for p in range(P):  # static unroll; P is small
        sl = lax.slice(sky, (p, 0, 0), (p + 1, active, d)).reshape(active, d)
        u = lax.dynamic_update_slice(u, sl, (off, jnp.zeros((), jnp.int32)))
        uo = lax.dynamic_update_slice(
            uo, jnp.full((active,), p, jnp.int32), (off,)
        )
        off = off + counts[p].astype(jnp.int32)
    u = lax.slice(u, (0, 0), (union_cap, d))
    uo = lax.slice(uo, (0,), (union_cap,))
    uv = jnp.arange(union_cap) < off
    keep = skyline_mask_auto(u, uv)
    surv = jax.ops.segment_sum(
        keep.astype(jnp.int32), uo, num_segments=P
    )
    g = keep.sum(dtype=jnp.int32)
    stats = jnp.concatenate([counts.astype(jnp.int32), surv, g[None]])
    return u, keep, stats


@functools.partial(jax.jit, static_argnames=("out_cap",))
def global_points_device(union, keep, out_cap: int):
    """Compact the global survivors (union + keep from
    ``global_merge_stats_device``) to the front of an (out_cap, d) buffer
    for a single bounded transfer — only paid when a query asks for
    skyline_points."""
    return compact(union, keep, out_cap)[0]


@functools.partial(
    jax.jit,
    static_argnames=("active", "clean_active", "union_cap", "dirty"),
)
def global_merge_delta_device(
    sky,
    counts,
    gpts,
    clean_bounds,
    active: int,
    clean_active: int,
    union_cap: int,
    dirty: tuple,
):
    """Dirty-subset variant of ``global_merge_stats_device``: the union is
    ``cached_global ∪ dirty partitions' current skylines`` instead of every
    partition's full prefix, shrinking the triangular pass from
    O((Σ all counts)²) to O((g + Σ dirty)²).

    Correctness (the merge law + transitivity): a CLEAN partition's
    contribution is its cached global survivors — any of its points culled
    at cache time had a dominator in some partition's then-skyline, and
    partition skylines only lose points to strict dominance by current
    members, so a current dominator always exists transitively; a DIRTY
    partition contributes its full current skyline (its cached survivors
    may be stale, so they are excluded — also what prevents a stale
    duplicate from double-counting against the current copy). Survivor
    order is byte-identical to the full merge: partitions are written in
    ascending id, clean segments keep the cached (storage-order) layout,
    and ``compact``'s stable sort preserves write order.

    ``dirty``: static per-partition bool tuple (executable count is bounded
    by the recurring dirty patterns; the caller's dirty-fraction cutoff
    keeps the tail from compiling). ``clean_bounds``: (P+1,) int32 row
    offsets of each partition's segment inside ``gpts`` (cumsum of the
    cached per-partition survivor counts — dirty partitions' segments are
    simply skipped). ``active`` bounds the dirty slices (bucket of the max
    dirty count); ``clean_active`` bounds the clean slices (bucket of the
    max clean segment width) — both slices write their full static width at
    the running offset and advance by the true width, each garbage tail
    overwritten by the next write (the gather trick
    ``global_merge_stats_device`` documents). ``gpts`` capacity must be >=
    g + clean_active so the clean ``dynamic_slice`` never clamps backward
    (the caller pads the cached points buffer to 2*next_pow2(g)).

    Returns (union, keep, stats) with the same shapes/semantics as the full
    merge so the caller's sync/points paths are shared."""
    from skyline_tpu.ops.dispatch import skyline_mask_auto

    P, cap, d = sky.shape
    scratch = union_cap + max(active, clean_active)
    u = jnp.full((scratch, d), jnp.inf, dtype=sky.dtype)
    uo = jnp.zeros((scratch,), dtype=jnp.int32)
    off = jnp.zeros((), jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    for p in range(P):  # static unroll; P is small
        if dirty[p]:
            sl = lax.slice(sky, (p, 0, 0), (p + 1, active, d)).reshape(
                active, d
            )
            u = lax.dynamic_update_slice(u, sl, (off, zero))
            uo = lax.dynamic_update_slice(
                uo, jnp.full((active,), p, jnp.int32), (off,)
            )
            off = off + counts[p].astype(jnp.int32)
        else:
            lo = clean_bounds[p]
            w = clean_bounds[p + 1] - lo
            sl = lax.dynamic_slice(gpts, (lo, zero), (clean_active, d))
            # unlike ``sky`` prefixes, rows past this segment are NOT +inf
            # padding — they are the NEXT partitions' cached survivors — so
            # the static-width tail must be masked out before the write (a
            # shorter next write would otherwise leave live duplicates)
            sl = jnp.where(
                jnp.arange(clean_active)[:, None] < w, sl, jnp.inf
            )
            u = lax.dynamic_update_slice(u, sl, (off, zero))
            uo = lax.dynamic_update_slice(
                uo, jnp.full((clean_active,), p, jnp.int32), (off,)
            )
            off = off + w
    u = lax.slice(u, (0, 0), (union_cap, d))
    uo = lax.slice(uo, (0,), (union_cap,))
    uv = jnp.arange(union_cap) < off
    keep = skyline_mask_auto(u, uv)
    surv = jax.ops.segment_sum(keep.astype(jnp.int32), uo, num_segments=P)
    g = keep.sum(dtype=jnp.int32)
    stats = jnp.concatenate([counts.astype(jnp.int32), surv, g[None]])
    return u, keep, stats


# --- Pruned tournament-tree global merge -----------------------------------
#
# The flat ``global_merge_stats_device`` pays one O(U²) dominance pass over
# the full union. The tree path instead (1) drops whole partitions via a
# host-side witness prefilter over tiny device summaries, then (2) merges the
# survivors pairwise up a binary tree — each level's pair merge prunes both
# sides, so the next level's quadratic kernel runs on a halved, already-
# thinned candidate set. Every primitive below preserves the flat path's
# survivor ORDER (ascending partition id, storage row within a partition —
# the order the flat gather writes and ``compact``'s stable sort keeps), so
# the tree's output bytes are identical to the flat recompute's.
# Orchestration lives in ``stream.batched.PartitionSet``.


@functools.partial(jax.jit, static_argnames=("active",))
def partition_summaries_device(sky, counts, active: int):
    """Per-partition prune summaries, (P, 2d + 2) packed as
    ``[min_corner (d) | witness (d) | min_sum | max_sum]``.

    ``witness`` is an ACTUAL live point of the partition — the row with the
    smallest coordinate sum (the best single-dominator candidate under
    minimization). The host prefilter prunes partition B when some other
    partition's witness dominates B's min-corner: the witness is then <=
    every B point in all dims and strictly below in the witnessing dim
    (witness_k < min_corner_k <= b_k), i.e. it strictly dominates ALL of B.
    Empty partitions report +inf everywhere and can neither prune nor
    survive. Launched asynchronously at flush time (a (P, 2d+2) transfer);
    the merge path re-launches only if the epoch moved since."""
    P, cap, d = sky.shape
    s = lax.slice(sky, (0, 0, 0), (P, active, d))
    valid = jnp.arange(active)[None, :] < counts[:, None]
    sm = jnp.where(valid[:, :, None], s, jnp.inf)
    min_corner = jnp.min(sm, axis=1)
    sums = jnp.where(valid, jnp.sum(s, axis=2), jnp.inf)
    wi = jnp.argmin(sums, axis=1)
    witness = jnp.take_along_axis(
        s, jnp.broadcast_to(wi[:, None, None], (P, 1, d)), axis=1
    ).reshape(P, d)
    witness = jnp.where((counts > 0)[:, None], witness, jnp.inf)
    min_sum = jnp.min(sums, axis=1)
    max_sum = jnp.max(jnp.where(valid, jnp.sum(s, axis=2), -jnp.inf), axis=1)
    return jnp.concatenate(
        [min_corner, witness, min_sum[:, None], max_sum[:, None]], axis=1
    )


def prune_witness_mask(summaries: np.ndarray, alive: np.ndarray, d: int):
    """Host-side O(P²·d) witness prefilter over the
    ``partition_summaries_device`` output: partition B is pruned when some
    alive partition A's witness (a REAL live point, not a bound) strictly
    dominates B's min-corner — the witness is then <= every B point in all
    dims and strictly below in the witnessing dim
    (``witness_k < min_corner_B_k <= b_k``), i.e. it strictly dominates ALL
    of B. Strict dominance is a strict partial order, so simultaneous
    pruning is acyclic: every pruned partition's dominator chain ends at a
    surviving partition's witness, and at least one alive partition always
    survives — dropping pruned partitions leaves the skyline byte-identical.

    Returns ``(pruned (P,) bool, witness_of (P,) int64)`` where
    ``witness_of[b]`` is the lowest-pid alive partition whose witness first
    certified b's prune (-1 when unpruned) — the per-partition witness
    REASON the EXPLAIN plane records. The mask is exactly the one
    ``PartitionSet._prune_mask`` historically computed inline; the reasons
    are free (one extra vector write per witnessing partition).
    """
    P = summaries.shape[0]
    mins = summaries[:, :d]
    wit = summaries[:, d : 2 * d]
    pruned = np.zeros(P, dtype=bool)
    witness_of = np.full(P, -1, dtype=np.int64)
    for a in np.flatnonzero(alive):
        w = wit[a]
        if not np.all(np.isfinite(w)):
            continue  # empty partition: +inf witness prunes nothing
        dom = np.all(w[None, :] <= mins, axis=1) & np.any(
            w[None, :] < mins, axis=1
        )
        dom[a] = False  # a witness never beats its own min-corner
        dom &= alive
        witness_of[dom & ~pruned] = a
        pruned |= dom
    return pruned, witness_of


# Quantized-grid flush prefilter (ISSUE 5 stage 1). GRID_BINS boundary
# steps per dimension; GRID_REPS representative skyline rows per partition.
# The summary is tiny — (P, BINS+1, d) f32 boundaries + (P, REPS, d) int32
# cell codes — so the flush-tail transfer is a few KB against the multi-MB
# skylines it summarizes.
GRID_BINS = 32
GRID_REPS = 64


@functools.partial(jax.jit, static_argnames=("active",))
def grid_summary_device(sky, counts, active: int):
    """Per-partition quantized grid summary for the flush prefilter:
    ``(bounds (P, GRID_BINS+1, d) f32, ux (P, R, d) int32)`` with
    R = min(active, GRID_REPS).

    ``bounds[p, :, k]`` is an explicit ascending boundary ladder
    ``lo + i*step`` over dimension ``k``'s finite live range — shipped to
    the host verbatim, so host and device quantize against the SAME f32
    values (no cross-platform arithmetic-identity assumptions). ``ux`` are
    the representatives' cell codes: ``ux = #(bounds < x)``, the smallest
    index with ``x <= bounds[ux]``. The host codes an incoming row y as
    ``vy = #(bounds <= y) - 1`` (largest index with ``bounds[vy] <= y``)
    and drops y iff ``ux < vy`` in EVERY dim: then
    ``x <= bounds[ux] < bounds[vy] <= y`` strictly per-dim (the host
    validates the ladder is strictly increasing and disables dims where
    f32 rounding collapsed it), i.e. the representative — an actual live
    skyline row — strictly dominates y, so the exact merge would drop y
    too (stage-1 soundness, RUNBOOK §2g).

    Representatives are the first R rows of the live prefix (sum-sorted
    under the lazy/SFS policies, insertion-ordered under incremental —
    soundness never depends on which rows are picked). Non-finite or
    out-of-count representative rows are masked to code GRID_BINS+1, which
    can never certify (vy <= GRID_BINS). Empty partitions produce NaN
    ladders that fail host validation — zero drops, conservative."""
    P, cap, d = sky.shape
    s = lax.slice(sky, (0, 0, 0), (P, active, d))
    valid = jnp.arange(active)[None, :] < counts[:, None]
    finite = jnp.isfinite(s) & valid[:, :, None]
    lo = jnp.min(jnp.where(finite, s, jnp.inf), axis=1)  # (P, d)
    hi = jnp.max(jnp.where(finite, s, -jnp.inf), axis=1)
    # step > 0 even for degenerate (single-value) dims, so the ladder is
    # strictly increasing whenever lo is finite and the step survives f32
    # addition (the host re-checks that)
    step = jnp.maximum(
        (hi - lo) / GRID_BINS, jnp.maximum(jnp.abs(lo), 1.0) * 1e-6
    )
    ladder = jnp.arange(GRID_BINS + 1, dtype=s.dtype)
    bounds = lo[:, None, :] + ladder[None, :, None] * step[:, None, :]
    r = min(active, GRID_REPS)
    reps = lax.slice(s, (0, 0, 0), (P, r, d))
    rep_ok = (jnp.arange(r)[None, :] < counts[:, None]) & jnp.all(
        jnp.isfinite(reps), axis=2
    )
    ux = jnp.sum(
        bounds[:, None, :, :] < reps[:, :, None, :], axis=2
    ).astype(jnp.int32)
    ux = jnp.where(rep_ok[:, :, None], ux, GRID_BINS + 1)
    return bounds, ux


@functools.partial(jax.jit, static_argnames=("p", "width"))
def extract_sky_leaf(sky, counts, p: int, width: int):
    """One partition's live prefix as a tree leaf: (vals (width, d),
    pids (width,), count). ``width`` must cover the partition's count (the
    caller buckets its count upper bound); rows >= count are +inf padding by
    the storage invariant. Static (p, width) keeps the executable set
    bounded by P x capacity buckets."""
    P, cap, d = sky.shape
    vals = lax.slice(sky, (p, 0, 0), (p + 1, width, d)).reshape(width, d)
    pids = jnp.full((width,), p, jnp.int32)
    return vals, pids, counts[p].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("p", "width"))
def extract_cached_leaf(gpts, lo, w, p: int, width: int):
    """A CLEAN partition's cached global-survivor segment as a tree leaf for
    the delta merge: rows [lo, lo+w) of the cached points buffer. The static
    ``width`` slice is masked past the true width ``w`` — rows beyond the
    segment are the NEXT partitions' cached survivors, not padding (the same
    hazard ``global_merge_delta_device`` documents). ``gpts`` capacity must
    be >= lo + width so the dynamic_slice never clamps backward (the cache
    pads to 2*next_pow2(g); width <= next_pow2(g) and lo <= g)."""
    d = gpts.shape[1]
    zero = jnp.zeros((), jnp.int32)
    sl = lax.dynamic_slice(gpts, (lo, zero), (width, d))
    sl = jnp.where(jnp.arange(width)[:, None] < w, sl, jnp.inf)
    pids = jnp.full((width,), p, jnp.int32)
    return sl, pids, w.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def tree_pair_merge(a, apids, acnt, b, bpids, bcnt, out_cap: int):
    """Merge two tree nodes — each already a skyline (mutually
    non-dominated) — and compact survivors in [a-order, b-order].

    Exactness without a self-prune pass: if a b-point y dominated an
    a-point x while y itself were dominated by some a-point w, transitivity
    would give w dominates x — impossible inside a skyline. So any b-point
    that dominates an a-point necessarily survives pass one, and checking a
    against only SURVIVING b-points (pass two) is exact; symmetrically the
    full valid a set is a correct dominator set for b. Two rectangular
    passes instead of ``_merge_step_core``'s three.

    Order: stable compaction of [a | b]. With leaves fed in ascending
    partition id, every level preserves (pid, storage-row) order, so the
    root's bytes equal the flat merge's compacted output. ``out_cap`` must
    be >= acnt + bcnt (callers bucket the summed upper bounds). Partition
    ids ride along for the root's per-partition survivor stats."""
    from skyline_tpu.ops.block_skyline import dominated_by_blocked
    from skyline_tpu.ops.dispatch import on_tpu
    from skyline_tpu.ops.dominance import compact_tagged

    wa, d = a.shape
    wb = b.shape[0]
    av = jnp.arange(wa) < acnt
    bv = jnp.arange(wb) < bcnt
    if on_tpu():
        from skyline_tpu.ops.pallas_dominance import dominated_by_pallas

        interp = _pallas_interpret()
        at, bt = a.T, b.T
        # victim validity lets the tile skip pass padding (invalid
        # victims' verdicts are masked by av/bv below)
        keep_b = bv & ~dominated_by_pallas(
            at, av, bt, interpret=interp, y_valid=bv
        )
        keep_a = av & ~dominated_by_pallas(
            bt, keep_b, at, interpret=interp, y_valid=av
        )
    else:
        # chunk the dominator set so the dense tile stays ~256 MB; victim
        # validity tightens the sum-bound chunk skip (invalid victims may
        # then read undominated — masked by av/bv below)
        blk = max(256, min(8192, (1 << 28) // max(wb, 1)))
        keep_b = bv & ~dominated_by_blocked(
            b, a, x_valid=av, block=blk, y_valid=bv
        )
        blk = max(256, min(8192, (1 << 28) // max(wa, 1)))
        keep_a = av & ~dominated_by_blocked(
            a, b, x_valid=keep_b, block=blk, y_valid=av
        )
    x = jnp.concatenate([a, b], axis=0)
    t = jnp.concatenate([apids, bpids], axis=0)
    keep = jnp.concatenate([keep_a, keep_b], axis=0)
    vals, pids, _, cnt = compact_tagged(x, t, keep, out_cap)
    return vals, pids, cnt.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_partitions",))
def tree_stats_device(counts, root_pids, root_cnt, num_partitions: int):
    """Pack the tree root into the flat merge's stats layout
    ``[counts (P,) | survivors_per_partition (P,) | global_count]`` so the
    caller's sync / cache paths are shared. Per-partition survivors fall out
    of a segment-sum over the partition ids the pair merges threaded
    through; pruned and empty partitions report 0."""
    w = root_pids.shape[0]
    valid = jnp.arange(w) < root_cnt
    surv = jax.ops.segment_sum(
        valid.astype(jnp.int32),
        jnp.where(valid, root_pids, 0),
        num_segments=num_partitions,
    )
    return jnp.concatenate(
        [counts.astype(jnp.int32), surv, root_cnt.astype(jnp.int32)[None]]
    )


@functools.partial(jax.jit, static_argnames=("out_cap",))
def tree_points_device(vals, out_cap: int):
    """Resize the tree root's value buffer to the points transfer / cache
    capacity. Rows past the survivor count are already +inf (compact
    invariant, or the sky storage invariant for a single-leaf root), so a
    plain slice / pad reproduces ``global_points_device``'s bytes."""
    w, d = vals.shape
    if out_cap <= w:
        return lax.slice(vals, (0, 0), (out_cap, d))
    return jnp.concatenate(
        [vals, jnp.full((out_cap - w, d), jnp.inf, vals.dtype)], axis=0
    )


def _shard_map_vmapped(mesh, axis, fn, n_in: int, n_out: int, donate=()):
    """``jit(shard_map(vmap(fn)))`` over the partition axis — the one shared
    wrapper for every meshed per-partition kernel. All inputs and outputs
    are partition-sharded; the per-partition kernels have no cross-partition
    data flow, so no collectives appear and each device runs its resident
    partitions only. Needed explicitly (vs GSPMD) because ``pallas_call``
    has no auto-partitioning rule."""
    from jax.sharding import PartitionSpec

    spec = PartitionSpec(axis)
    sharded = jax.shard_map(
        jax.vmap(fn),
        mesh=mesh,
        in_specs=(spec,) * n_in,
        out_specs=(spec,) * n_out,
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=donate)


@functools.lru_cache(maxsize=None)
def meshed_merge_step(mesh, axis: str, use_pallas: bool, out_cap: int, mp: bool = False):
    """Batched merge wrapped in ``shard_map`` over the partition axis
    (see ``_shard_map_vmapped``). Cached per (mesh, axis, kernel, capacity
    bucket, mixed-precision flag) so steady-state flushes reuse one
    executable. Returns 4 outputs — the per-partition bf16-resolved counts
    ride along (all-zero when ``mp=False``)."""
    core = _merge_step_pallas_core if use_pallas else _merge_step_core
    return _shard_map_vmapped(
        mesh, axis, lambda s, sv, b, bv: core(s, sv, b, bv, out_cap, mp)[:4],
        4, 4,
    )


@functools.lru_cache(maxsize=None)
def meshed_sfs_round(mesh, axis: str, use_pallas: bool, active: int, mp: bool = False):
    """``sfs_round`` wrapped in ``shard_map`` over the partition axis (see
    ``_shard_map_vmapped``) — the lazy policy's meshed flush. Cached per
    (mesh, axis, kernel, active bucket, mixed-precision flag); donates the
    sky buffer like the single-device jit. Returns 3 outputs — per-partition
    bf16-resolved counts third (all-zero when ``mp=False``); the core's tile
    count stays behind."""
    from skyline_tpu.ops.sfs import pallas_interpret, sfs_round_core

    interp = pallas_interpret()
    return _shard_map_vmapped(
        mesh,
        axis,
        lambda s, c, b, bv: sfs_round_core(
            s, c, b, bv, active, use_pallas, interp, mp
        )[:3],
        4,
        3,
        donate=(0,),
    )


@functools.lru_cache(maxsize=None)
def meshed_sfs_cleanup(mesh, axis: str, use_pallas: bool, old_active: int, active: int):
    """``sfs_cleanup`` wrapped in ``shard_map`` over the partition axis —
    the old-vs-new prune after SFS rounds on non-empty initial state, per
    resident partition (no collectives)."""
    from skyline_tpu.ops.sfs import pallas_interpret, sfs_cleanup_core

    interp = pallas_interpret()
    return _shard_map_vmapped(
        mesh,
        axis,
        lambda s, c, oc: sfs_cleanup_core(
            s, c, oc, old_active, active, use_pallas, interp
        ),
        3,
        2,
        donate=(0,),
    )
