"""Device-resident ingest: routing, window assembly, and SFS block slicing
run on the accelerator instead of host numpy.

The host ingest path (engine ``process_records`` + ``PartitionSet`` pending
lists) computes partition ids, routes rows, sum-sorts and pads blocks in
numpy, then uploads each padded block — ~1.2 s of host work per 1M-row
window in a 2026 profiling breakdown. This module is the
keyBy-inside-the-dataflow equivalent (the reference keeps its shuffle inside
the Flink job graph, FlinkSkyline.java:138): raw chunks upload once as they
arrive (overlapping parse and transport), partition ids / per-chunk barrier
stats are computed on device, the flush-time (pid, coordinate-sum) sort and
segment bounds are one device launch, and the SFS rounds read their blocks
directly out of the sorted device window via ``dynamic_slice`` — no host
assembly and no per-block ``device_put``.

Owner: ``stream.batched.PartitionSet`` (``ingest="device"``). All kernels
here are stateless jits; static shapes come from power-of-two chunk/window
buckets so executables are bounded and cached.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from skyline_tpu.ops.dispatch import on_tpu
from skyline_tpu.ops.sfs import pallas_interpret, sfs_round_core
from skyline_tpu.parallel.partitioners import partition_ids

# Padding tail appended to the sorted window so a B-row dynamic_slice
# starting at any valid row offset never clamps backward (dynamic_slice
# shifts the start when the slice would run past the end — which would
# desynchronize the block from its validity mask). Must be >= the largest
# SFS block size used by the flush loops.
SORT_TAIL = 65536


@functools.partial(
    jax.jit,
    static_argnames=("algo", "num_partitions", "domain_max"),
    donate_argnums=(0, 1),
)
def ingest_chunk(
    window,
    pidbuf,
    chunk,
    ids,
    nvalid,
    offset,
    *,
    algo: str,
    num_partitions: int,
    domain_max: float,
):
    """Append one uploaded chunk to the device window and route it.

    window: (cap, d) +inf-padded accumulation buffer (donated — updated in
    place); pidbuf: (cap,) int32, ``num_partitions`` sentinel for invalid
    rows (donated); chunk: (B, d) +inf-padded rows; ids: (B,) int32 record
    ids (-1 padding); nvalid/offset: dynamic scalars.

    Returns (window', pidbuf', stats (2, P)) where stats rows are the
    per-partition [row counts, max record ids] of THIS chunk — the engine's
    barrier bookkeeping (max-seen-id per partition, FlinkSkyline.java:276-283)
    synced lazily on the host only when a query needs it.
    """
    B = chunk.shape[0]
    valid = jnp.arange(B) < nvalid
    pids = partition_ids(chunk, algo, num_partitions, domain_max)
    pids = jnp.where(valid, pids, num_partitions).astype(jnp.int32)
    window = lax.dynamic_update_slice(
        window, chunk, (offset, jnp.zeros((), jnp.int32))
    )
    pidbuf = lax.dynamic_update_slice(pidbuf, pids, (offset,))
    counts = jax.ops.segment_sum(
        valid.astype(jnp.int32), pids, num_segments=num_partitions + 1
    )[:num_partitions]
    maxids = jax.ops.segment_max(
        jnp.where(valid, ids, -1), pids, num_segments=num_partitions + 1
    )[:num_partitions]
    return window, pidbuf, jnp.stack([counts, maxids])


@functools.partial(
    jax.jit, static_argnames=("n_bucket", "num_partitions", "tail")
)
def sort_window(
    window, pidbuf, nvalid, n_bucket: int, num_partitions: int, tail: int
):
    """Flush-time shuffle: order the accumulated window by (partition id,
    coordinate sum) and return per-partition segment bounds.

    Within each partition the rows come out in ascending coordinate-sum
    order — exactly the SFS append-only invariant (ops/sfs.py), so the
    flush loops can stream contiguous blocks straight from this buffer.
    Two stable argsorts compose the two-key order (int64 keys are
    unavailable without x64). Rows at or past ``nvalid`` are forced to the
    sentinel pid — the accumulation buffer is reused across windows, so
    rows beyond the current fill may hold stale pids from a previous,
    larger window. Invalid rows sort last; ``bounds[P]`` equals ``nvalid``.

    Returns (sorted (n_bucket + tail, d) with a +inf tail pad — see
    SORT_TAIL — and bounds (P + 1,) int32).
    """
    d = window.shape[1]
    w = lax.slice(window, (0, 0), (n_bucket, d))
    p = lax.slice(pidbuf, (0,), (n_bucket,))
    p = jnp.where(jnp.arange(n_bucket) < nvalid, p, num_partitions)
    sums = jnp.where(p < num_partitions, jnp.sum(w, axis=1), jnp.inf)
    o1 = jnp.argsort(sums, stable=True)
    o2 = jnp.argsort(p[o1], stable=True)
    order = o1[o2]
    ws = jnp.concatenate(
        [w[order], jnp.full((tail, d), jnp.inf, dtype=w.dtype)], axis=0
    )
    bounds = jnp.searchsorted(
        p[order], jnp.arange(num_partitions + 1, dtype=p.dtype)
    ).astype(jnp.int32)
    return ws, bounds


def _searchsorted_cols(sorted_cols, q):
    """Per-dim searchsorted: sorted_cols (M, d) ascending per column,
    q (N, d) queries -> dense ranks (N, d) int32 (int32 keeps rank sums
    exact past f32's 2^24 limit — ops/pallas_dominance._dom_tile_rank)."""
    return jax.vmap(
        lambda sc, col: jnp.searchsorted(sc, col, side="left"),
        in_axes=(1, 1),
        out_axes=1,
    )(sorted_cols, q).astype(jnp.int32)


def rank_flush_enabled() -> bool:
    """Rank-cascade SFS flush: enabled when the rank kernels can run (TPU,
    or interpret mode for tests) and ``SKYLINE_RANK_CASCADE`` is not 0.
    Read lazily at trace/flush time."""
    from skyline_tpu.ops import cascade
    from skyline_tpu.ops.dispatch import on_tpu

    return cascade.gate("mask_rank_pallas") and (on_tpu() or pallas_interpret())


@functools.partial(
    jax.jit,
    static_argnames=("n_bucket", "active_old", "univ_bucket"),
)
def rank_window(
    ws,
    sky,
    counts,
    n_bucket: int,
    active_old: int,
    univ_bucket: int,
):
    """Rank preprocessing for the rank-cascade SFS flush: the compared
    universe is the sorted window's rows PLUS every partition's live
    skyline prefix (old survivors act as dominators against new blocks and
    as cleanup victims, so they must share the rank space — dense ranks
    are exact only over universe members, ops/pallas_dominance.py).

    ws: (n_bucket + tail, d) sorted window; sky: (P, cap, d) with
    ``active_old`` bounding live prefixes (0 = fresh set, universe is the
    window alone). Invalid rows are +inf and rank as the max (inert).

    Returns (sorted_dims (univ_bucket, d) — per-dim ascending universe for
    ranking arbitrary universe members later (sky prefixes per round), and
    ws_ranks (same leading extent as ws, d + 1) — the window rows' ranks
    with the rank-sum as the last column, sliceable exactly like ``ws``
    (its +inf tail rows rank as the max: inert).
    """
    P, cap, d = sky.shape
    w = lax.slice(ws, (0, 0), (n_bucket, d))
    if active_old:
        act = lax.slice(sky, (0, 0, 0), (P, active_old, d)).reshape(
            P * active_old, d
        )
        # rows at or past each partition's count are +inf already (compact
        # / SFS-append invariants) except garbage is impossible: both flush
        # paths pad with +inf. Mask defensively against counts anyway.
        ok = (
            jnp.arange(active_old)[None, :] < counts[:, None]
        ).reshape(P * active_old)
        act = jnp.where(ok[:, None], act, jnp.inf)
        univ = jnp.concatenate([w, act], axis=0)
    else:
        univ = w
    pad = univ_bucket - univ.shape[0]
    if pad > 0:
        univ = jnp.concatenate(
            [univ, jnp.full((pad, d), jnp.inf, univ.dtype)], axis=0
        )
    sorted_dims = jnp.sort(univ, axis=0)
    ranks = _searchsorted_cols(sorted_dims, ws)
    rsum = jnp.sum(ranks, axis=1, keepdims=True, dtype=jnp.int32)
    return sorted_dims, jnp.concatenate([ranks, rsum], axis=1)


def _rank_rows(sorted_dims, rows):
    """Rank arbitrary universe-member rows against the per-dim sorted
    universe -> (N, d+1) int32 ranks+ranksum (transposed layout NOT
    applied)."""
    r = _searchsorted_cols(sorted_dims, rows)
    return jnp.concatenate(
        [r, jnp.sum(r, axis=1, keepdims=True, dtype=jnp.int32)], axis=1
    )


def _sfs_round_rank_core(
    sky_p, count, win, wr, sorted_dims, off, width, B: int, active: int,
    interp: bool,
):
    """Rank-cascade SFS round body: dominance passes over dense ranks,
    append in value space. The sky's active prefix is re-ranked in-jit per
    round (d searchsorteds over ``active`` rows — amortized against the
    O(B x active) pairwise pass)."""
    from skyline_tpu.ops.pallas_dominance import (
        dominated_by_any_rank_pallas,
        dominated_by_rank_pallas,
    )

    d = win.shape[1]
    zero = jnp.zeros((), jnp.int32)
    block = lax.dynamic_slice(win, (off, zero), (B, d))
    block_r = lax.dynamic_slice(wr, (off, zero), (B, d + 1))
    bvalid = jnp.arange(B) < width
    block = jnp.where(bvalid[:, None], block, jnp.inf)
    # invalid tail rows: force ranks to the max so they are inert exactly
    # like +inf values (their true ranks belong to the NEXT partition's
    # rows, which are live universe members and would not be inert)
    block_r = jnp.where(
        bvalid[:, None], block_r, jnp.int32(sorted_dims.shape[0] * (d + 1))
    )
    sky_act = lax.slice(sky_p, (0, 0), (active, d))
    sky_ok = jnp.arange(active) < count
    sky_r = _rank_rows(sorted_dims, sky_act)
    block_rt = block_r.T
    keep = bvalid & ~dominated_by_any_rank_pallas(
        block_rt, bvalid, triangular=True, interpret=interp
    )
    keep = keep & ~dominated_by_rank_pallas(
        sky_r.T, sky_ok, block_rt, interpret=interp
    )
    from skyline_tpu.ops.dominance import compact

    vals, _, m = compact(block, keep, B)
    sky_p = lax.dynamic_update_slice(sky_p, vals, (count, zero))
    return sky_p, count + m


@functools.partial(
    jax.jit, static_argnames=("B", "active"), donate_argnums=(0,)
)
def sfs_round_at_rank(
    sky_p, count, win, wr, sorted_dims, off, width, *, B: int, active: int
):
    """Single-partition rank-cascade round (see ``_sfs_round_rank_core``)."""
    return _sfs_round_rank_core(
        sky_p, count, win, wr, sorted_dims, off, width, B, active,
        pallas_interpret(),
    )


@functools.partial(
    jax.jit, static_argnames=("B", "active"), donate_argnums=(0,)
)
def sfs_round_at_rank_vmapped(
    sky, counts, win, wr, sorted_dims, offs, widths, *, B: int, active: int
):
    """Vmapped rank-cascade round over all partitions."""
    interp = pallas_interpret()

    def core(s, c, off, width):
        return _sfs_round_rank_core(
            s, c, win, wr, sorted_dims, off, width, B, active, interp
        )

    return jax.vmap(core)(sky, counts, offs, widths)


@functools.partial(
    jax.jit,
    static_argnames=("old_active", "active"),
    donate_argnums=(0,),
)
def sfs_cleanup_rank(
    sky, counts, old_counts, sorted_dims, old_active: int, active: int
):
    """Rank-cascade twin of ``ops.sfs.sfs_cleanup``: prune old rows
    dominated by newly appended rows, comparing in rank space (both row
    sets are universe members — old prefixes were folded into the rank
    universe by ``rank_window``)."""
    from skyline_tpu.ops.dominance import compact
    from skyline_tpu.ops.pallas_dominance import dominated_by_rank_pallas

    interp = pallas_interpret()
    P, cap, d = sky.shape

    def core(s, c, old_c):
        act = lax.slice(s, (0, 0), (active, d))
        new_ok = (jnp.arange(active) >= old_c) & (jnp.arange(active) < c)
        old = lax.slice(s, (0, 0), (old_active, d))
        act_r = _rank_rows(sorted_dims, act)
        old_r = _rank_rows(sorted_dims, old)
        old_dom = dominated_by_rank_pallas(
            act_r.T, new_ok, old_r.T, interpret=interp
        )
        old_keep = (jnp.arange(old_active) < old_c) & ~old_dom
        keep = jnp.zeros((cap,), dtype=bool)
        keep = keep.at[:active].set(new_ok)
        keep = keep.at[:old_active].set(old_keep | new_ok[:old_active])
        vals, _, cnt = compact(s, keep, cap)
        return vals, cnt.astype(jnp.int32)

    return jax.vmap(core)(sky, counts, old_counts)


@functools.partial(
    jax.jit, static_argnames=("B", "active", "mp"), donate_argnums=(0,)
)
def sfs_round_at(sky_p, count, win, off, width, *, B: int, active: int, mp: bool = False):
    """One partition's SFS round reading its block out of the sorted device
    window: block = win[off : off + B], valid rows = first ``width``.
    The tail rows of a partition's final block belong to the NEXT partition
    in the sorted order — masked to +inf so they are inert as dominators
    and never appended. Drop-in device-window twin of
    ``ops.sfs.sfs_round_single`` — ``mp`` (static) threads the
    mixed-precision pass; the bf16-resolved count rides third and the
    tile pairs run fourth."""
    d = win.shape[1]
    block = lax.dynamic_slice(win, (off, jnp.zeros((), jnp.int32)), (B, d))
    bvalid = jnp.arange(B) < width
    block = jnp.where(bvalid[:, None], block, jnp.inf)
    return sfs_round_core(
        sky_p, count, block, bvalid, active, on_tpu(), pallas_interpret(), mp
    )


@functools.partial(
    jax.jit, static_argnames=("B", "active", "mp"), donate_argnums=(0,)
)
def sfs_round_at_vmapped(
    sky, counts, win, offs, widths, *, B: int, active: int, mp: bool = False
):
    """Vmapped ``sfs_round_at`` over all partitions (sky (P, cap, d),
    offs/widths (P,)) — one launch per round for balanced loads, each lane
    slicing its own block from the shared sorted window."""
    use_pallas = on_tpu()
    interp = pallas_interpret()
    d = win.shape[1]

    def core(s, c, off, width):
        block = lax.dynamic_slice(
            win, (off, jnp.zeros((), jnp.int32)), (B, d)
        )
        bvalid = jnp.arange(B) < width
        block = jnp.where(bvalid[:, None], block, jnp.inf)
        return sfs_round_core(
            s, c, block, bvalid, active, use_pallas, interp, mp
        )

    return jax.vmap(core)(sky, counts, offs, widths)
