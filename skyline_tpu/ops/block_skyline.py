"""Blockwise / large-window skyline computation.

Two tiers above the dense tile kernels in ``dominance.py``:

1. ``skyline_mask_blocked`` — fully jitted, static-shape, nested-``lax.scan``
   over (column-block, row-block) tiles with a sum-sort triangular pruning:
   under minimization, ``a`` dominates ``b`` implies ``sum(a) < sum(b)``, so
   after sorting by coordinate sum only earlier blocks can dominate later
   ones. Used for per-shard local skylines on the mesh (N up to ~10^5).

2. ``skyline_large`` — sort-filter-skyline (SFS) for full-size windows
   (N ~ 10^6): sort by sum ascending, stream blocks through the device, and
   maintain an append-only global-skyline buffer on device. Because
   dominators always have strictly smaller sums, every point that survives
   its block-prune is *globally* non-dominated and the buffer never needs
   re-pruning. Host control flow issues one async round per block
   (``ops.sfs.sfs_round_single`` — the same kernel the streaming engine's
   lazy flush policy uses for skewed partitions), tightening the dominator
   bound from lag-2 count reads that never stall the dispatch pipeline;
   this single-set form is the library op.

This replaces the reference's tuple-at-a-time BNL (FlinkSkyline.java:417-444),
whose O(|buffer| x |skyline|) pointer-chasing loop is the system's documented
hot loop (SURVEY.md §3.2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from skyline_tpu.ops.dominance import (
    PAD_VALUE,
    dominated_by,
    skyline_mask,
    strictly_dominated_bf16,
)
from skyline_tpu.utils.buckets import next_pow2

# Dominator-prefix length for the bf16 margin pre-pass of the scan
# fallbacks (mirrors ops/sfs._MP_PREFIX): victims certified strictly
# dominated by one of the first _MP_PREFIX dominator rows are final before
# the chunk scan runs, and their sums drop out of the victim_max bound so
# more dominator chunks clear the sum-skip. Certification is a proof of
# f32 dominance (ops/dominance.strictly_dominated_bf16), so OR-ing it into
# the scan verdict is bit-exact.
_MP_PREFIX = 512


def _sum_sort(x: jax.Array, valid: jax.Array):
    """Sort rows by coordinate sum ascending, invalid rows last.

    Returns (x_sorted, valid_sorted, inverse_permutation).
    """
    keys = jnp.where(valid, jnp.sum(x, axis=-1), jnp.inf)
    order = jnp.argsort(keys, stable=True)
    inv = jnp.argsort(order, stable=True)
    return x[order], valid[order], inv


@functools.partial(jax.jit, static_argnames=("block",))
def skyline_mask_blocked(x: jax.Array, valid: jax.Array | None = None, block: int = 2048):
    """Survivor mask over (N, d) points, tiled in ``block``-row chunks.

    Semantically identical to ``skyline_mask`` but never materializes more
    than a (block, block) pairwise tile, so it scales to N ~ 10^5 under jit.
    N is padded up to a multiple of ``block`` internally; the returned mask
    is in the caller's original row order.
    """
    n, d = x.shape
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    nb = -(-n // block)  # ceil
    padded = nb * block
    if padded != n:
        pad_x = jnp.full((padded - n, d), PAD_VALUE, dtype=x.dtype)
        x = jnp.concatenate([x, pad_x], axis=0)
        valid = jnp.concatenate([valid, jnp.zeros((padded - n,), dtype=bool)], axis=0)

    xs, vs, inv = _sum_sort(x, valid)
    xb = xs.reshape(nb, block, d)
    vb = vs.reshape(nb, block)

    # Phase A: intra-block survivor masks, sequential over blocks to bound
    # peak memory at one (block, block) tile.
    mask_a = lax.map(lambda args: skyline_mask(args[0], args[1]), (xb, vb))

    # Phase B: cross-block triangular prune. Only blocks i <= j can hold
    # dominators of block j (sum-sorted). Phase-A survivors suffice as
    # dominators: a phase-A-dominated point's dominator also dominates
    # whatever it dominated (transitivity).
    block_ids = jnp.arange(nb)

    def col_step(_, j):
        yj = xb[j]

        def row_step(dom_j, i):
            # lax.cond genuinely skips the tile at runtime (the scan is not
            # vmapped), so the triangular prune halves the pairwise work.
            dom_j = lax.cond(
                i <= j,
                lambda d: d | dominated_by(yj, xb[i], x_valid=mask_a[i]),
                lambda d: d,
                dom_j,
            )
            return dom_j, None

        dom_j0 = jnp.zeros((block,), dtype=bool)
        dom_j, _ = lax.scan(row_step, dom_j0, block_ids)
        return None, mask_a[j] & ~dom_j

    _, keep = lax.scan(col_step, None, block_ids)
    keep = keep.reshape(padded)[inv]
    return keep[:n]


@functools.partial(jax.jit, static_argnames=("chunk", "mp"))
def skyline_mask_scan(
    x: jax.Array,
    valid: jax.Array | None = None,
    chunk: int = 0,
    mp: bool = False,
):
    """Survivor mask via a LINEAR scan of dominator chunks against all columns.

    Same O(N^2 d) comparisons as the dense/blocked kernels but organized as
    ``nb`` sequential steps of one (chunk, N) tile each — an order of
    magnitude fewer dispatches than the (nb^2)-step nested scan in
    ``skyline_mask_blocked``, which is latency-bound on TPU for N ~ 10^5
    (measured on the chip in July 2026). Peak per-step memory is one (chunk, N) bool tile, so
    ``chunk`` shrinks automatically as N grows.

    ``mp`` (static) prepends the bf16 margin pass: rows certified strictly
    dominated by a short dominator prefix are final before the scan and
    leave the victim_max bound, so more chunks clear the sum-skip. The
    returned mask is bit-identical either way.
    """
    n, d = x.shape
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    if chunk <= 0:
        # keep the per-step (chunk, N) tile around ~2^28 bools (~256 MB)
        chunk = max(256, min(4096, (1 << 28) // max(n, 1)))
    nb = -(-n // chunk)
    padded = nb * chunk
    if padded != n:
        pad_x = jnp.full((padded - n, d), PAD_VALUE, dtype=x.dtype)
        xp = jnp.concatenate([x, pad_x], axis=0)
        vp = jnp.concatenate([valid, jnp.zeros((padded - n,), dtype=bool)], axis=0)
    else:
        xp, vp = x, valid
    rows = xp.reshape(nb, chunk, d)
    rvalid = vp.reshape(nb, chunk)

    if mp:
        limit = min(padded, _MP_PREFIX)
        certified = vp & strictly_dominated_bf16(
            xp, xp[:limit], vp[:limit]
        )
    else:
        certified = jnp.zeros((padded,), dtype=bool)

    # Sum-bound chunk skip (same argument as pallas_dominance._tile_sum_skip:
    # f32 addition is monotone, so a dominator's sum never exceeds its
    # victim's). A chunk whose smallest valid-row sum beats every valid
    # point's sum cannot dominate anything; lax.cond genuinely skips the
    # (chunk, N) tile at runtime (the scan is not vmapped). All-padding
    # chunks — capacity-bucket overshoot — always skip. Skipped chunks leave
    # invalid positions undominated, which `& vp` masks identically.
    # Certified victims drop out of the bound: a chunk only able to
    # dominate them is skippable because their verdict is already final.
    sums = jnp.where(vp, jnp.sum(xp, axis=-1), jnp.inf)
    chunk_min = jnp.min(sums.reshape(nb, chunk), axis=1)
    victim_max = jnp.max(jnp.where(vp & ~certified, sums, -jnp.inf))

    def step(dom, blk):
        rx, rv, mn = blk
        dom = lax.cond(
            mn > victim_max,
            lambda d: d,
            lambda d: d | dominated_by(xp, rx, x_valid=rv),
            dom,
        )
        return dom, None

    dom0 = jnp.zeros((padded,), dtype=bool)
    dom, _ = lax.scan(step, dom0, (rows, rvalid, chunk_min))
    return (~(dom | certified) & vp)[:n]


@functools.partial(jax.jit, static_argnames=("block", "mp"))
def dominated_by_blocked(
    y: jax.Array,
    x: jax.Array,
    x_valid: jax.Array | None = None,
    block: int = 8192,
    y_valid: jax.Array | None = None,
    mp: bool = False,
) -> jax.Array:
    """Like ``dominated_by`` but scans dominator set ``x`` in ``block``-row
    chunks so the pairwise tile never exceeds (len(y), block). Used for the
    cross-shard prune in the global merge, where the gathered dominator set is
    P times a shard, and for the tournament-tree pair merges on CPU.

    Dominator chunks whose smallest valid-row sum exceeds the largest victim
    sum are skipped outright (sum-bound prune, see ``skyline_mask_scan``).
    Passing ``y_valid`` tightens that bound to valid victims only — then
    positions with ``y_valid`` False may be reported undominated where the
    dense op would say dominated; callers must mask the result by victim
    validity (every call site in this repo already does). ``mp`` (static)
    prepends the bf16 margin pass over a short dominator prefix; certified
    victims are final (OR-ed into the result) and leave the victim_max
    bound — bit-identical either way."""
    n, d = x.shape
    if y.shape[0] == 0:
        return jnp.zeros((0,), dtype=bool)
    if x_valid is None:
        x_valid = jnp.ones((n,), dtype=bool)
    if mp:
        limit = min(n, _MP_PREFIX)
        certified = strictly_dominated_bf16(y, x[:limit], x_valid[:limit])
        if y_valid is not None:
            certified = certified & y_valid
    else:
        certified = jnp.zeros((y.shape[0],), dtype=bool)
    nb = -(-n // block)
    padded = nb * block
    if padded != n:
        pad_x = jnp.full((padded - n, d), PAD_VALUE, dtype=x.dtype)
        x = jnp.concatenate([x, pad_x], axis=0)
        x_valid = jnp.concatenate(
            [x_valid, jnp.zeros((padded - n,), dtype=bool)], axis=0
        )
    xb = x.reshape(nb, block, d)
    vb = x_valid.reshape(nb, block)

    xsums = jnp.where(x_valid, jnp.sum(x, axis=-1), jnp.inf)
    chunk_min = jnp.min(xsums.reshape(nb, block), axis=1)
    ysums = jnp.sum(y, axis=-1)
    if y_valid is not None:
        ysums = jnp.where(y_valid, ysums, -jnp.inf)
    ysums = jnp.where(certified, -jnp.inf, ysums)
    victim_max = jnp.max(ysums)

    def step(dom, chunk):
        cx, cv, mn = chunk
        dom = lax.cond(
            mn > victim_max,
            lambda d: d,
            lambda d: d | dominated_by(y, cx, x_valid=cv),
            dom,
        )
        return dom, None

    dom0 = jnp.zeros((y.shape[0],), dtype=bool)
    dom, _ = lax.scan(step, dom0, (xb, vb, chunk_min))
    return dom | certified


@functools.partial(jax.jit, static_argnames=("out_cap",))
def _slice_front(sky, out_cap: int):
    return lax.slice(sky, (0, 0), (out_cap, sky.shape[1]))


def skyline_large(
    x: np.ndarray,
    block: int = 0,
    dense_threshold: int = 8192,
    mp: bool | None = None,
) -> np.ndarray:
    """Exact skyline of an (N, d) numpy window: host sum-sort, device-side
    append-only SFS rounds (``ops.sfs.sfs_round_single``, Pallas kernels on
    TPU), pipeline-friendly lag-2 count syncs.

    Sum-sorting guarantees appended points are final — no later point can
    dominate an earlier one — so the buffer is append-only and the total
    work is O(N * S) dominance tests (S = skyline size) instead of the BNL's
    pointer-chasing loop or the naive O(N^2). The per-round dominator prefix
    is re-tightened from LAG-2 count reads: before issuing round r the host
    reads the survivor count of round r-2 — work the device already
    finished while later rounds queued — so the dominator bucket tracks the
    true skyline size (O(N*(S+B)) total) without ever stalling the dispatch
    pipeline on a high-latency device link. The old per-block-synced XLA
    form measured 74 s on the 1M x 8D anti-correlated window
    (on the chip, July 2026); this form runs the same
    kernels/shapes as the engine's SFS flush. Its own time on the chip is
    not measured yet.

    ``block=0`` scales the block with N on TPU (the same heuristic as the
    streaming engine's skewed-partition path: fewer dispatches for big
    windows, block self-prune cost grows only linearly in B); on CPU it
    stays at 8192 so the dense (block x active) dominance mask stays
    bounded.

    ``mp=None`` reads ``SKYLINE_MIXED_PRECISION`` per call (host-side, so
    flipping the env really switches executables); True/False pin the
    bf16-first cascade on/off. The result is bit-identical either way.
    """
    from skyline_tpu.ops.dispatch import mixed_precision_enabled, on_tpu
    from skyline_tpu.ops.sfs import sfs_round_single

    if mp is None:
        mp = mixed_precision_enabled()
    x = np.ascontiguousarray(x, dtype=np.float32)
    n, d = x.shape
    if n == 0:
        return x
    if n <= dense_threshold:
        keep = np.asarray(skyline_mask(jnp.asarray(x)))
        return x[keep]

    order = np.argsort(x.sum(axis=1), kind="stable")
    xs = x[order]

    if block <= 0:
        if on_tpu():
            block = next_pow2(
                min(n, max(16384, min(n // 8, 65536))), min_cap=1024
            )
        else:
            block = 8192
    nb = -(-n // block)
    # worst case (nothing dominated) the append prefix reaches n, and the
    # final round writes a full block at that offset
    cap = next_pow2(n + block, min_cap=1024)
    sky = jnp.full((cap, d), jnp.inf, dtype=jnp.float32)
    count = jnp.zeros((), dtype=jnp.int32)

    counts = []  # per-round device count scalars, for the lag-2 reads
    for rnd in range(nb):
        blk = xs[rnd * block : (rnd + 1) * block]
        w = blk.shape[0]
        if w < block:
            blk = np.concatenate(
                [blk, np.full((block - w, d), np.inf, dtype=np.float32)],
                axis=0,
            )
        bvalid = np.arange(block) < w
        if rnd >= 2:
            # count entering this round <= count after round r-2 plus the
            # rows appended by round r-1; reading counts[rnd-2] waits only
            # for work two rounds deep, which has already drained
            ub = int(counts[rnd - 2]) + block
        else:
            ub = rnd * block  # rows streamed so far bound the count
        active = min(cap, next_pow2(max(ub, 1), min_cap=1024))
        sky, count = sfs_round_single(
            sky, count, jnp.asarray(blk), jnp.asarray(bvalid), active, mp
        )[:2]
        counts.append(count)

    k = int(count)  # the final sync
    out_cap = min(cap, next_pow2(max(k, 1), min_cap=1024))
    return np.asarray(_slice_front(sky, out_cap))[:k].copy()

