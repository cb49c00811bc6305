"""SFS (sort-filter-skyline) append rounds — the framework's fastest exact
skyline machinery for windows available in full.

Under minimization, ``a`` dominates ``b`` implies ``sum(a) < sum(b)``; after
sorting a window by coordinate sum ascending and streaming blocks in order,
the skyline buffer becomes APPEND-ONLY: every block survivor is globally
non-dominated (nothing later can dominate it), so there is no buffer
re-pruning and no re-compaction — one forward pass of O(N·S) dominance work.
This replaces the reference's tuple-at-a-time BNL loop
(SkylineLocalProcessor.processBuffer, FlinkSkyline.java:417-444).

These are pure device kernels (ops layer); the stateful streaming owner is
``stream.batched.PartitionSet`` (lazy flush policy), and the single-set
library form is ``ops.block_skyline.skyline_large``.

Host sibling: ``ops.sorted_sfs`` runs the same sum-sorted append scan in
NumPy with a dedup front end and exact in-block tiles for the equal-sum
band — byte-identical appends (same pre-sorted rows, same order, selection
only). On non-TPU backends the lazy flush picks between these rounds and
the host cascade per (d, N, backend) signature from measured profiler wall
data (``dispatch.choose_variant``; RUNBOOK §2m).

The jits donate the ``sky`` buffer so each append round updates the
full-capacity buffer in place instead of copying it (64 MB/round at the
north-star window; donation is a no-op with a warning on CPU, filtered in
tests/conftest.py). Callers must treat the passed-in buffer as consumed —
every call site reassigns ``sky, counts = sfs_*(sky, counts, ...)``. The
count carries are NOT donated: they are 4-byte scalars, and callers keep
references to earlier rounds' counts (``skyline_large``'s lag-2 reads)
that donation would invalidate.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax import lax

from skyline_tpu.ops.dispatch import on_tpu
from skyline_tpu.ops.dominance import (
    compact,
    dominated_by,
    strictly_dominated_bf16,
)

# Dominator-prefix length for the row-level bf16 pre-drop (mixed-precision
# stage 2): block rows certainly strictly-dominated by one of the first
# _MP_PREFIX skyline rows are dropped (masked to +inf) before the exact
# kernels. Sum-sorted skylines put the strongest dominators first, so a
# short prefix catches most dominated rows at O(B·prefix) cost — the full
# exact pass over survivors keeps the result bit-identical regardless.
_MP_PREFIX = 512


def pallas_interpret() -> bool:
    """Read lazily (at trace time, not import time): set
    ``SKYLINE_PALLAS_INTERPRET=1`` to run the Pallas kernels in interpret
    mode on CPU — how ``dryrun_multichip`` validates the
    shard_map-of-pallas_call lowering without TPU hardware. Evaluated when a
    kernel first traces; already-compiled executables are unaffected by
    later env changes."""
    from skyline_tpu.analysis.registry import env_bool

    return env_bool("SKYLINE_PALLAS_INTERPRET", False)


def adjacent_duplicates(x, valid):
    """(N,) bool: row ``i`` and row ``i - 1`` are both valid and equal in
    every coordinate — the followers of each run of adjacent equal valid
    rows. The first row of every run stays unmarked, an invalid row breaks
    a run, and a row holding a NaN never equals anything."""
    same = jnp.all(x[1:] == x[:-1], axis=1) & valid[1:] & valid[:-1]
    return jnp.concatenate([jnp.zeros((1,), dtype=bool), same])


def sfs_round_core(sky, count, block, bvalid, active, use_pallas, interp, mp=False):
    """One SFS append round for one partition.

    sky: (cap, d) buffer whose first ``count`` rows are a skyline; block:
    (B, d) sum-sorted ascending (invalid rows padded +inf at the end), with
    all sums >= any previously appended block's in this SFS pass. Appends
    the block's survivors at ``count``. ``active`` (static) bounds the
    dominator prefix actually compared against — the capacity bucket of the
    current max count, so early rounds don't pay full-capacity passes.

    ``mp`` (static) enables the mixed-precision stage-2 pass: a bf16 margin
    pre-drop of block rows certainly strictly-dominated by a skyline prefix
    row (counted in the third return), plus the in-kernel bf16 first pass
    of the Pallas tri-kernels. Bit-exact vs ``mp=False``: a certified drop
    implies the exact sky-vs-block pass drops the row too, and any block
    row it would itself have pruned is strictly dominated by the same sky
    row (transitivity), so the survivor set and the stable compact order
    are unchanged.

    Both passes take the followers of each run of adjacent equal rows
    (``adjacent_duplicates``) out of the dominators, never out of the
    victims. Equal rows dominate the same victims and the run's first row
    stays a dominator, so every verdict is unchanged; what changes is the
    kernels' sum-bound tile skip, which a tile of copies (tying on every
    sum) otherwise never passes. The rectangular pass also hands the skip
    the victims' validity. Every copy is still a victim, so every copy
    survives as before.

    Returns ``(sky, count, resolved, tiles)``: ``resolved`` is the int32
    count of bf16-certified drops (0 when ``mp=False``), ``tiles`` the int32
    count of the two Pallas passes' tile pairs whose body runs
    (``tiles_run``), reckoned beside either path.

    Caller guarantees count + B <= cap (the compacted block writes B slots;
    rows past the survivor count are +inf padding landing on virgin rows).
    """
    from skyline_tpu.ops.pallas_dominance import (
        dominated_by_any_pallas,
        dominated_by_pallas,
        tiles_run,
    )

    cap, d = sky.shape
    sky_act = lax.slice(sky, (0, 0), (active, d))
    sky_ok = jnp.arange(active) < count
    resolved = jnp.zeros((), dtype=jnp.int32)
    if mp:
        limit = min(active, _MP_PREFIX)
        pre = strictly_dominated_bf16(
            block,
            lax.slice(sky, (0, 0), (limit, d)),
            jnp.arange(limit) < count,
        )
        pre = pre & bvalid
        resolved = jnp.sum(pre, dtype=jnp.int32)
        bvalid = bvalid & ~pre
        # +inf'd rows stay sum-sort-compatible for the triangular skip (a
        # replaced row only moves UP in sum, and its own column's verdict
        # is masked out by bvalid)
        block = jnp.where(bvalid[:, None], block, jnp.inf)
    sky_dom = sky_ok & ~adjacent_duplicates(sky_act, sky_ok)
    block_dom = bvalid & ~adjacent_duplicates(block, bvalid)
    sky_t, block_t = sky_act.T, block.T
    if use_pallas:
        keep = bvalid & ~dominated_by_any_pallas(
            block_t, block_dom, triangular=True, interpret=interp, mp=mp
        )
        keep = keep & ~dominated_by_pallas(
            sky_t, sky_dom, block_t, interpret=interp, mp=mp, y_valid=bvalid
        )
    else:
        keep = bvalid & ~dominated_by(block, block, x_valid=block_dom)
        keep = keep & ~dominated_by(block, sky_act, x_valid=sky_dom)
    tiles = tiles_run(sky_t, sky_dom, block_t, bvalid) + tiles_run(
        block_t, block_dom, block_t, triangular=True
    )
    vals, _, m = compact(block, keep, block.shape[0])
    sky = lax.dynamic_update_slice(sky, vals, (count, 0))
    return sky, count + m, resolved, tiles


@functools.partial(
    jax.jit, static_argnames=("active", "mp"), donate_argnums=(0,)
)
def sfs_round(sky, counts, blocks, bvalids, active: int, mp: bool = False):
    """Vmapped SFS round over all partitions: sky (P, cap, d), counts (P,)
    int32, blocks (P, B, d), bvalids (P, B) -> (sky', counts', resolved
    (P,), tiles (P,)). One device launch for the whole set — right when
    partitions carry comparable row counts (every vmap lane computes the
    full (B x active) passes whether its block is real or padding; see
    ``sfs_round_single`` for the skewed case). ``mp`` (static) threads the
    mixed-precision pass — a jit cache key, so flipping the env gate
    really switches executables."""
    use_pallas = on_tpu()
    interp = pallas_interpret()

    def core(s, c, b, bv):
        return sfs_round_core(s, c, b, bv, active, use_pallas, interp, mp)

    return jax.vmap(core)(sky, counts, blocks, bvalids)


@functools.partial(
    jax.jit, static_argnames=("active", "mp"), donate_argnums=(0,)
)
def sfs_round_single(sky_p, count, block, bvalid, active: int, mp: bool = False):
    """One partition's SFS round without the vmap lane dimension: sky_p
    (cap, d), count () int32, block (B, d), bvalid (B,). Under routing skew
    (one or two partitions holding most of the stream — mr-angle at 8D
    anti-correlated routes ~96% of rows to 2 of 8 partitions) the vmapped
    round pays P lanes of (B x active) work for one real lane; processing
    the heavy partitions individually costs exactly their own rows.
    Returns (sky', count', resolved, tiles)."""
    return sfs_round_core(
        sky_p, count, block, bvalid, active, on_tpu(), pallas_interpret(), mp
    )


def sfs_cleanup_core(s, c, old_c, old_active, active, use_pallas, interp):
    """One partition's old-vs-new prune after SFS rounds on non-empty
    initial state: old rows (prefix ``old_c``) may be dominated by newly
    appended rows (guaranteed non-dominated among themselves and not
    dominated BY the old rows); prune and re-compact. Returns
    (vals (cap, d), count)."""
    cap, d = s.shape
    act = lax.slice(s, (0, 0), (active, d))
    new_ok = (jnp.arange(active) >= old_c) & (jnp.arange(active) < c)
    old = lax.slice(s, (0, 0), (old_active, d))
    if use_pallas:
        from skyline_tpu.ops.pallas_dominance import dominated_by_pallas

        old_dom = dominated_by_pallas(act.T, new_ok, old.T, interpret=interp)
    else:
        old_dom = dominated_by(old, act, x_valid=new_ok)
    old_keep = (jnp.arange(old_active) < old_c) & ~old_dom
    keep = jnp.zeros((cap,), dtype=bool)
    keep = keep.at[:active].set(new_ok)
    keep = keep.at[:old_active].set(old_keep | new_ok[:old_active])
    vals, _, cnt = compact(s, keep, cap)
    return vals, cnt.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("old_active", "active"),
    donate_argnums=(0,),
)
def sfs_cleanup(sky, counts, old_counts, old_active: int, active: int):
    """Vmapped ``sfs_cleanup_core`` over all partitions.
    ``old_active``/``active`` (static) are the capacity buckets of the old
    and final max counts — dominator and victim sets are sliced to them so
    a shrunken skyline in a grown buffer never pays full-capacity passes.
    Returns (sky', counts')."""
    use_pallas = on_tpu()
    interp = pallas_interpret()

    def core(s, c, old_c):
        return sfs_cleanup_core(
            s, c, old_c, old_active, active, use_pallas, interp
        )

    vals, cnt = jax.vmap(core)(sky, counts, old_counts)
    return vals, cnt
