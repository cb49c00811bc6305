"""Pallas TPU kernel for the dominance bitmask — the system's hot op.

Computes, for every point of a set, whether ANY valid point dominates it
(minimization: all(<=) and any(<)). This is the inner operation of both the
local flush and the global merge; the XLA version (`skyline_mask_scan`)
materializes (chunk, N) bool tiles through HBM, while this kernel keeps the
whole (R, C) comparison tile in VMEM and fuses the per-dimension compare
cascade with the row-reduction. Off-TPU, concrete (non-traced) d>2 calls
may instead route to the host sorted cascade (``ops/sorted_sfs.py``) when
its measured wall beats the scan — see ``dispatch.skyline_mask_auto``;
this kernel remains the only d>2 path on TPU and inside jit.

Layout: points are fed TRANSPOSED as ``(d, N)`` so each dimension's
coordinates lie contiguous along lanes — the (R, C) broadcast compare then
maps directly onto the 8x128 VPU with no gather. The d-loop is a static
Python unroll (d is tiny: 2-16).

Grid is (col_tiles, row_tiles): all row tiles for one column tile run
consecutively, accumulating the per-column "dominated" flags in the output
block across the inner grid dimension (the standard Pallas reduce pattern).

Rank-compressed cascade (round 4; round 3 had rejected it when ranking was
host-side): ``rank_transform`` computes per-dim DENSE ranks + rank sums on
device — dense rank over the compared universe is a perfect order
embedding (v1 < v2 implies rank(v1) < rank(v2) because v1 itself is
counted; equal values share a rank), and the strictness test collapses to
ONE precomputed rank-sum compare per pair: ``a dominates b  <=>
max_k(ra_k - rb_k) <= 0  AND  rsum_a < rsum_b`` (all-<= with equal sums
forces equality in every dim since each term is <=). That is 2 VPU ops per
dim + 2 instead of 3 per dim + 2 — see ``_dom_tile_rank``. The hardware
A/B (benchmarks/rank_cascade.py -> artifacts/rank_cascade_ab.json) has not
run on the chip; until it does the value cascade stays the default
(ops/dispatch.py).
Rank sums stay exact in f32 (ranks < N <= 2^20, sums < d * N << 2^24).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skyline_tpu.ops.dominance import PAD_VALUE

# (rows=dominators, cols=victims) per VMEM tile. Defaults picked by the
# committed tile sweep (artifacts/kernels_tpu.json: 85 Gpairs/s at 512x2048
# with the min/max cascade, vs 54 at the old 512x1024 bool-chain kernel).
# d<=16 keeps the unrolled cascade small.
ROW_TILE = 512
COL_TILE = 2048


def _dom_tile(d: int, x_ref, y_ref, v_ref, rows=slice(None), cols=slice(None)):
    """(R, C) dominance tile via the min/max reformulation:
    ``x dominates y  <=>  max_k(x_k - y_k) <= 0  AND  min_k(x_k - y_k) < 0``
    — 3 f32 VPU ops per dimension (sub, max, min) instead of the naive
    4-op compare/bool chain, and the bool work collapses to one pair of
    compares per tile. Measured ~1.6x the bool-chain kernel
    (artifacts/kernels_tpu.json). ``rows`` / ``cols`` pick a sub-block of
    the tile (the mixed-precision body rechecks one block at a time)."""
    diff = x_ref[0, rows][:, None] - y_ref[0, cols][None, :]
    mx = diff
    mn = diff
    for k in range(1, d):  # static unroll over dimensions
        dk = x_ref[k, rows][:, None] - y_ref[k, cols][None, :]
        mx = jnp.maximum(mx, dk)
        mn = jnp.minimum(mn, dk)
    vmask = v_ref[0, rows][:, None] > 0.5  # (R, 1) from a 32-bit load
    return (mx <= 0.0) & (mn < 0.0) & vmask


# bf16 margin for the in-kernel mixed-precision first pass (ISSUE 5 stage
# 2). Wider than ops/dominance._BF16_EPS because here the margin and the
# differences are themselves computed in bf16: 2^-6 is 4x the ~2^-7.9
# combined representation-error bound, absorbing the extra rounding of the
# bf16 margin arithmetic with slack to spare. Over-wide margins only send
# more pairs to the f32 recheck — they can never flip a certified verdict,
# so the kernel stays bit-exact (RUNBOOK §2g).
_BF16_K_EPS = 0.015625  # 2^-6
_BF16_K_TINY = 1e-30


def _dom_tile_mp(d: int, x_ref, y_ref, v_ref, rows, cols):
    """bf16 trilean classification of one (S, B) block of (dominator,
    victim) pairs: returns ``(certain, undecided)`` where
    ``certain[i, j]`` certifies f32 STRICT dominance (every dim below the
    margin band) and ``undecided`` says whether some pair lies inside the
    band in some dim with no dim certainly greater — only then does the
    block need the f32 recheck. Pairs with a certainly-greater dim are
    final non-dominators (x_k > y_k in f32 kills all(<=)). NaN coords fail
    every margin test -> undecided -> f32 recheck (conservative); +inf
    dominator rows get diff = +inf > margin -> certainly-greater -> decided
    inert.

    Mosaic constraints (v5e): each coordinate is loaded as a 2-D f32 value
    and cast to bf16 afterwards (a 1-D bf16 -> column reshape has no
    layout), and the undecided flag is a 32-bit reduction (a scalar
    reduction of an i1 tile cannot be relaid out)."""
    bf, f32 = jnp.bfloat16, jnp.float32

    def margin_diff(k):
        xb = x_ref[k, rows][:, None].astype(bf)  # (S, 1)
        yb = y_ref[k, cols][None, :].astype(bf)  # (1, B)
        m = _BF16_K_EPS * (jnp.abs(xb) + jnp.abs(yb)) + _BF16_K_TINY
        # bf16 values compared in f32: same verdicts, and Mosaic has no
        # layout for a bf16 compare's mask on v5e
        return (xb - yb).astype(f32), m.astype(f32)

    diff, m = margin_diff(0)
    all_lt = diff < -m
    any_gt = diff > m
    for k in range(1, d):  # static unroll over dimensions
        dk, m = margin_diff(k)
        all_lt = all_lt & (dk < -m)
        any_gt = any_gt | (dk > m)
    vcol = v_ref[0, rows][:, None]  # (S, 1) validity as 1.0 / 0.0
    certain = all_lt & (vcol > 0.5)
    open_pairs = jnp.where(all_lt | any_gt, 0.0, 1.0) * vcol
    undecided = jnp.max(open_pairs) > 0.5
    return certain, undecided


# (dominator rows, victim cols) per block of the mixed-precision body.
# Bounds the live intermediates: the whole (512, 2048) tile at once needs
# ~49 MiB of scoped VMEM on v5e (limit 16 MiB). Lane slices must stay
# 128-aligned.
_MP_BLOCK = (128, 512)


def _tile_body(d: int, mp: bool, x_ref, y_ref, v_ref, out_ref):
    """Shared compute body of the value-cascade kernels: with ``mp`` the
    bf16 margin pass decides each block of pairs first and the f32 cascade
    reruns on that block only when some pair lands inside the margin band.
    Exact either way: a fully decided block's certain set IS the f32
    dominator set (decided-false pairs have a strictly-greater dim), and an
    ambiguous block ORs in the full f32 verdict (a superset of its certain
    pairs)."""
    if not mp:
        dom = _dom_tile(d, x_ref, y_ref, v_ref)
        out_ref[...] = out_ref[...] | dom.any(axis=0, keepdims=True)
        return

    r, c = x_ref.shape[1], y_ref.shape[1]
    sr, sc = min(_MP_BLOCK[0], r), min(_MP_BLOCK[1], c)
    n_row_blocks = r // sr

    def block(b, carry):
        i, j = b % n_row_blocks, b // n_row_blocks
        rows = pl.ds(pl.multiple_of(i * sr, sr), sr)
        cols = pl.ds(pl.multiple_of(j * sc, sc), sc)
        certain, undecided = _dom_tile_mp(d, x_ref, y_ref, v_ref, rows, cols)
        out_ref[:, cols] = out_ref[:, cols] | certain.any(axis=0, keepdims=True)

        @pl.when(undecided)
        def _exact():
            dom = _dom_tile(d, x_ref, y_ref, v_ref, rows, cols)
            out_ref[:, cols] = out_ref[:, cols] | dom.any(axis=0, keepdims=True)

        return carry

    jax.lax.fori_loop(0, n_row_blocks * (c // sc), block, 0)


def _tile_sum_skip(d: int, x_ref, y_ref, v_ref):
    """Sum-bound early exit for one (R, C) tile: if the smallest coordinate
    sum among VALID dominator rows exceeds the largest victim sum, no pair in
    the tile can dominate and the compute body is skipped.

    Soundness in f32: rounded addition is monotone, so ``a <= b`` per-dim
    implies ``sumf(a) <= sumf(b)`` — domination never crosses a strict sum
    gap. Strict ``>`` is required (a dominator may tie its victim's sum).
    +inf pad victims give max = inf and suppress the skip (conservative);
    all-pad / all-invalid dominator tiles give min = inf and always skip —
    which is where the win is: capacity-bucket overshoot fills whole
    dominator tiles with padding, and in cross-set merges of sum-sorted
    survivor prefixes entire (strong, weak) tile pairs clear the gap."""
    sx = x_ref[0, :]
    sy = y_ref[0, :]
    for k in range(1, d):  # static unroll over dimensions
        sx = sx + x_ref[k, :]
        sy = sy + y_ref[k, :]
    sx = jnp.where(v_ref[0, :] > 0.5, sx, jnp.inf)
    return jnp.min(sx) > jnp.max(sy)


def _tile_rank_skip(d: int, x_ref, y_ref, v_ref):
    """Rank-cascade twin of ``_tile_sum_skip`` over the precomputed int32
    rank-sum row (row ``d``). Rank domination needs ``rsum_x < rsum_y``
    strictly, so ``>=`` across the tile bound rules it out (int32 sums are
    exact — no rounding caveat)."""
    big = jnp.iinfo(jnp.int32).max
    sx = jnp.where(v_ref[0, :] > 0.5, x_ref[d, :], big)
    return jnp.min(sx) >= jnp.max(y_ref[d, :])


def _kernel_tri(d: int, rt: int, ct: int, mp: bool, x_ref, v_ref, y_ref, out_ref):
    """Triangular variant: inputs are pre-sorted by coordinate sum ascending,
    so a row (dominator) tile strictly after the column (victim) tile in sort
    order can never dominate — the whole tile is skipped. Halves the work of
    the self-skyline case. Surviving tiles still pass the data-dependent
    sum-bound check (``_tile_sum_skip``) before paying the O(R*C*d) body
    (bf16-first when ``mp``, see ``_tile_body``).

    Padding note: +inf pad rows produce diff = inf - y = inf -> mx = inf,
    never <= 0, so padding stays dominance-neutral; inf - inf = nan
    compares false on both branches, so pad-vs-pad pairs are inert too."""
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i * rt <= j * ct + (ct - 1))
    def _compute():
        @pl.when(jnp.logical_not(_tile_sum_skip(d, x_ref, y_ref, v_ref)))
        def _body():
            _tile_body(d, mp, x_ref, y_ref, v_ref, out_ref)


def _kernel(d: int, rt: int, ct: int, mp: bool, x_ref, v_ref, y_ref, out_ref):
    # x_ref: (d, R) dominator coords; v_ref: (1, R) dominator validity as
    # float32 (Mosaic can't reshape 1-bit vectors across the minor dim);
    # y_ref: (d, C) victim coords; out_ref: (1, C) accumulated dominated flags
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.logical_not(_tile_sum_skip(d, x_ref, y_ref, v_ref)))
    def _compute():
        _tile_body(d, mp, x_ref, y_ref, v_ref, out_ref)


def _dom_tile_rank(d: int, x_ref, y_ref, v_ref):
    """(R, C) dominance tile over per-dim dense ranks: rows 0..d-1 of the
    refs are ranks, row d is the rank sum — all INT32 (2 VPU ops per
    dimension: sub, max; plus one sum compare). The strict-dimension test
    the value cascade pays a min-chain for collapses into the precomputed
    rank sums (see module docstring for the exactness argument). int32 is
    load-bearing: rank sums reach d * universe (~2^25 at the 8-D/1M flush
    with folded sky prefixes), past float32's 2^24 exact-integer limit —
    an f32 rank-sum would tie where the true sums differ by 1 and silently
    keep dominated rows."""
    diff = x_ref[0, :][:, None] - y_ref[0, :][None, :]
    mx = diff
    for k in range(1, d):
        mx = jnp.maximum(mx, x_ref[k, :][:, None] - y_ref[k, :][None, :])
    sd = x_ref[d, :][:, None] - y_ref[d, :][None, :]
    vmask = v_ref[0, :][:, None] > 0.5
    return (mx <= 0) & (sd < 0) & vmask


def _kernel_rank_tri(d: int, rt: int, ct: int, x_ref, v_ref, y_ref, out_ref):
    """Triangular rank-cascade kernel: same skip logic as ``_kernel_tri``
    (inputs sorted ascending by a dominance-monotone key — value sum or
    rank sum both qualify)."""
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i * rt <= j * ct + (ct - 1))
    def _compute():
        @pl.when(jnp.logical_not(_tile_rank_skip(d, x_ref, y_ref, v_ref)))
        def _body():
            dom = _dom_tile_rank(d, x_ref, y_ref, v_ref)
            out_ref[...] = out_ref[...] | dom.any(axis=0, keepdims=True)


def _kernel_rank(d: int, rt: int, ct: int, x_ref, v_ref, y_ref, out_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.logical_not(_tile_rank_skip(d, x_ref, y_ref, v_ref)))
    def _compute():
        dom = _dom_tile_rank(d, x_ref, y_ref, v_ref)
        out_ref[...] = out_ref[...] | dom.any(axis=0, keepdims=True)


def rank_transform(x: jax.Array, valid: jax.Array):
    """Per-dim dense ranks + rank sum over one point set (the compared
    universe) — the device-side preprocessing for the rank cascade.

    x: (N, d); valid: (N,) bool. Invalid rows are ranked as +inf values:
    every dim gets rank n_valid (= count of finite entries), making them
    inert exactly like +inf padding in the value cascade (they tie other
    pads, never strictly dominate). Returns ``rt (d+1, N) int32`` — ranks
    transposed with the rank-sum as the extra last row, the layout
    ``dominated_by_any_rank_pallas`` consumes. int32 keeps rank SUMS exact
    past f32's 2^24 limit (see ``_dom_tile_rank``).
    """
    xm = jnp.where(valid[:, None], x, jnp.inf)
    sorted_cols = jnp.sort(xm, axis=0)
    ranks = jax.vmap(
        lambda col, sc: jnp.searchsorted(sc, col, side="left"),
        in_axes=(1, 1),
        out_axes=1,
    )(xm, sorted_cols).astype(jnp.int32)
    rsum = jnp.sum(ranks, axis=1, keepdims=True, dtype=jnp.int32)
    return jnp.concatenate([ranks, rsum], axis=1).T


@functools.partial(
    jax.jit, static_argnames=("triangular", "interpret", "row_tile", "col_tile")
)
def dominated_by_any_rank_pallas(
    rt: jax.Array,
    valid: jax.Array,
    triangular: bool = False,
    interpret: bool = False,
    row_tile: int = ROW_TILE,
    col_tile: int = COL_TILE,
) -> jax.Array:
    """Rank-cascade twin of ``dominated_by_any_pallas``: rt is the
    (d+1, N) output of ``rank_transform`` (per-dim dense ranks + rank-sum
    row). ``triangular=True`` requires columns sorted ascending by a
    dominance-monotone key (value sum or rank sum)."""
    dp1, n = rt.shape
    d = dp1 - 1
    r_t, c_t = min(row_tile, n), min(col_tile, n)
    grid = (n // c_t, n // r_t)
    v2 = valid[None, :].astype(jnp.float32)
    kern = _kernel_rank_tri if triangular else _kernel_rank
    out = pl.pallas_call(
        functools.partial(kern, d, r_t, c_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((dp1, r_t), lambda j, i: (0, i)),
            pl.BlockSpec((1, r_t), lambda j, i: (0, i)),
            pl.BlockSpec((dp1, c_t), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, c_t), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.bool_),
        interpret=interpret,
    )(rt, v2, rt)
    return out[0]


@functools.partial(
    jax.jit, static_argnames=("interpret", "row_tile", "col_tile")
)
def dominated_by_rank_pallas(
    xt: jax.Array,
    x_valid: jax.Array,
    yt: jax.Array,
    interpret: bool = False,
    row_tile: int = ROW_TILE,
    col_tile: int = COL_TILE,
) -> jax.Array:
    """Rank-cascade twin of ``dominated_by_pallas``: xt (d+1, Nx) dominator
    ranks (+ rank-sum row), yt (d+1, Ny) victim ranks over the SAME rank
    universe. Nx % row_tile == 0, Ny % col_tile == 0."""
    dp1, nx = xt.shape
    _, ny = yt.shape
    rt, ct = min(row_tile, nx), min(col_tile, ny)
    grid = (ny // ct, nx // rt)
    v2 = x_valid[None, :].astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel_rank, dp1 - 1, rt, ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((dp1, rt), lambda j, i: (0, i)),
            pl.BlockSpec((1, rt), lambda j, i: (0, i)),
            pl.BlockSpec((dp1, ct), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, ct), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, ny), jnp.bool_),
        interpret=interpret,
    )(xt, v2, yt)
    return out[0]


@functools.partial(
    jax.jit,
    static_argnames=("triangular", "interpret", "row_tile", "col_tile", "mp"),
)
def dominated_by_any_pallas(
    xt: jax.Array,
    valid: jax.Array,
    triangular: bool = False,
    interpret: bool = False,
    row_tile: int = ROW_TILE,
    col_tile: int = COL_TILE,
    mp: bool = False,
) -> jax.Array:
    """dominated[j] = any valid i dominates j, over one transposed set.

    xt: (d, N) float32 with PAD_VALUE columns for padding; valid: (N,) bool.
    N must be a multiple of lcm(row_tile, col_tile) — use ``skyline_mask_pallas``
    which handles padding. Self-pairs are safe (a point never dominates
    itself) and padding columns never dominate (+inf is never <=).
    ``triangular=True`` requires rows sorted by coordinate sum ascending.
    ``mp=True`` runs the bf16 margin pass first inside each tile (bit-exact,
    see ``_tile_body``).
    """
    d, n = xt.shape
    # clamp tiles to the problem size (callers pad to >=1024-row buckets);
    # without this a 1024-cap buffer meets a 2048 default tile -> empty grid
    rt, ct = min(row_tile, n), min(col_tile, n)
    grid = (n // ct, n // rt)
    v2 = valid[None, :].astype(jnp.float32)  # (1, N), 32-bit for Mosaic
    kern = _kernel_tri if triangular else _kernel
    out = pl.pallas_call(
        functools.partial(kern, d, rt, ct, mp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, rt), lambda j, i: (0, i)),  # dominators
            pl.BlockSpec((1, rt), lambda j, i: (0, i)),  # their validity
            pl.BlockSpec((d, ct), lambda j, i: (0, j)),  # victims
        ],
        out_specs=pl.BlockSpec((1, ct), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.bool_),
        interpret=interpret,
    )(xt, v2, xt)
    return out[0]


@functools.partial(
    jax.jit, static_argnames=("interpret", "row_tile", "col_tile", "mp")
)
def dominated_by_pallas(
    xt: jax.Array,
    x_valid: jax.Array,
    yt: jax.Array,
    interpret: bool = False,
    row_tile: int = ROW_TILE,
    col_tile: int = COL_TILE,
    mp: bool = False,
) -> jax.Array:
    """Rectangular variant: dominated[j] = any valid x_i dominates y_j.

    xt: (d, Nx) dominators (Nx % row_tile == 0); yt: (d, Ny) victims
    (Ny % col_tile == 0). The streaming flush's batch-vs-skyline prune maps
    here directly. ``mp=True`` enables the in-tile bf16 first pass.
    """
    d, nx = xt.shape
    _, ny = yt.shape
    rt, ct = min(row_tile, nx), min(col_tile, ny)
    grid = (ny // ct, nx // rt)
    v2 = x_valid[None, :].astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, d, rt, ct, mp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, rt), lambda j, i: (0, i)),
            pl.BlockSpec((1, rt), lambda j, i: (0, i)),
            pl.BlockSpec((d, ct), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, ct), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, ny), jnp.bool_),
        interpret=interpret,
    )(xt, v2, yt)
    return out[0]


@functools.partial(
    jax.jit, static_argnames=("interpret", "row_tile", "col_tile", "mp")
)
def skyline_mask_pallas(
    x: jax.Array,
    valid: jax.Array | None = None,
    interpret: bool = False,
    row_tile: int = ROW_TILE,
    col_tile: int = COL_TILE,
    mp: bool = False,
) -> jax.Array:
    """Survivor mask over (N, d) points via the Pallas dominance kernel.

    Semantically identical to ``skyline_mask`` / ``skyline_mask_scan``;
    pads N up to a tile multiple internally, sum-sorts to exploit the
    triangular skip, and unsorts the result. ``mp=True`` enables the
    in-tile bf16 first pass (bit-exact).
    """
    n, d = x.shape
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    tile = max(row_tile, col_tile)
    padded = -(-n // tile) * tile
    if padded != n:
        pad_x = jnp.full((padded - n, d), PAD_VALUE, dtype=x.dtype)
        x = jnp.concatenate([x, pad_x], axis=0)
        valid = jnp.concatenate(
            [valid, jnp.zeros((padded - n,), dtype=bool)], axis=0
        )
    keys = jnp.where(valid, jnp.sum(x, axis=-1), jnp.inf)
    order = jnp.argsort(keys, stable=True)
    inv = jnp.argsort(order, stable=True)
    xs = x[order]
    vs = valid[order]
    dominated = dominated_by_any_pallas(
        xs.T,
        vs,
        triangular=True,
        interpret=interpret,
        row_tile=row_tile,
        col_tile=col_tile,
        mp=mp,
    )
    keep_sorted = ~dominated & vs
    return keep_sorted[inv][:n]


@functools.partial(
    jax.jit, static_argnames=("interpret", "row_tile", "col_tile")
)
def skyline_mask_rank_pallas(
    x: jax.Array,
    valid: jax.Array | None = None,
    interpret: bool = False,
    row_tile: int = ROW_TILE,
    col_tile: int = COL_TILE,
) -> jax.Array:
    """Rank-cascade twin of ``skyline_mask_pallas``: same pad / sum-sort /
    triangular / unsort pipeline, with the pairwise pass running over
    device-computed dense ranks (``rank_transform``) instead of raw values.
    Self-contained — the compared universe is exactly ``x``'s valid rows,
    so the rank embedding is exact and the result is identical."""
    n, d = x.shape
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    tile = max(row_tile, col_tile)
    padded = -(-n // tile) * tile
    if padded != n:
        pad_x = jnp.full((padded - n, d), PAD_VALUE, dtype=x.dtype)
        x = jnp.concatenate([x, pad_x], axis=0)
        valid = jnp.concatenate(
            [valid, jnp.zeros((padded - n,), dtype=bool)], axis=0
        )
    keys = jnp.where(valid, jnp.sum(x, axis=-1), jnp.inf)
    order = jnp.argsort(keys, stable=True)
    inv = jnp.argsort(order, stable=True)
    xs = x[order]
    vs = valid[order]
    rt = rank_transform(xs, vs)
    dominated = dominated_by_any_rank_pallas(
        rt,
        vs,
        triangular=True,
        interpret=interpret,
        row_tile=row_tile,
        col_tile=col_tile,
    )
    keep_sorted = ~dominated & vs
    return keep_sorted[inv][:n]
