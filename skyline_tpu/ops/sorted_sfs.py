"""Sorted-order SFS dominance cascade for d > 2 — the host sibling of the
device SFS kernels (ISSUE 11).

``flush/merge_kernel`` was ~98% of a profiled 8-D anti-correlated window,
and the profile decomposes into exactly two cost modes (measured on the
8-D anti-correlated mr-angle stream):

- the **duplicate-heavy partition**: the reference's 8-D anti-correlated
  generator clips ~44% of rows to the all-zero origin (negative sum
  targets truncate to 0), mr-angle routes every one of them to partition
  0, and duplicates never dominate each other — so 57k identical rows
  all survive while the dense SFS pays ~N²/2 pairwise work to discover
  zero dominations (5.95s of the 8.4s local flush);
- the **tiny-skyline heavy partition**: 63k spread-sum rows collapse to
  a 17-row skyline, but the block self-prune pays B² per block and the
  buffer pass never exploits that victims die against the first few
  strong dominators (1.59s).

The sorted cascade kills both modes exactly:

1. **dedup** — group byte-identical tuples (after normalizing -0.0 to
   +0.0 so numeric equality and byte equality coincide); every copy of a
   unique tuple shares one dominance verdict, so the all-zero partition
   collapses to a single candidate. After dedup, distinct rows that
   compare ``all(<=)`` are automatically strict somewhere, so the scan
   needs only one comparison per dimension per pair.
2. **sum-sorted scan** — sort unique tuples by their float64 row sum
   ascending (fixed-order rounding is monotone, so a dominator's key is
   <= its victim's key; ties are possible and are exactly the "ambiguous
   band") and stream them in blocks against a compact survivor buffer
   that only ever grows. Buffer chunks are visited smallest-sum-first —
   the strongest dominators — and dead victims are compressed out after
   every chunk, so a tiny-skyline stream does ~N·S work instead of
   N²/2.
3. **in-block pairwise tiles** — each block is closed with one exact
   dense pass over its own buffer-surviving rows. Because blocks are
   contiguous in sum order, every equal/near-sum ambiguous pair lands
   either in one block (caught here) or across blocks (caught by the
   full per-pair check of the buffer pass), with no epsilon to tune:
   soundness needs only "a dominator never sorts after its victim's
   block", which the monotone key guarantees.

Semantics are exactly ``ops.dominance.skyline_mask``: minimization,
``all(<=) & any(<)``, duplicates all survive, NaN rows neither dominate
nor are dominated (they always survive), +inf rows are dominance-neutral
dominators, invalid rows are excluded both as dominators and survivors.
Rows mixing +inf and -inf have a NaN sum — no usable sort key — and take
a tiny exact pairwise detour instead.

Everything here is selection-only host NumPy: no arithmetic ever touches
the returned rows, so byte-identity with the device kernels follows from
mask equality (asserted across the kind × d × N grid by
``tests/test_sorted_sfs.py``).

This path cannot run inside jit (it is host code; the jaxpr audit
asserts it never leaks into a trace) — ``dispatch.skyline_mask_auto``
only routes concrete non-TPU arrays here, and ``stream/batched.py``'s
lazy flush picks it per (d, N, backend) signature from measured
KernelProfiler wall data.
"""

from __future__ import annotations

import numpy as np

from skyline_tpu.analysis.registry import env_int

__all__ = [
    "sorted_skyline_mask_np",
    "sorted_sfs_keep",
    "sorted_sfs_block",
]


def sorted_sfs_block() -> int:
    """``SKYLINE_SORTED_SFS_BLOCK``: max scan-block width (rows per
    in-block exact tile). Blocks start at 1024 and double up to this cap
    — bigger blocks amortize the buffer pass, smaller ones keep the
    B×B in-block tile cheap when everything survives. The default is the
    flush buffer size that measured best on the bench grid."""
    return max(64, env_int("SKYLINE_SORTED_SFS_BLOCK", 8192))


# buffer chunk width for the strongest-first compression pass; fixed —
# small enough that the (chunk × alive) tile stays cache-resident, big
# enough that the per-chunk python overhead is noise
_CHUNK = 1024


def _dominated_any(dominators: np.ndarray, victims: np.ndarray) -> np.ndarray:
    """(m,) bool: victim j is dominated by SOME dominator row.

    Caller guarantees dominators and victims are distinct-as-tuples
    normalized rows (post-dedup, -0.0 folded into +0.0), so ``all(<=)``
    between different rows implies strictness and one comparison per
    dimension suffices. The per-dimension accumulate with an early bail
    keeps the peak intermediate at one (n, m) bool tile."""
    le = dominators[:, 0:1] <= victims[None, :, 0]
    for k in range(1, dominators.shape[1]):
        if not le.any():
            break
        le &= dominators[:, k : k + 1] <= victims[None, :, k]
    return le.any(axis=0)


def _self_prune(rows: np.ndarray) -> np.ndarray:
    """(b,) bool keep-mask of one block against itself (exact dense tile;
    rows are distinct normalized tuples, see ``_dominated_any``)."""
    b = rows.shape[0]
    if b <= 1:
        return np.ones(b, bool)
    le = rows[:, 0:1] <= rows[None, :, 0]
    for k in range(1, rows.shape[1]):
        le &= rows[:, k : k + 1] <= rows[None, :, k]
    np.fill_diagonal(le, False)
    return ~le.any(axis=0)


def _scan_unique(uniq: np.ndarray) -> np.ndarray:
    """Keep-mask over distinct normalized tuples — the sorted-order SFS
    scan itself (steps 2 and 3 of the module docstring)."""
    m, _ = uniq.shape
    keep = np.zeros(m, bool)
    with np.errstate(invalid="ignore"):
        s = uniq.astype(np.float64).sum(axis=1)
    special = np.isnan(s)  # mixed ±inf rows: no usable sort key
    core = np.flatnonzero(~special)
    order = np.argsort(s[core], kind="stable")
    core = core[order]
    U = uniq[core]
    k = core.size

    buf: list[np.ndarray] = []  # survivor arrays, ascending-sum order
    B_max = sorted_sfs_block()
    B = min(1024, B_max)
    i = 0
    while i < k:
        blk = U[i : i + B]
        pos = np.arange(i, min(i + B, k))
        alive = np.ones(blk.shape[0], bool)
        # buffer pass: strongest (smallest-sum) chunks first, victims
        # compressed out as soon as anything kills them
        for barr in buf:
            for j in range(0, barr.shape[0], _CHUNK):
                if not alive.any():
                    break
                ai = np.flatnonzero(alive)
                dead = _dominated_any(barr[j : j + _CHUNK], blk[ai])
                if dead.any():
                    alive[ai[dead]] = False
            if not alive.any():
                break
        # in-block exact tile: the ambiguous equal/near-sum band
        if alive.any():
            ai = np.flatnonzero(alive)
            alive[ai[~_self_prune(blk[ai])]] = False
        if alive.any():
            buf.append(blk[alive])
            keep[core[pos[alive]]] = True
        i += B
        B = min(B * 2, B_max)

    if special.any():
        # NaN-sum rows: exact pairwise both ways against everything.
        # These rows are vanishingly rare (a row must mix +inf and -inf),
        # so the dense detour is O(|special| * m).
        spec_idx = np.flatnonzero(special)
        for si in spec_idx:
            row = uniq[si]
            others = np.delete(np.arange(m), si)
            if not _dominated_any(uniq[others], row[None, :]).any():
                keep[si] = True
        # ...and as dominators over the core survivors
        surv = np.flatnonzero(keep & ~special)
        if surv.size:
            dead = _dominated_any(uniq[spec_idx], uniq[surv])
            keep[surv[dead]] = False
    return keep


def sorted_skyline_mask_np(x, valid=None) -> np.ndarray:
    """Exact survivor mask of an (n, d) host array — byte-for-byte the
    same mask ``ops.dominance.skyline_mask`` computes, via the sorted
    cascade (see module docstring). Returns an (n,) numpy bool array."""
    x = np.asarray(x, dtype=np.float32)
    n = x.shape[0]
    out = np.zeros(n, bool)
    if n == 0:
        return out
    if valid is None:
        vidx = np.arange(n)
    else:
        vidx = np.flatnonzero(np.asarray(valid))
        if vidx.size == 0:
            return out
    xv = x[vidx]
    # NaN rows: never dominate, never dominated -> always survive
    nanrow = np.isnan(xv).any(axis=1)
    if nanrow.any():
        out[vidx[nanrow]] = True
        xv = xv[~nanrow]
        vidx = vidx[~nanrow]
        if vidx.size == 0:
            return out
    # fold -0.0 into +0.0 so byte dedup equals numeric dedup (the only
    # IEEE pair of distinct bit patterns that compare numerically equal);
    # selection-only: the fold never reaches the caller's rows
    xv = xv + np.float32(0.0)
    uniq, inv = np.unique(xv, axis=0, return_inverse=True)
    if uniq.shape[0] == 1:
        out[vidx] = True  # all duplicates of one tuple: everything lives
        return out
    out[vidx] = _scan_unique(uniq)[inv.reshape(-1)]
    return out


def sorted_sfs_keep(rows: np.ndarray, old: np.ndarray | None = None) -> np.ndarray:
    """Flush helper: keep-mask over ``rows`` of the survivors of
    ``old ∪ rows`` restricted to ``rows`` — exactly the set the device
    SFS rounds append (new-window rows not dominated by the resident
    skyline or by any other new row; old rows dominated by new ones are
    later removed by ``sfs_cleanup``, same as the device path)."""
    rows = np.asarray(rows, dtype=np.float32)
    if old is None or old.shape[0] == 0:
        return sorted_skyline_mask_np(rows)
    old = np.asarray(old, dtype=np.float32)
    union = np.concatenate([old, rows], axis=0)
    return sorted_skyline_mask_np(union)[old.shape[0] :]
