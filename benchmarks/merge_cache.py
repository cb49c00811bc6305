"""A/B: from-scratch full global merge vs the incremental paths the
epoch-keyed cache enables (ISSUE 3 tentpole) — exact cache hit (zero
kernel launches) and dirty-subset delta merge (``cached_global ∪ dirty
skylines`` instead of the full union).

For each (n, d) at P partitions, drives a ``PartitionSet`` directly (no
engine, so the measurement is the merge itself):

- full:  ``SKYLINE_MERGE_CACHE=0``, every trigger recomputes the union
- hit:   cache primed, repeated triggers over unchanged state
- delta: one partition dirtied per trigger (the steady-streaming shape)

Each delta result is asserted byte-identical to a cache-off full
recompute of the same state (the randomized interleaving property test
lives in tests/test_merge_cache.py). Writes
``artifacts/merge_cache_ab.json``.

A third leg A/Bs the ISSUE-4 pruned tournament-tree merge against the
flat union pass (both cache-off full merges over identical state,
byte-identity asserted; the ``full`` leg above pins
``SKYLINE_MERGE_TREE=0`` so it stays the flat baseline). Writes
``artifacts/merge_tree_ab.json``.

A fourth leg A/Bs the ISSUE-5 flush dominance cascade (quantized grid
prefilter + bf16 margin pass) on vs off over identical streams: prime
half, flush (publishes grid summaries), time the second-half flush each
way, assert the global merges byte-identical, and report the drop
fraction + flush-time delta. Writes ``artifacts/flush_prefilter_ab.json``.

Usage: python benchmarks/merge_cache.py [--repeats 5] [--sizes ...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _timed(fn, repeats: int) -> float:
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1000.0)


def bench_one(n: int, d: int, P: int, repeats: int) -> dict:
    from skyline_tpu.stream.batched import PartitionSet
    from skyline_tpu.workload.generators import anti_correlated

    rng = np.random.default_rng(0)
    x = anti_correlated(rng, n, d, 0, 10000).astype(np.float32)
    pids = rng.integers(0, P, n)
    pset = PartitionSet(P, d, buffer_size=max(n, 1024))
    for p in range(P):
        rows = np.ascontiguousarray(x[pids == p])
        if rows.shape[0]:
            pset.add_batch(p, rows, max_id=n, now_ms=0.0)
    pset.flush_all()

    # full: every trigger pays the whole union (the pre-cache behavior);
    # tree pinned OFF so this stays the flat baseline the other legs —
    # and bench_tree below — compare against
    os.environ["SKYLINE_MERGE_CACHE"] = "0"
    os.environ["SKYLINE_MERGE_TREE"] = "0"
    pset.global_merge_stats(emit_points=True)  # warm the executables
    full_ms = _timed(
        lambda: pset.global_merge_stats(emit_points=True), repeats
    )
    os.environ.pop("SKYLINE_MERGE_TREE", None)

    # hit: primed cache, unchanged state — no kernel launches at all
    os.environ["SKYLINE_MERGE_CACHE"] = "1"
    pset.global_merge_stats(emit_points=True)  # prime (counts as a miss)
    hit_ms = _timed(
        lambda: pset.global_merge_stats(emit_points=True), repeats
    )

    # delta: dirty ONE partition per trigger; the flush runs outside the
    # timed region so the number is the merge, not the top-up
    def dirty_round(measure: bool) -> float:
        pset.add_batch(
            0,
            anti_correlated(rng, 256, d, 0, 10000).astype(np.float32),
            max_id=n,
            now_ms=0.0,
        )
        pset.flush_all()
        t0 = time.perf_counter()
        res = pset.global_merge_stats(emit_points=True)
        dt = time.perf_counter() - t0
        if measure:
            os.environ["SKYLINE_MERGE_CACHE"] = "0"
            ref = pset.global_merge_stats(emit_points=True)
            os.environ["SKYLINE_MERGE_CACHE"] = "1"
            assert res[2] == ref[2], (res[2], ref[2])
            assert res[3].tobytes() == ref[3].tobytes(), (
                f"delta diverges from full recompute at n={n} d={d}"
            )
        return dt

    dirty_round(measure=False)  # warm the delta executables
    delta_ms = float(
        np.median([dirty_round(measure=True) for _ in range(repeats)]) * 1000.0
    )

    g = pset.global_merge_stats()[2]
    return {
        "n": n,
        "d": d,
        "partitions": P,
        "skyline_size": int(g),
        "full_ms": round(full_ms, 2),
        "cache_hit_ms": round(hit_ms, 3),
        "delta_ms": round(delta_ms, 2),
        "hit_speedup": round(full_ms / hit_ms, 1) if hit_ms else None,
        "delta_speedup": round(full_ms / delta_ms, 2) if delta_ms else None,
        "cache_hits": pset.merge_cache_hits,
        "cache_misses": pset.merge_cache_misses,
        "delta_merges": pset.merge_delta_merges,
    }


def bench_tree(n: int, d: int, P: int, repeats: int) -> dict:
    """Tree-vs-flat full merge over identical state, both cache-off, with
    the byte-identity assert the tree's pruning must uphold."""
    from skyline_tpu.stream.batched import PartitionSet
    from skyline_tpu.workload.generators import anti_correlated

    os.environ["SKYLINE_MERGE_CACHE"] = "0"
    rng = np.random.default_rng(1)
    x = anti_correlated(rng, n, d, 0, 10000).astype(np.float32)
    pids = rng.integers(0, P, n)
    pset = PartitionSet(P, d, buffer_size=max(n, 1024))
    for p in range(P):
        rows = np.ascontiguousarray(x[pids == p])
        if rows.shape[0]:
            pset.add_batch(p, rows, max_id=n, now_ms=0.0)
    pset.flush_all()

    os.environ["SKYLINE_MERGE_TREE"] = "0"
    flat_ref = pset.global_merge_stats(emit_points=True)  # warm
    flat_ms = _timed(
        lambda: pset.global_merge_stats(emit_points=True), repeats
    )

    os.environ["SKYLINE_MERGE_TREE"] = "1"
    tree_res = pset.global_merge_stats(emit_points=True)  # warm
    assert tree_res[2] == flat_ref[2], (tree_res[2], flat_ref[2])
    assert tree_res[3].tobytes() == flat_ref[3].tobytes(), (
        f"tree diverges from flat merge at n={n} d={d}"
    )
    tree_ms = _timed(
        lambda: pset.global_merge_stats(emit_points=True), repeats
    )
    info = pset.last_tree_info or {}
    return {
        "n": n,
        "d": d,
        "partitions": P,
        "skyline_size": int(flat_ref[2]),
        "flat_full_ms": round(flat_ms, 2),
        "tree_full_ms": round(tree_ms, 2),
        "tree_speedup": round(flat_ms / tree_ms, 2) if tree_ms else None,
        "levels": info.get("levels"),
        "pruned_fraction": info.get("pruned_fraction"),
        "candidates_per_level": info.get("candidates_per_level"),
    }


def bench_prefilter(n: int, d: int, P: int, repeats: int) -> dict:
    """Flush-cascade A/B (ISSUE-5 tentpole): grid prefilter + bf16 margin
    pass on vs off over identical streams, byte-identical global merges
    asserted. Primes half the stream (the first flush publishes the grid
    summaries at its tail), then times the second-half flush — the shape
    where the prefilter can actually drop rows before the merge kernels."""
    from skyline_tpu.stream.batched import PartitionSet
    from skyline_tpu.workload.generators import anti_correlated

    def one_run(on: bool):
        v = "1" if on else "0"
        os.environ["SKYLINE_FLUSH_PREFILTER"] = v
        os.environ["SKYLINE_MIXED_PRECISION"] = v
        rng = np.random.default_rng(2)
        x = anti_correlated(rng, n, d, 0, 10000).astype(np.float32)
        pids = rng.integers(0, P, n)
        pset = PartitionSet(P, d, buffer_size=max(n, 1024))
        half = n // 2

        def feed(lo, hi):
            for p in range(P):
                rows = np.ascontiguousarray(x[lo:hi][pids[lo:hi] == p])
                if rows.shape[0]:
                    pset.add_batch(p, rows, max_id=n, now_ms=0.0)

        feed(0, half)
        pset.flush_all()
        feed(half, n)
        t0 = time.perf_counter()
        pset.flush_all()
        dt = (time.perf_counter() - t0) * 1000.0
        return pset, dt

    def leg(on: bool):
        # fresh same-seed pset per repeat: a flush is one-shot, so the
        # timed region can't be replayed in place; first run warms the
        # executables and is discarded
        times, pset = [], None
        for i in range(repeats + 1):
            pset, dt = one_run(on)
            if i > 0:
                times.append(dt)
        return pset, float(np.median(times))

    pset_off, off_ms = leg(on=False)
    ref = pset_off.global_merge_stats(emit_points=True)
    pset_on, on_ms = leg(on=True)
    res = pset_on.global_merge_stats(emit_points=True)
    assert res[2] == ref[2], (res[2], ref[2])
    assert res[3].tobytes() == ref[3].tobytes(), (
        f"prefilter cascade diverges from exact path at n={n} d={d}"
    )
    cs = pset_on.flush_cascade_stats()
    return {
        "n": n,
        "d": d,
        "partitions": P,
        "skyline_size": int(ref[2]),
        "off_flush_ms": round(off_ms, 2),
        "on_flush_ms": round(on_ms, 2),
        "flush_speedup": round(off_ms / on_ms, 2) if on_ms else None,
        "prefilter_drop_fraction": round(cs["prefilter_drop_fraction"], 4),
        "prefilter_dropped": cs["prefilter_dropped"],
        "bf16_resolved": cs["bf16_resolved"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--sizes", type=int, nargs="+", default=[65536, 262144])
    ap.add_argument("--dims", type=int, nargs="+", default=[8])
    ap.add_argument("--partitions", type=int, default=8)
    ap.add_argument("--out", default="artifacts/merge_cache_ab.json")
    ap.add_argument("--tree-out", default="artifacts/merge_tree_ab.json")
    ap.add_argument(
        "--prefilter-out", default="artifacts/flush_prefilter_ab.json"
    )
    a = ap.parse_args(argv)

    import jax

    prev = {  # lint: allow-raw-env (save/restore snapshot by name)
        k: os.environ.get(k)  # lint: allow-raw-env
        for k in (
            "SKYLINE_MERGE_CACHE",
            "SKYLINE_MERGE_TREE",
            "SKYLINE_FLUSH_PREFILTER",
            "SKYLINE_MIXED_PRECISION",
        )
    }
    results = {
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "rows": [],
    }
    tree_results = {
        "backend": results["backend"],
        "device": results["device"],
        "rows": [],
    }
    prefilter_results = {
        "backend": results["backend"],
        "device": results["device"],
        "rows": [],
    }
    try:
        for n in a.sizes:
            for d in a.dims:
                row = bench_one(n, d, a.partitions, a.repeats)
                print(json.dumps(row), flush=True)
                results["rows"].append(row)
                trow = bench_tree(n, d, a.partitions, a.repeats)
                print(json.dumps(trow), flush=True)
                tree_results["rows"].append(trow)
                prow = bench_prefilter(n, d, a.partitions, a.repeats)
                print(json.dumps(prow), flush=True)
                prefilter_results["rows"].append(prow)
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(results, f, indent=1)
    if a.tree_out:
        os.makedirs(os.path.dirname(a.tree_out) or ".", exist_ok=True)
        with open(a.tree_out, "w") as f:
            json.dump(tree_results, f, indent=1)
    if a.prefilter_out:
        os.makedirs(os.path.dirname(a.prefilter_out) or ".", exist_ok=True)
        with open(a.prefilter_out, "w") as f:
            json.dump(prefilter_results, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
