"""A/B: f32 min/max value cascade vs dense-rank cascade for the dominance
pass (VERDICT r3 item 3 — re-evaluated with DEVICE-side ranking, which voids
the round-3 rejection grounds of host-rank cost + rank transfer).

Measures, at the self-skyline shape the global union pass runs
(sum-sorted, triangular), for d in {8, 16} at N=262144 and N=524288
(the north-star union bucket):

- value: ``skyline_mask_pallas``  (3 ops/dim cascade)
- rank:  ``skyline_mask_rank_pallas``  (2 ops/dim + rank-sum compare,
  including the on-device rank_transform overhead)

Asserts both produce identical masks, reports medians over repeats, and
writes ``artifacts/rank_cascade_ab.json``.

Usage: python benchmarks/rank_cascade.py [--repeats 5] [--out ...]
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


def bench_one(n: int, d: int, repeats: int, interpret: bool = False) -> dict:
    import functools

    import jax.numpy as jnp

    from skyline_tpu.ops.pallas_dominance import (
        skyline_mask_pallas as _mask_value,
        skyline_mask_rank_pallas as _mask_rank,
    )

    # --interpret: emulated Pallas for off-TPU smoke runs of this harness
    # (orders of magnitude slower — timings are then meaningless)
    skyline_mask_pallas = functools.partial(_mask_value, interpret=interpret)
    skyline_mask_rank_pallas = functools.partial(_mask_rank, interpret=interpret)

    rng = np.random.default_rng(0)
    base = rng.uniform(0, 10000, (n, 1))
    x = np.abs((10000 - base) + rng.normal(0, 500, (n, d))).astype(np.float32)
    xd = jnp.asarray(x)
    valid = jnp.ones((n,), dtype=bool)

    # warm + correctness
    mv = np.asarray(skyline_mask_pallas(xd, valid))
    mr = np.asarray(skyline_mask_rank_pallas(xd, valid))
    assert (mv == mr).all(), (
        f"rank cascade diverges at n={n} d={d}: "
        f"{int(mv.sum())} vs {int(mr.sum())} survivors"
    )

    def timed(fn):
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            np.asarray(fn(xd, valid))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts) * 1000.0)

    tv = timed(skyline_mask_pallas)
    tr = timed(skyline_mask_rank_pallas)
    return {
        "n": n,
        "d": d,
        "skyline_size": int(mv.sum()),
        "value_ms": round(tv, 1),
        "rank_ms": round(tr, 1),
        "speedup": round(tv / tr, 3),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--sizes", type=int, nargs="+", default=[262144, 524288])
    ap.add_argument("--dims", type=int, nargs="+", default=[8, 16])
    ap.add_argument("--out", default="artifacts/rank_cascade_ab.json")
    ap.add_argument("--interpret", action="store_true",
                    help="emulated Pallas (CPU smoke runs; timings "
                         "meaningless, correctness assert still real)")
    a = ap.parse_args(argv)

    import jax

    results = {
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "rows": [],
    }
    for n in a.sizes:
        for d in a.dims:
            row = bench_one(n, d, a.repeats, interpret=a.interpret)
            print(json.dumps(row), flush=True)
            results["rows"].append(row)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
