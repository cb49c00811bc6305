"""Closed-loop benchmark runner for the BASELINE.json configs.

Runs each of the five scored configurations end-to-end through the streaming
engine (and the sliding-window processor for config #4), printing one JSON
line per config and writing a collector-schema CSV per config under
``--outdir`` so the plot tools work on the results directly.

Sizes default to a quick pass (``--scale 1`` = full BASELINE sizes; the
default ``--scale 0.1`` runs 10x smaller for smoke runs).

Usage: python benchmarks/run_configs.py [--scale 0.1] [--outdir bench_out]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks._common import CHUNK, one_window
from skyline_tpu.metrics.collector import append_result_row
from skyline_tpu.stream import EngineConfig
from skyline_tpu.stream.sliding_engine import SlidingEngine
from skyline_tpu.workload.generators import generate

CONFIGS = [
    # (name, distribution, dims, algo, window_n at scale 1)
    ("2d_correlated_grid_tumbling", "correlated", 2, "mr-grid", 1_000_000),
    ("4d_uniform_dim", "uniform", 4, "mr-dim", 1_000_000),
    ("8d_uniform_dim", "uniform", 8, "mr-dim", 1_000_000),
    ("8d_anticorrelated_angle", "anti_correlated", 8, "mr-angle", 1_000_000),
    ("qos_4d_10m", "qos", 4, "mr-angle", 10_000_000),
]
SLIDING_CONFIG = ("sliding_4d_anticorrelated", "anti_correlated", 4, 200_000, 50_000)


def run_tumbling(name, dist, dims, algo, n, outdir, policy="lazy",
                 warmup=True):
    rng = np.random.default_rng(0)
    cfg = EngineConfig(parallelism=4, algo=algo, dims=dims, domain_max=10000.0,
                       buffer_size=8192, flush_policy=policy)
    x = generate(dist, rng, n, dims, 0, 10000)
    ids = np.arange(n, dtype=np.int64)
    # warmup window (same data -> identical shape-bucket sequence): measured
    # windows then reflect steady-state streaming, not XLA compile latency —
    # the same methodology as bench.py's warmup window
    warm_s = 0.0
    if warmup:
        warm_s, _ = one_window(cfg, ids, x)
    dt, r = one_window(cfg, ids, x)
    append_result_row(os.path.join(outdir, f"{name}.csv"),
                      {**r, "record_count": n})
    return {
        "config": name,
        "n": n,
        "dims": dims,
        "algo": algo,
        "tuples_per_sec": round(n / dt, 1),
        "window_s": round(dt, 2),
        "warmup_window_s": round(warm_s, 2),
        "skyline_size": r["skyline_size"],
        "optimality": r["optimality"],
    }


def _one_sliding_run(cfg, window, slide, ids, x):
    """One full sliding stream through a fresh SlidingEngine; returns
    (wall_s, per-slide results)."""
    eng = SlidingEngine(cfg, window_size=window, slide=slide,
                        emit_per_slide=True)
    n = x.shape[0]
    t0 = time.perf_counter()
    results = []
    for i in range(0, n, CHUNK):
        eng.process_records(ids[i : i + CHUNK], x[i : i + CHUNK])
        results.extend(eng.poll_results())
    return time.perf_counter() - t0, results


def run_sliding(name, dist, dims, window, slide, outdir, warmup=True):
    """Sliding config through the first-class SlidingEngine (worker-grade
    path: routing, bucket rings, per-slide results, collector CSV)."""
    rng = np.random.default_rng(0)
    cfg = EngineConfig(parallelism=4, algo="mr-angle", dims=dims,
                      domain_max=10000.0)
    n = window * 4  # several full-overlap slides
    x = generate(dist, rng, n, dims, 0, 10000)
    ids = np.arange(n, dtype=np.int64)
    warm_s = 0.0
    if warmup:
        warm_s, _ = _one_sliding_run(cfg, window, slide, ids, x)
    dt, results = _one_sliding_run(cfg, window, slide, ids, x)
    for r in results:
        append_result_row(os.path.join(outdir, f"{name}.csv"), r)
    sizes = [r["skyline_size"] for r in results if r["window_filled"]]
    return {
        "config": name,
        "n": n,
        "dims": dims,
        "window": window,
        "slide": slide,
        "tuples_per_sec": round(n / dt, 1),
        "stream_s": round(dt, 2),
        "warmup_stream_s": round(warm_s, 2),
        "slides": len(results),
        "skyline_size_median": int(np.median(sizes)) if sizes else 0,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--outdir", default="bench_out")
    ap.add_argument("--only", help="substring filter on config names")
    ap.add_argument("--policy", choices=("incremental", "lazy"),
                    default="lazy",
                    help="tumbling-config flush policy (lazy = SFS at query)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the unmeasured warmup pass per config "
                         "(measured numbers then include XLA compiles)")
    a = ap.parse_args(argv)
    from skyline_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    os.makedirs(a.outdir, exist_ok=True)
    failures = 0
    for name, dist, dims, algo, n in CONFIGS:
        if a.only and a.only not in name:
            continue
        # one config's crash must
        # not cost the rest of the matrix: record it, keep going, exit 1
        try:
            out = run_tumbling(name, dist, dims, algo,
                               max(10_000, int(n * a.scale)),
                               a.outdir, policy=a.policy,
                               warmup=not a.no_warmup)
        except Exception as e:  # noqa: BLE001
            out = {"config": name, "error": f"{type(e).__name__}: {e}"[:400]}
            failures += 1
        print(json.dumps(out), flush=True)
    name, dist, dims, window, slide = SLIDING_CONFIG
    if not a.only or a.only in name:
        # derive slide first and keep window an exact multiple of it
        # (SlidingSkyline requires window_size % slide == 0 at any --scale)
        k = window // slide
        s = max(2_500, int(slide * a.scale))
        try:
            out = run_sliding(name, dist, dims, k * s, s, a.outdir,
                              warmup=not a.no_warmup)
        except Exception as e:  # noqa: BLE001
            out = {"config": name, "error": f"{type(e).__name__}: {e}"[:400]}
            failures += 1
        print(json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
