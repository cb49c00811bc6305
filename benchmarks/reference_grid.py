"""The reference's experiment suite, reproduced: 3 algos x 2D/3D/4D x 1M
anti-correlated windows (graph_paper_figures.py:28-42; pdf §5) through this
engine, then the ours-vs-reference overlay figures.

Each cell runs one tumbling window end-to-end in-process (same path as
bench.py: routing -> local skylines -> barrier -> global merge), writes a
collector-schema CSV per cell, prints one JSON line per cell, and finally
renders the two overlay PNGs via plots/paper_figures.py --ours.

Usage:
  python benchmarks/reference_grid.py [--n 1000000] [--outdir bench_out]
      [--figdir artifacts] [--policy lazy]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks._common import one_window
from skyline_tpu.metrics.collector import append_result_row
from skyline_tpu.stream import EngineConfig
from skyline_tpu.workload.generators import anti_correlated

ALGOS = ["mr-dim", "mr-grid", "mr-angle"]
DIMS = [2, 3, 4]


def run_cell(algo: str, dims: int, n: int, policy: str, outdir: str,
             warmup: bool = True) -> dict:
    rng = np.random.default_rng(0)
    cfg = EngineConfig(parallelism=4, algo=algo, dims=dims, domain_max=10000.0,
                       buffer_size=8192, flush_policy=policy)
    x = anti_correlated(rng, n, dims, 0, 10000)
    ids = np.arange(n, dtype=np.int64)
    # unmeasured warmup window on the same data (same shape buckets) so the
    # measured cell reflects steady-state streaming, not XLA compiles —
    # bench.py's methodology; the reference's numbers are likewise from a
    # long-lived warmed JVM job
    warm_s = 0.0
    if warmup:
        warm_s, _ = one_window(cfg, ids, x)
    dt, r = one_window(cfg, ids, x)
    csv_path = os.path.join(outdir, f"grid_{algo}_{dims}d.csv")
    if os.path.isfile(csv_path):
        os.remove(csv_path)
    append_result_row(csv_path, {**r, "record_count": n})
    return {
        "config": f"grid_{algo}_{dims}d",
        "n": n,
        "algo": algo,
        "dims": dims,
        "window_s": round(dt, 2),
        "warmup_window_s": round(warm_s, 2),
        "tuples_per_sec": round(n / dt, 1),
        "total_ms_reported": r["total_processing_time_ms"],
        "skyline_size": r["skyline_size"],
        "optimality": round(r["optimality"], 4),
        "csv": csv_path,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--outdir", default="bench_out")
    ap.add_argument("--figdir", default="artifacts")
    ap.add_argument("--policy", choices=("incremental", "lazy"), default="lazy")
    ap.add_argument("--skip-figures", action="store_true")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the unmeasured warmup window per cell")
    a = ap.parse_args(argv)

    import jax

    from skyline_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # every cell runs in this one process: a chip belongs to one process
    os.makedirs(a.outdir, exist_ok=True)
    results = []
    for dims in DIMS:
        for algo in ALGOS:
            out = run_cell(algo, dims, a.n, a.policy, a.outdir,
                           warmup=not a.no_warmup)
            print(json.dumps(out), flush=True)
            results.append(out)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    grid_json = os.path.join(a.figdir, "reference_grid.json")
    os.makedirs(a.figdir, exist_ok=True)
    with open(grid_json, "w") as f:
        json.dump({"device": device, "results": results}, f, indent=1)

    if not a.skip_figures:
        from skyline_tpu.plots.paper_figures import main as fig_main

        ours = [f"{r['dims']}:{r['algo']}={r['csv']}" for r in results]
        fig_main(["--ours", *ours,
                  "--prefix", os.path.join(a.figdir, "ours_vs_reference_")])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
