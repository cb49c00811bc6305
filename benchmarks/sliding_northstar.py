"""Sliding-window north star: 8-D anti-correlated, 1M-tuple window,
slide = window/8 — the flagship evidence for the first-class sliding mode
(VERDICT r3 item 7; the reference has no eviction at all, so there is no
reference number to beat — this artifact pins OUR sustained rate).

Drives ``SlidingEngine`` directly (no transport): streams slide-sized
chunks, triggers a query at every slide close (the continuous-monitoring
usage the mode exists for), and reports per-slide wall latencies once the
window is full, p50/p90, sustained slides/s and tuples/s.

Writes ``artifacts/sliding_northstar.json``.

Usage:
  python benchmarks/sliding_northstar.py [--window 1048576] [--slides 12]
      [--dims 8] [--cpu-scale]  (--cpu-scale shrinks to 65536/8 for CI)
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--window", type=int, default=1_048_576)
    ap.add_argument("--k", type=int, default=8, help="slides per window")
    ap.add_argument("--slides", type=int, default=12,
                    help="measured slides after the window fills")
    ap.add_argument("--dims", type=int, default=8)
    ap.add_argument("--algo", default="mr-angle")
    ap.add_argument("--cpu-scale", action="store_true",
                    help="shrink to a CI-sized config on CPU")
    ap.add_argument("--out", default="artifacts/sliding_northstar.json")
    a = ap.parse_args(argv)
    if a.cpu_scale:
        a.window, a.slides = 65536, 4

    from skyline_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from skyline_tpu.stream.engine import EngineConfig
    from skyline_tpu.stream.sliding_engine import SlidingEngine
    from skyline_tpu.workload.generators import anti_correlated

    slide = a.window // a.k
    cfg = EngineConfig(
        parallelism=4, algo=a.algo, dims=a.dims, domain_max=10000.0
    )
    eng = SlidingEngine(cfg, window_size=a.window, slide=slide)
    rng = np.random.default_rng(0)
    next_id = 0
    # shared telemetry Histogram: exact order-statistic quantiles at this
    # sample count, same percentile machinery as bench.py and /stats
    from skyline_tpu.telemetry import Histogram

    lat_hist = Histogram("slide_latency_s", unit="s")
    sky_sizes: list[int] = []
    warm = a.k  # slides that fill the window (not measured)
    for s in range(a.k + a.slides):
        x = anti_correlated(rng, slide, a.dims, 0, 10000)
        ids = np.arange(next_id, next_id + slide, dtype=np.int64)
        next_id += slide
        t0 = time.perf_counter()
        eng.process_records(ids, x)
        eng.process_trigger(f"{s},0")
        (res,) = eng.poll_results()
        dt = time.perf_counter() - t0
        if s >= warm:
            lat_hist.observe(dt)
            sky_sizes.append(res["skyline_size"])
        print(
            json.dumps(
                {
                    "slide": s,
                    "window_filled": res.get("window_filled"),
                    "skyline_size": res["skyline_size"],
                    "latency_s": round(dt, 3),
                    "measured": s >= warm,
                }
            ),
            flush=True,
        )
    p50 = lat_hist.quantile(0.5)
    p90 = lat_hist.quantile(0.9)
    out = {
        "config": (
            f"sliding_{a.dims}d_anticorrelated_w{a.window}_s{slide}"
        ),
        "backend": jax.default_backend(),
        "window": a.window,
        "slide": slide,
        "dims": a.dims,
        "algo": a.algo,
        "slides_measured": lat_hist.count,
        "per_slide_p50_s": round(p50, 3),
        "per_slide_p90_s": round(p90, 3),
        "sustained_slides_per_s": round(1.0 / p50, 3),
        "sustained_tuples_per_s": round(slide / p50, 1),
        "skyline_size_p50": int(np.median(sky_sizes)),
    }
    print(json.dumps(out), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
