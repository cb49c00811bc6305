"""A/B: fleet + workload observability overhead (ISSUE 13) — the per-chip
telemetry and the streaming characterizer must be free on the jitted path
and near-free off it.

Three legs, one process:

- e2e:      identical streams driven through a 2-chip ``ShardedEngine``
  with SKYLINE_FLEET/SKYLINE_WORKLOAD both off vs both on — skyline
  byte-identity asserted for EVERY trigger (the planes are host-side
  bookkeeping only; nothing may enter a jitted computation), and the
  wall delta is the planes' tax, which must stay within run-to-run
  noise.
- observe:  the characterizer's per-batch ingest cost at its real call
  rate (one stride-sampled fold per micro-batch, epoch closes included).
- note:     the fleet accumulators' per-event cost (ingest/flush/level-1/
  level-2 notes plus the per-merge imbalance roll-up) — what each
  tournament pays with the plane on.

Writes ``artifacts/fleet_ab.json``.

Usage: python benchmarks/fleet.py [--n 20000] [--d 4] [--repeats 3]
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

def _drive(rows, d: int, planes_on: bool):
    """One stream -> two triggers (cold tournament, facade cache hit)
    through a 2-chip sharded engine; returns (wall_s, per-trigger skyline
    bytes, stats). Knobs flip via env BEFORE engine construction (read at
    ctor); the telemetry hub is present in BOTH legs so the delta
    isolates the fleet/workload planes, not the whole observability
    stack."""
    from skyline_tpu.distributed import ShardedEngine
    from skyline_tpu.stream import EngineConfig
    from skyline_tpu.telemetry import Telemetry

    os.environ["SKYLINE_FLEET"] = "1" if planes_on else "0"
    os.environ["SKYLINE_WORKLOAD"] = "1" if planes_on else "0"
    # the characterizer stride-samples each micro-batch to its cap, so at
    # the default 4096-sampled-row epoch a 20k-row window never closes an
    # epoch; shrink it so the artifact carries a real classification
    os.environ["SKYLINE_WORKLOAD_EPOCH_ROWS"] = "1024"
    eng = ShardedEngine(
        EngineConfig(parallelism=2, dims=d, domain_max=10000.0,
                     buffer_size=4096, emit_skyline_points=True),
        chips=2,
        telemetry=Telemetry(),
    )
    n = rows.shape[0]
    ids = np.arange(n, dtype=np.int64)
    answers = []
    t0 = time.perf_counter()
    chunk = 1024
    for i in range(0, n, chunk):
        eng.process_records(ids[i : i + chunk], rows[i : i + chunk])
    for trigger in ("cold,0", "hit,0"):
        eng.process_trigger(trigger)
        (result,) = eng.poll_results()
        pts = np.asarray(result["skyline_points"], dtype=np.float32)
        answers.append((int(result["skyline_size"]), pts.tobytes()))
    dt = time.perf_counter() - t0
    return dt, answers, eng.stats()


def bench_e2e(n: int, d: int, repeats: int) -> dict:
    from skyline_tpu.workload.generators import anti_correlated

    rng = np.random.default_rng(0)
    rows = anti_correlated(rng, n, d, 0, 10000)
    off_s, on_s = [], []
    fleet_block, workload_block = {}, {}
    for _ in range(repeats + 1):  # first round warms the executables
        off_dt, off_answers, off_st = _drive(rows, d, planes_on=False)
        on_dt, on_answers, st = _drive(rows, d, planes_on=True)
        # acceptance: byte-identical skylines with the planes on and off,
        # for both the cold tournament and the cache-hit path
        assert on_answers == off_answers, "fleet/workload changed the skyline"
        assert "workload" not in off_st and "fleet" not in off_st.get(
            "sharded", {}
        ), "gated-off engine still carries the planes"
        off_s.append(off_dt)
        on_s.append(on_dt)
        fleet_block = st["sharded"].get("fleet", {})
        workload_block = st.get("workload", {})
    off_ms = float(np.median(off_s[1:]) * 1000.0)
    on_ms = float(np.median(on_s[1:]) * 1000.0)
    return {
        "n": n,
        "d": d,
        "chips": 2,
        "triggers": 2,
        "off_ms": round(off_ms, 1),
        "on_ms": round(on_ms, 1),
        "overhead_pct": round((on_ms / off_ms - 1.0) * 100.0, 1),
        "byte_identical": True,
        "imbalance_index": fleet_block.get("imbalance_index"),
        "interconnect_rows_total": fleet_block.get("interconnect_rows_total"),
        "workload_kind": workload_block.get("kind"),
        "workload_epochs": workload_block.get("epochs_closed"),
    }


def bench_observe(batches: int = 2_000, d: int = 8) -> dict:
    """The characterizer's ingest-side cost at its real call rate: one
    4096-row micro-batch per call (stride-sampled to ``sample_cap``
    inside), epoch closes amortized in."""
    from skyline_tpu.telemetry.workload import WorkloadCharacterizer

    rng = np.random.default_rng(1)
    batch = rng.random((4096, d)).astype(np.float32) * 1000.0
    w = WorkloadCharacterizer(d)
    t0 = time.perf_counter()
    for _ in range(batches):
        w.observe(batch)
    per_batch_us = (time.perf_counter() - t0) / batches * 1e6
    st = w.stats()
    return {
        "batches": batches,
        "batch_rows": 4096,
        "us_per_batch": round(per_batch_us, 2),
        "epochs_closed": st["epochs_closed"],
        "rows_sampled": st["rows_sampled"],
    }


def bench_note(merges: int = 10_000, chips: int = 4) -> dict:
    """The fleet accumulators at tournament rate: per merge, one ingest +
    one flush + one level-1 note per chip, a level-2 outcome per chip,
    and the imbalance roll-up."""
    from skyline_tpu.telemetry.fleet import FleetStats

    f = FleetStats(chips)
    t0 = time.perf_counter()
    for i in range(merges):
        for c in range(chips):
            f.note_ingest(c, 4096)
            f.note_flush(c, 4096, 1.5)
            f.note_level1(c, 512, 2.0)
            f.note_level2(c, pruned=(c == chips - 1), crossed_rows=512)
        f.note_merge_done()
    per_merge_us = (time.perf_counter() - t0) / merges * 1e6
    return {
        "merges": merges,
        "chips": chips,
        "us_per_merge": round(per_merge_us, 2),
        "doc_bytes": len(json.dumps(f.doc()).encode()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fleet/workload plane overhead A/B"
    )
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--d", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument(
        "--out", default=os.path.join(REPO, "artifacts", "fleet_ab.json")
    )
    a = ap.parse_args(argv)

    result = {
        "e2e": bench_e2e(a.n, a.d, a.repeats),
        "observe": bench_observe(),
        "note": bench_note(),
    }
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    print(f"wrote {a.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    # run alone, the A/B pins two virtual CPU chips; imported (bench.py)
    # it runs on whatever devices the caller has
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # lint: allow-raw-env
    _flags = os.environ.get("XLA_FLAGS", "")  # lint: allow-raw-env
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=2"
        ).strip()
    raise SystemExit(main())
