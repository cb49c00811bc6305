"""A/B: chip fault-tolerance overhead + failover drill (RUNBOOK §2p).

Two legs, one process:

- healthy:  identical streams driven through a 2-chip ``ShardedEngine``
  with the merge deadline OFF (level-1 runs inline, the pre-§2p path) vs
  ON with a generous budget (every level-1 merge runs under a watchdog
  thread, the bounded path) — skyline byte-identity asserted for EVERY
  trigger, zero degraded answers asserted on both legs, and the wall
  delta is the watchdog's tax, which must stay within run-to-run noise.
- drill:    inject ``slow@sharded.chip_merge#1:1`` under a tight
  deadline: the degraded answer must arrive marked (excluded chip +
  completeness bound), the chip quarantines, online failover re-owns its
  partition group, and the first post-heal answer is byte-identical to
  the healthy run. Stamps ``time_to_healed_ms`` (the failover itself)
  and ``degraded_window_ms`` (degraded answer out -> full answer back).

Writes ``artifacts/failover_ab.json``.

Usage: python benchmarks/failover.py [--n 20000] [--d 4] [--repeats 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

def _build(d: int):
    from skyline_tpu.distributed import ShardedEngine
    from skyline_tpu.stream import EngineConfig
    from skyline_tpu.telemetry import Telemetry

    return ShardedEngine(
        EngineConfig(parallelism=2, dims=d, domain_max=10000.0,
                     buffer_size=4096, emit_skyline_points=True),
        chips=2,
        telemetry=Telemetry(),
    )


def _answer(eng, trigger: str):
    eng.process_trigger(trigger)
    (result,) = eng.poll_results()
    pts = np.asarray(result["skyline_points"], dtype=np.float32)
    return result, (int(result["skyline_size"]), pts.tobytes())


def _drive(rows, d: int, bounded: bool):
    """One stream -> two triggers (cold tournament, facade cache hit);
    the deadline knob is read per merge LAUNCH, so flipping env here
    toggles the watchdog path for the whole leg. Returns (wall_s,
    per-trigger answers, stats)."""
    if bounded:
        # generous budget: the bounded machinery runs on every level-1
        # merge but no healthy chip ever trips it
        os.environ["SKYLINE_CHIP_MERGE_DEADLINE_MS"] = "60000"
    else:
        os.environ.pop("SKYLINE_CHIP_MERGE_DEADLINE_MS", None)
    eng = _build(d)
    n = rows.shape[0]
    ids = np.arange(n, dtype=np.int64)
    answers = []
    t0 = time.perf_counter()
    chunk = 1024
    for i in range(0, n, chunk):
        eng.process_records(ids[i : i + chunk], rows[i : i + chunk])
    for trigger in ("cold,0", "hit,0"):
        _, ans = _answer(eng, trigger)
        answers.append(ans)
    dt = time.perf_counter() - t0
    return dt, answers, eng


def bench_healthy(n: int, d: int, repeats: int) -> dict:
    from skyline_tpu.workload.generators import anti_correlated

    rng = np.random.default_rng(0)
    rows = anti_correlated(rng, n, d, 0, 10000)
    off_s, on_s = [], []
    degraded_total = 0
    for _ in range(repeats + 1):  # first round warms the executables
        off_dt, off_answers, off_eng = _drive(rows, d, bounded=False)
        on_dt, on_answers, on_eng = _drive(rows, d, bounded=True)
        # acceptance: the bounded path is byte-identical on a healthy
        # fleet — the watchdog never changes an answer, only its budget
        assert on_answers == off_answers, "bounded merge changed the skyline"
        for eng in (off_eng, on_eng):
            st = eng.stats()["sharded"]
            degraded_total += int(st["degraded_merges"])
            assert st["health"]["quarantined"] == [], (
                "healthy run quarantined a chip"
            )
            degraded_total += int(
                eng.telemetry.counters.get("degraded_answers")
            )
        off_s.append(off_dt)
        on_s.append(on_dt)
    # acceptance: a healthy run never emits a degraded answer, period
    assert degraded_total == 0, f"healthy run degraded {degraded_total}x"
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
        "degraded_answers": 0,
    }


def bench_drill(n: int, d: int) -> dict:
    """slow@chip1 under a tight deadline: degraded -> quarantined ->
    failed over -> healed byte-identical."""
    from skyline_tpu.resilience.faults import FaultPlan, clear, install_plan
    from skyline_tpu.workload.generators import anti_correlated

    rng = np.random.default_rng(0)
    rows = anti_correlated(rng, n, d, 0, 10000)
    os.environ.pop("SKYLINE_CHIP_MERGE_DEADLINE_MS", None)

    # the truth: an uninterrupted healthy run over the same stream
    _, truth, _ = _drive(rows, d, bounded=False)

    eng = _build(d)
    ids = np.arange(n, dtype=np.int64)
    for i in range(0, n, 1024):
        eng.process_records(ids[i : i + 1024], rows[i : i + 1024])
    _, warm = _answer(eng, "warm,0")  # compile walls land here
    assert warm == truth[0]

    os.environ["SKYLINE_CHIP_MERGE_DEADLINE_MS"] = "500"
    os.environ["SKYLINE_CHIP_MERGE_RETRIES"] = "0"
    os.environ["SKYLINE_FAULT_SLOW_MS"] = "2000"
    install_plan(FaultPlan.parse("slow@sharded.chip_merge#1:1"))
    eng.pset._gm_cache = None  # same epoch: force the level-1 rerun
    t_fault = time.perf_counter()
    degraded, _ = _answer(eng, "fault,0")
    t_degraded = time.perf_counter()
    clear()
    for t in threading.enumerate():  # drain the abandoned slow attempt
        if t.name.startswith("chip1-merge"):
            t.join(timeout=30)
    assert degraded["partial"] is True, "drill did not degrade the answer"
    assert degraded["excluded_chips"] == [1]
    assert eng.health.quarantined() == [1]
    # acceptance: the degraded answer landed within the merge deadline
    # budget (deadline + host-side assembly slack), not after the slow
    # chip finally finished
    degraded_wall_ms = (t_degraded - t_fault) * 1000.0
    assert degraded_wall_ms < 2000.0, (
        f"degraded answer took {degraded_wall_ms:.0f}ms — waited out the "
        "slow chip instead of honoring the deadline"
    )

    os.environ.pop("SKYLINE_CHIP_MERGE_DEADLINE_MS", None)
    eng.pset._gm_cache = None
    healed, healed_ans = _answer(eng, "healed,0")  # launch runs failover
    t_healed = time.perf_counter()
    assert "partial" not in healed
    assert eng.pset.failovers == 1
    lf = eng.pset.last_failover
    assert healed_ans == truth[0], "post-heal answer != uninterrupted run"
    return {
        "n": n,
        "d": d,
        "chips": 2,
        "fault": "slow@sharded.chip_merge#1:1",
        "deadline_ms": 500.0,
        "degraded_answer_wall_ms": round(degraded_wall_ms, 1),
        "excluded_chips": degraded["excluded_chips"],
        "completeness_bound": degraded["completeness_bound"],
        "time_to_healed_ms": round(float(lf["wall_ms"]), 1),
        "degraded_window_ms": round((t_healed - t_degraded) * 1000.0, 1),
        "failover_owner": int(lf["owner"]),
        "healed_byte_identical": True,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="chip fault-tolerance overhead A/B + failover drill"
    )
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--d", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument(
        "--out", default=os.path.join(REPO, "artifacts", "failover_ab.json")
    )
    a = ap.parse_args(argv)

    result = {
        "healthy": bench_healthy(a.n, a.d, a.repeats),
        "drill": bench_drill(a.n, a.d),
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
