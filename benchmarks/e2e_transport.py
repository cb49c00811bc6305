"""Transport-inclusive end-to-end throughput: the number comparable to the
reference's ~58k tuples/s at 2D (1M / 17.3 s best TotalTime, pdf §5.5,
graph_paper_figures.py:28-32 — Kafka-to-result wall with ingest dominating).

Drives the real stack as separate OS processes — producer (CSV lines over
the Kafka wire protocol) -> kafkalite broker (TCP) -> worker (parse via
native/fastcsv -> engine) -> collector (CSV) — and reports:

- ``wall_s`` / ``tuples_per_sec_wall``: first-produce -> result-row wall
  (the whole pipeline including generation and transport)
- ``total_ms_reported``: the result's own TotalTime (job-start -> emit,
  FlinkSkyline.java:587 semantics — the reference's headline column)

Prints one JSON line per config and writes ``artifacts/e2e_transport.json``.

Policy choice: round 3 measured lazy 22.0 s wall vs incremental (buffer
262144) 61.0 s at 8-D/1M warm — incremental re-prunes against the running
~400k-row skylines every flush, tripling dominance work. Round 4 adds the
``overlap`` policy (lazy SFS machinery flushed every overlap_rows, device
rounds concurrent with transport ingest — the Flink-style source/operator
overlap) plus device-resident ingest; the runner defaults to it
(``--flush-policy`` overrides for A/Bs).

Usage:
  python benchmarks/e2e_transport.py [--records 1000000] [--dims 2 8]
      [--cpu] [--out artifacts/e2e_transport.json]
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# one process-supervision implementation: the deployment launcher owns it
from deploy.launch import CPU_PLANE_ENV, Stack, wait_for_broker  # noqa: E402


def run_config(dims: int, records: int, bootstrap: str, log_dir: str,
               cpu: bool, timeout_s: float,
               flush_policy: str = "overlap") -> dict:
    os.makedirs(log_dir, exist_ok=True)
    csv_path = os.path.join(log_dir, f"e2e_{dims}d.csv")
    if os.path.isfile(csv_path):
        os.remove(csv_path)
    stack = Stack(log_dir)
    host, _, port = bootstrap.partition(":")
    try:
        stack.start(
            "broker",
            ["-m", "skyline_tpu.bridge.kafkalite.broker",
             "--host", host, "--port", port],
            env=CPU_PLANE_ENV,
        )
        wait_for_broker(bootstrap)
        # workers share one compile cache (enable_compile_cache)
        worker_env = dict(CPU_PLANE_ENV) if cpu else None
        stack.start(
            "worker",
            ["-m", "skyline_tpu.bridge.worker", "--bootstrap", bootstrap,
             "--algo", "mr-angle", "--dims", str(dims),
             "--parallelism", "4", "--domain", "10000",
             "--flush-policy", flush_policy, "--stats-port", "0"],
            env=worker_env,
        )
        stack.start(
            "collector",
            ["-m", "skyline_tpu.metrics.collector", csv_path,
             "--bootstrap", bootstrap],
            env=CPU_PLANE_ENV,
        )
        # wait for the worker's query subscription (latest offsets) before
        # producing the trigger-bearing stream
        worker_log = os.path.join(log_dir, "worker.log")
        deadline = time.time() + 180
        while time.time() < deadline:
            if (os.path.isfile(worker_log)
                    and "skyline worker:" in open(worker_log).read()):
                break
            crashed = stack.poll_crashed()
            if crashed:
                raise RuntimeError(crashed)
            time.sleep(0.2)
        else:
            raise RuntimeError("worker not ready in 180s")

        t0 = time.perf_counter()
        producer = stack.start(
            "producer",
            ["-m", "skyline_tpu.workload.producer", "input-tuples",
             "anti-correlated", str(dims), "0", "10000", "queries",
             "--count", str(records), "--seed", "0",
             "--query-threshold", "0", "--final-trigger",
             "--bootstrap", bootstrap],
            env=CPU_PLANE_ENV,
        )
        produce_s = None
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            # a crashed/hung-killed process means no result will ever
            # come — fail the config now, not at the full timeout (a
            # producer crash is reported here too, with its log path)
            crashed = stack.poll_crashed()
            if crashed:
                raise RuntimeError(crashed)
            if produce_s is None and producer.poll() is not None:
                produce_s = time.perf_counter() - t0
            if os.path.isfile(csv_path):
                with open(csv_path) as f:
                    rows = list(csv.reader(f))
                if len(rows) >= 2:
                    wall_s = time.perf_counter() - t0
                    row = dict(zip(rows[0], rows[1]))
                    return {
                        "config": f"e2e_transport_{dims}d_anticorrelated",
                        "n": records,
                        "dims": dims,
                        "flush_policy": flush_policy,
                        "wall_s": round(wall_s, 2),
                        "produce_s": round(produce_s, 2) if produce_s else None,
                        "tuples_per_sec_wall": round(records / wall_s, 1),
                        "skyline_size": int(row["SkylineSize"]),
                        "total_ms_reported": int(row["TotalTime(ms)"]),
                        "latency_ms_reported": int(row["Latency(ms)"]),
                        "backend": "cpu" if cpu else "tpu",
                    }
            time.sleep(0.5)
        raise RuntimeError(f"no result within {timeout_s}s")
    finally:
        stack.stop()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--records", type=int, default=1_000_000)
    ap.add_argument("--dims", type=int, nargs="+", default=[2, 8])
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--bootstrap", default="127.0.0.1:19892")
    ap.add_argument("--log-dir", default="deploy_logs_e2e")
    ap.add_argument("--timeout", type=float, default=1800.0)
    ap.add_argument("--flush-policy", default="overlap",
                    choices=("incremental", "lazy", "overlap"),
                    help="worker flush policy; overlap runs device append "
                         "rounds concurrently with transport ingest "
                         "(round-4 default; round 3 measured lazy best "
                         "before the device-ingest/overlap rework)")
    ap.add_argument("--out", default="artifacts/e2e_transport.json")
    a = ap.parse_args(argv)
    results = []
    for dims in a.dims:
        out = run_config(dims, a.records, a.bootstrap, a.log_dir, a.cpu,
                         a.timeout, a.flush_policy)
        print(json.dumps(out), flush=True)
        results.append(out)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
