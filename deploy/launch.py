"""One-command deployment launcher — the docker-compose role (I2) for
bare-metal hosts.

The reference ships docker-setup/docker-compose.yml (Kafka KRaft broker +
Flink jobmanager/taskmanager); its bare-metal runbook is a 7-terminal
startup order (README_Ubuntu_Setup.md:19-129). This launcher collapses the
whole stack into one supervised command:

    python deploy/launch.py --demo          # bounded end-to-end smoke run
    python deploy/launch.py                 # long-running stack, Ctrl-C stops

It starts, in dependency order, each as a real OS process:
  1. kafkalite broker   (the Kafka service; skipped with --external-broker)
  2. skyline worker     (the Flink job slot)
  3. metrics collector  (python/metrics_collector.py role)
  4. producer           (unified_producer.py role; --demo only, bounded)

All children are killed on exit (or on any child's crash). Logs stream to
``deploy_logs/<name>.log``. The containerized variant of the same topology
is deploy/docker-compose.yml.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# Host-side plane processes (broker / collector / producer / CPU workers)
# pin the CPU backend: a chip belongs to one process, and the chip-holding
# worker (started without this env) must be the only JAX process on it
CPU_PLANE_ENV = {"JAX_PLATFORMS": "cpu"}


class Stack:
    def __init__(self, log_dir: str):
        self.procs: list[tuple[str, subprocess.Popen]] = []
        self.log_dir = log_dir
        os.makedirs(log_dir, exist_ok=True)

    def start(self, name: str, args: list[str], env: dict | None = None):
        log = open(os.path.join(self.log_dir, f"{name}.log"), "w")
        e = dict(os.environ)
        e.setdefault("PYTHONPATH", REPO_ROOT)
        # the stack runs the host-side plane; workers pick their own jax
        # platform (TPU when reachable) unless the caller pinned one
        if env:
            e.update(env)
        p = subprocess.Popen(
            [sys.executable, *args],
            stdout=log,
            stderr=subprocess.STDOUT,
            env=e,
            cwd=REPO_ROOT,
        )
        self.procs.append((name, p))
        print(f"[launch] {name}: pid {p.pid}", file=sys.stderr)
        return p

    def poll_crashed(self) -> str | None:
        """Non-zero exit of any supervised process (clean rc=0 exits —
        e.g. a finished producer — are not crashes)."""
        for name, p in self.procs:
            rc = p.poll()
            if rc is not None and rc != 0:
                return f"{name} exited rc={rc} (see {self.log_dir}/{name}.log)"
        return None

    def stop(self):
        for name, p in reversed(self.procs):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 5
        for name, p in reversed(self.procs):
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()


def wait_for_broker(bootstrap: str, timeout_s: float = 15.0) -> None:
    import socket

    host, _, port = bootstrap.partition(":")
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            socket.create_connection((host, int(port or 9092)), timeout=1).close()
            return
        except OSError:
            time.sleep(0.2)
    raise RuntimeError(f"broker at {bootstrap} not reachable after {timeout_s}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bootstrap", default="127.0.0.1:19092",
                    help="broker address (non-default port so a real Kafka "
                         "on 9092 can coexist)")
    ap.add_argument("--external-broker", action="store_true",
                    help="don't start kafkalite; use an existing broker at "
                         "--bootstrap (e.g. the reference's docker Kafka)")
    ap.add_argument("--algo", default="mr-angle")
    ap.add_argument("--dims", type=int, default=2)
    ap.add_argument("--parallelism", type=int, default=4)
    ap.add_argument("--domain", type=float, default=10000.0)
    ap.add_argument("--distribution", default="anti-correlated")
    ap.add_argument("--demo", action="store_true",
                    help="bounded smoke run: produce --demo-records tuples + "
                         "one trigger, wait for the result row, then exit")
    ap.add_argument("--demo-records", type=int, default=100_000)
    ap.add_argument("--out-csv", default="deploy_logs/results.csv")
    ap.add_argument("--log-dir", default="deploy_logs")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the worker to the CPU backend (no TPU attempt)")
    ap.add_argument("--query-timeout-ms", type=float, default=0.0,
                    help="worker failure watchdog: finalize overdue queries "
                         "as partial results (0 = wait forever)")
    ap.add_argument("--flush-policy", choices=("incremental", "lazy"),
                    default="incremental")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the worker's partition state over this many "
                         "devices (0 = single device)")
    ap.add_argument("--stats-port", type=int, default=18081,
                    help="worker live-stats port (the Flink Web UI :8081 "
                         "role); 0 disables")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window size in tuples (0 = unbounded)")
    ap.add_argument("--slide", type=int, default=0,
                    help="slide in tuples (with --window)")
    args = ap.parse_args(argv)
    if (args.window > 0) != (args.slide > 0):
        ap.error("--window and --slide must be given together")

    stack = Stack(args.log_dir)
    worker_env = dict(CPU_PLANE_ENV) if args.cpu else None
    try:
        if not args.external_broker:
            host, _, port = args.bootstrap.partition(":")
            stack.start(
                "broker",
                ["-m", "skyline_tpu.bridge.kafkalite.broker",
                 "--host", host, "--port", port or "9092"],
            )
        wait_for_broker(args.bootstrap)
        worker_args = [
            "-m", "skyline_tpu.bridge.worker",
            "--bootstrap", args.bootstrap, "--algo", args.algo,
            "--dims", str(args.dims), "--parallelism", str(args.parallelism),
            "--domain", str(args.domain),
            "--flush-policy", args.flush_policy,
            "--stats-port", str(args.stats_port),
        ]
        if args.query_timeout_ms:
            worker_args += ["--query-timeout-ms", str(args.query_timeout_ms)]
        if args.mesh:
            worker_args += ["--mesh", str(args.mesh)]
        if args.window:
            worker_args += ["--window", str(args.window),
                            "--slide", str(args.slide)]
        stack.start("worker", worker_args, env=worker_env)
        csv_path = args.out_csv
        if os.path.isfile(csv_path):
            os.remove(csv_path)
        stack.start(
            "collector",
            ["-m", "skyline_tpu.metrics.collector", csv_path,
             "--bootstrap", args.bootstrap],
            env=CPU_PLANE_ENV,
        )
        # wait for the worker's startup banner: its latest-offset query
        # consumer subscribes during construction, and a trigger produced
        # before that subscription would be skipped as history (a fixed
        # sleep loses the race on hosts with a cold jax import)
        worker_log = os.path.join(args.log_dir, "worker.log")
        ready_deadline = time.time() + 120
        while time.time() < ready_deadline:
            crashed = stack.poll_crashed()
            if crashed:
                print(f"[launch] FAILED: {crashed}", file=sys.stderr)
                return 1
            if os.path.isfile(worker_log) and "skyline worker:" in open(worker_log).read():
                break
            time.sleep(0.2)
        else:
            print("[launch] FAILED: worker not ready within 120s", file=sys.stderr)
            return 1

        if args.demo:
            n = args.demo_records
            stack.start(
                "producer",
                ["-m", "skyline_tpu.workload.producer",
                 "input-tuples", args.distribution, str(args.dims),
                 "0", str(int(args.domain)), "queries",
                 "--count", str(n), "--seed", "0",
                 # immediate trigger after the finite stream: an id-barrier
                 # trigger can defer forever when a sparse partition's few
                 # records all predate the barrier id (SURVEY.md §3.3 —
                 # the reference's own producer is an infinite loop)
                 "--query-threshold", "0", "--final-trigger",
                 "--bootstrap", args.bootstrap],
                env=CPU_PLANE_ENV,
            )
            deadline = time.time() + 600
            while time.time() < deadline:
                crashed = stack.poll_crashed()
                if crashed:
                    print(f"[launch] FAILED: {crashed}", file=sys.stderr)
                    return 1
                if os.path.isfile(csv_path):
                    with open(csv_path) as f:
                        rows = f.read().strip().splitlines()
                    if len(rows) >= 2:
                        print(f"[launch] demo OK — result row: {rows[1]}",
                              file=sys.stderr)
                        return 0
                time.sleep(0.5)
            print("[launch] FAILED: no result row within 600s", file=sys.stderr)
            return 1

        print("[launch] stack up; Ctrl-C to stop", file=sys.stderr)
        while True:
            crashed = stack.poll_crashed()
            if crashed:
                print(f"[launch] FAILED: {crashed}", file=sys.stderr)
                return 1
            time.sleep(1.0)
    except KeyboardInterrupt:
        print("[launch] stopping", file=sys.stderr)
        return 0
    finally:
        stack.stop()


if __name__ == "__main__":
    raise SystemExit(main())
