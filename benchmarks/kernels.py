"""Kernel microbenchmarks: the evidence behind every in-code perf claim.

Times the dominance/skyline kernel family at realistic shapes on the active
backend (TPU when run plain, CPU with ``JAX_PLATFORMS=cpu``), plus the
native-vs-Python CSV parse rates, and prints one JSON document. Committed
artifacts live in ``artifacts/kernels_{tpu,cpu}.json`` — the docstrings in
``ops/dispatch.py``, ``ops/block_skyline.py`` and ``native/__init__.py``
cite them.

What's measured (all warm — compile excluded; median of ``--reps``):

- ``skyline_mask``        dense (N, N) tile kernel           N in {4k, 8k}
- ``skyline_mask_scan``   linear chunked scan                N in {16k, 64k, 256k}
- ``skyline_mask_blocked``nested-scan triangular             N in {16k, 64k}
- ``skyline_mask_pallas`` VMEM-tiled triangular (TPU only)   N in {16k, 64k, 256k}
- ``dominated_by_pallas`` rectangular sky-vs-batch pass      (64k x 8k)
- ``merge_step_batched``  one full incremental flush step    (P=8, cap=64k, B=8k)
- ``compact``             the flush's argsort compaction     (P=8, 72k rows)
- ``skyline_large``       host-driven SFS, whole window      N in {256k, 1M}
- ``skyline_mask_sweep2`` d=2 sort-sweep (no pairwise work,   N in {64k, 256k, 1M}
                          so no gpairs_per_s column)
- ``parse``               native fastcsv vs Python wire parse (100k lines)

Usage: python benchmarks/kernels.py [--reps 5] [--out artifacts/kernels_tpu.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _median_time(fn, reps: int) -> float:
    """Median wall seconds of ``fn()`` over ``reps`` runs (fn must block)."""
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_mask_kernels(reps: int, d: int, results: dict) -> None:
    import jax
    import jax.numpy as jnp

    from skyline_tpu.ops.block_skyline import (
        skyline_mask_blocked,
        skyline_mask_scan,
    )
    from skyline_tpu.ops.dominance import skyline_mask
    from skyline_tpu.workload.generators import anti_correlated

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)

    variants: list[tuple[str, object, list[int]]] = [
        ("skyline_mask_dense", lambda xv: skyline_mask(xv),
         [4096, 8192] if on_tpu else [4096]),
        (
            "skyline_mask_scan",
            lambda xv: skyline_mask_scan(xv),
            [16384, 65536, 262144] if on_tpu else [16384],
        ),
        (
            "skyline_mask_blocked",
            lambda xv: skyline_mask_blocked(xv),
            [16384, 65536] if on_tpu else [16384],
        ),
    ]
    if on_tpu:
        from skyline_tpu.ops.pallas_dominance import skyline_mask_pallas

        variants.append(
            (
                "skyline_mask_pallas",
                lambda xv: skyline_mask_pallas(xv),
                [16384, 65536, 262144],
            )
        )

    for name, fn, sizes in variants:
        for n in sizes:
            x = jnp.asarray(anti_correlated(rng, n, d, 0, 10000))
            np.asarray(fn(x))  # compile + drain
            t = _median_time(lambda: np.asarray(fn(x)), reps)
            # N^2/2 when the kernel exploits sum-sort triangularity
            pairs = n * n / 2 if name in ("skyline_mask_blocked", "skyline_mask_pallas") else n * n
            results[f"{name}/n={n}/d={d}"] = {
                "ms": round(t * 1000, 2),
                "gpairs_per_s": round(pairs / t / 1e9, 1),
            }

    # d=2 sort-sweep (ops/sweep2d.py): no pairwise work, so report ms only
    # (the kernel every d<=2 path dispatches to on both backends)
    from skyline_tpu.ops.sweep2d import skyline_mask_sweep2

    for n in [65536, 262144, 1048576]:
        x2 = jnp.asarray(anti_correlated(rng, n, 2, 0, 10000))
        v2 = jnp.ones((n,), bool)
        np.asarray(skyline_mask_sweep2(x2, v2))
        t = _median_time(lambda: np.asarray(skyline_mask_sweep2(x2, v2)), reps)
        results[f"skyline_mask_sweep2/n={n}/d=2"] = {
            "ms": round(t * 1000, 2),
        }


def bench_flush_step(reps: int, d: int, results: dict) -> None:
    """One incremental flush step at the north-star shapes: P=8 partitions,
    cap=65536 running skylines, B=8192 batch."""
    import jax
    import jax.numpy as jnp

    from skyline_tpu.ops.dominance import compact
    from skyline_tpu.stream.window import (
        _merge_step_batched,
        _merge_step_pallas_batched,
    )
    from skyline_tpu.workload.generators import anti_correlated

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(1)
    P, cap, B = 8, 65536, 8192
    if not on_tpu:
        cap, B = 8192, 1024  # CPU would take minutes at TPU shapes

    # a realistic running skyline: the skyline of an anti-correlated draw,
    # padded into the capacity buffer (valid fraction ~cap/2)
    sky = np.full((P, cap, d), np.inf, dtype=np.float32)
    sky_valid = np.zeros((P, cap), dtype=bool)
    from skyline_tpu.ops.dispatch import skyline_keep_np

    for p in range(P):
        draw = anti_correlated(rng, cap, d, 0, 10000)
        pts = draw[skyline_keep_np(draw)][: cap // 2]
        sky[p, : pts.shape[0]] = pts
        sky_valid[p, : pts.shape[0]] = True
    batch = np.stack([anti_correlated(rng, B, d, 0, 10000) for _ in range(P)])
    bvalid = np.ones((P, B), dtype=bool)

    sky_j = jnp.asarray(sky)
    skyv_j = jnp.asarray(sky_valid)
    b_j = jnp.asarray(batch)
    bv_j = jnp.asarray(bvalid)

    merge = _merge_step_pallas_batched if on_tpu else _merge_step_batched
    np.asarray(merge(sky_j, skyv_j, b_j, bv_j, cap)[2])  # compile + drain
    t = _median_time(
        lambda: np.asarray(merge(sky_j, skyv_j, b_j, bv_j, cap)[2]), reps
    )
    results[f"merge_step_batched/P={P}/cap={cap}/B={B}/d={d}"] = {
        "ms": round(t * 1000, 2),
        "kernel": "pallas" if on_tpu else "xla",
    }

    # the compaction alone: argsort + gather over the (P, cap+B) buffer
    x_all = jnp.concatenate([sky_j, b_j], axis=1)
    keep = jnp.concatenate([skyv_j, bv_j], axis=1)
    comp = jax.jit(
        jax.vmap(lambda xv, kv: compact(xv, kv, cap)), static_argnums=()
    )
    np.asarray(comp(x_all, keep)[2])  # compile + drain
    t = _median_time(lambda: np.asarray(comp(x_all, keep)[2]), reps)
    results[f"compact/P={P}/rows={cap + B}/d={d}"] = {"ms": round(t * 1000, 2)}


def bench_rect_pass(reps: int, d: int, results: dict) -> None:
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return
    from skyline_tpu.ops.pallas_dominance import dominated_by_pallas
    from skyline_tpu.workload.generators import anti_correlated

    rng = np.random.default_rng(2)
    nx, ny = 65536, 8192
    xt = jnp.asarray(anti_correlated(rng, nx, d, 0, 10000).T)
    yt = jnp.asarray(anti_correlated(rng, ny, d, 0, 10000).T)
    xv = jnp.ones((nx,), dtype=bool)
    np.asarray(dominated_by_pallas(xt, xv, yt))  # compile + drain
    t = _median_time(
        lambda: np.asarray(dominated_by_pallas(xt, xv, yt)), reps
    )
    results[f"dominated_by_pallas/{nx}x{ny}/d={d}"] = {
        "ms": round(t * 1000, 2),
        "gpairs_per_s": round(nx * ny / t / 1e9, 1),
    }


def bench_sfs(reps: int, d: int, results: dict) -> None:
    import jax

    from skyline_tpu.ops.block_skyline import skyline_large
    from skyline_tpu.workload.generators import anti_correlated

    sizes = [262144, 1_000_000] if jax.default_backend() == "tpu" else [65536]
    rng = np.random.default_rng(3)
    for n in sizes:
        x = anti_correlated(rng, n, d, 0, 10000)
        skyline_large(x)  # compile all capacity buckets
        t = _median_time(lambda: skyline_large(x), max(1, reps // 2))
        results[f"skyline_large/n={n}/d={d}"] = {
            "ms": round(t * 1000, 2),
            "skyline_size": int(skyline_large(x).shape[0]),
        }


def bench_parse(reps: int, results: dict) -> None:
    from skyline_tpu import native
    from skyline_tpu.bridge import wire

    rng = np.random.default_rng(4)
    n, d = 100_000, 8
    vals = rng.uniform(0, 10000, size=(n, d))
    lines = [
        f"{i}," + ",".join(f"{v:.3f}" for v in row)
        for i, row in enumerate(vals)
    ]
    # force the Python fallback by hiding the native lib from wire's check
    real_get_lib = native.get_lib
    native.get_lib = lambda: None
    try:
        t_py = _median_time(lambda: wire.parse_tuple_lines(lines, d), reps)
    finally:
        native.get_lib = real_get_lib
    results[f"parse_python/lines={n}/d={d}"] = {
        "ms": round(t_py * 1000, 2),
        "mlines_per_s": round(n / t_py / 1e6, 2),
    }
    if native.get_lib() is not None:
        t_nat = _median_time(lambda: wire.parse_tuple_lines(lines, d), reps)
        results[f"parse_native/lines={n}/d={d}"] = {
            "ms": round(t_nat * 1000, 2),
            "mlines_per_s": round(n / t_nat / 1e6, 2),
            "speedup_vs_python": round(t_py / t_nat, 1),
        }


def bench_transport(results: dict) -> None:
    """Produce/consume throughput through the kafkalite broker over real
    TCP — the artifact behind the transport-rate claims (native CRC32C +
    record framing on produce, inlined varint decode on fetch). Records an
    ``error`` entry instead of wedging if the broker can't start or the
    stream stalls."""
    import time as _time

    # one process-supervision implementation: the deployment launcher owns
    # it (PYTHONPATH/cwd pinning, log capture, SIGTERM+wait+kill stop)
    from deploy.launch import Stack, wait_for_broker
    from skyline_tpu.bridge.kafka import KafkaBus

    port = 19901
    log_dir = os.path.join("/tmp", f"kernels_transport_{os.getpid()}")
    stack = Stack(log_dir)
    try:
        stack.start(
            "broker",
            ["-m", "skyline_tpu.bridge.kafkalite.broker",
             "--host", "127.0.0.1", "--port", str(port)],
            env={"JAX_PLATFORMS": "cpu"},
        )
        wait_for_broker(f"127.0.0.1:{port}")
        crashed = stack.poll_crashed()
        if crashed:
            raise RuntimeError(crashed)
        bus = KafkaBus(f"127.0.0.1:{port}")
        rng = np.random.default_rng(5)
        # pid-unique topics: a stale broker from a killed prior run must
        # not contribute its old records to this run's measurement
        run_tag = os.getpid()
        for d in (2, 8):
            n = 200_000
            vals = rng.uniform(0, 10000, (n, d)).astype(np.int64)
            lines = [
                f"{i}," + ",".join(map(str, row))
                for i, row in enumerate(vals.tolist())
            ]
            topic = f"bench-{run_tag}-{d}"
            t0 = _time.perf_counter()
            bus.produce_many(topic, lines)
            tp = _time.perf_counter() - t0
            cons = bus.consumer(topic, from_beginning=True)
            t0 = _time.perf_counter()
            got = 0
            deadline = t0 + 120.0
            while got < n:
                got += len(cons.poll(max_records=1 << 20))
                if _time.perf_counter() > deadline:
                    raise RuntimeError(
                        f"consume stalled: {got}/{n} records in 120s"
                    )
            tc = _time.perf_counter() - t0
            results[f"kafkalite_produce/lines={n}/d={d}"] = {
                "ms": round(tp * 1000, 1),
                "klines_per_s": round(n / tp / 1e3, 1),
            }
            results[f"kafkalite_consume/lines={n}/d={d}"] = {
                "ms": round(tc * 1000, 1),
                "klines_per_s": round(n / tc / 1e3, 1),
            }
    except Exception as e:  # noqa: BLE001
        results["kafkalite_transport"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    finally:
        stack.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    ap.add_argument("--d", type=int, default=8)
    ap.add_argument(
        "--only",
        default=None,
        help="comma list from: masks,flush,rect,sfs,parse,transport",
    )
    args = ap.parse_args()

    import jax

    results: dict = {}
    meta = {
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "reps": args.reps,
    }
    only = set(args.only.split(",")) if args.only else None

    def want(k):
        return only is None or k in only

    if want("masks"):
        bench_mask_kernels(args.reps, args.d, results)
    if want("flush"):
        bench_flush_step(args.reps, args.d, results)
    if want("rect"):
        bench_rect_pass(args.reps, args.d, results)
    if want("sfs"):
        bench_sfs(args.reps, args.d, results)
    if want("parse"):
        bench_parse(args.reps, results)
    if want("transport"):
        bench_transport(results)

    doc = {"meta": meta, "results": results}
    out = json.dumps(doc, indent=1)
    print(out)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(out + "\n")


if __name__ == "__main__":
    main()
