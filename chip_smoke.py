"""Bring-up smoke test: the north-star deployment, end to end on the chip.

Run from the repo root on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # --mesh-chips 4 and --mesh 4 vs flat

One chip: builds the worker the way ``bridge/worker.py`` does for the
north-star deployment (in-memory bus, MR-Angle, parallelism 4, lazy flush,
buffer 8192, initial capacity 65536, serve plane on a free port), feeds
three batches of 1,000,000 8-D anti-correlated tuples (domain 0-10,000,
made from ``--seed``) with a query trigger after each, and checks after
every trigger that the published skyline, and ``GET /skyline?format=csv``,
equal the host sorted cascade over every row ingested so far (sha256 of the
canonically ordered f32 rows). A separate 8,192-row engine is checked
against the O(n^2 d) audit oracle.

``--chips 4``: the first window only, through a flat worker, a sharded
worker (``--mesh-chips 4``) and a meshed worker (``--mesh 4``); the two
multi-chip answers must equal the flat one, with each chip group's state on
its own device.

Earlier lines print set-up facts (devices, compile and warm-up seconds,
per-trigger wall time and skyline size, the dispatch variants that ran);
none of them is a metric. The last line is one JSON object,
``{"ok": true, "device": {...}}``; any failed check exits non-zero
without it. The script fails when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
import urllib.request

import numpy as np

DIMS = 8
DOMAIN = 10000.0
ROWS = 1_000_000  # tuples per batch: the north-star window
BATCHES = 3


class SmokeFailure(Exception):
    pass


_compile_s = [0.0]  # backend compile seconds so far in this process


def count_compiles() -> None:
    import jax.monitoring

    def on_duration(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require_tpu(chips: int) -> list:
    """The devices to run on; refuses anything but real TPU chips."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu", f"JAX found no TPU (found {devs[0].platform})")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    from skyline_tpu.ops.sfs import pallas_interpret

    check(not pallas_interpret(), "Pallas kernels are set to interpret mode")
    return devs


def canon_digest(points) -> str:
    """sha256 of the rows as f32, in lexicographic row order."""
    pts = np.ascontiguousarray(np.asarray(points, dtype=np.float32)).reshape(-1, DIMS)
    order = np.lexsort(pts.T[::-1])
    return hashlib.sha256(np.ascontiguousarray(pts[order]).tobytes()).hexdigest()


def csv_rows(text: str) -> np.ndarray:
    """Rows of a ``format=csv`` body (``id,v1,...,vd`` per line)."""
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        return np.empty((0, DIMS), np.float32)
    arr = np.array([ln.split(",")[1:] for ln in lines], dtype=np.float64)
    return arr.astype(np.float32)


def wire_lines(ids: np.ndarray, x: np.ndarray) -> list[str]:
    """Data-plane lines as a producer sends them (integer-valued coords)."""
    from skyline_tpu.native import format_tuples_native

    fmt = format_tuples_native(ids, x.astype(np.int64))
    check(fmt is not None, "native formatter unavailable")
    text = fmt[0].decode()
    offs = fmt[1].tolist()
    return [text[offs[i]:offs[i + 1]].strip() for i in range(len(ids))]


def http_get(port: int, path: str) -> str:
    url = f"http://127.0.0.1:{port}{path}"
    with urllib.request.urlopen(url, timeout=120) as r:
        check(r.status == 200, f"GET {path} -> {r.status}")
        return r.read().decode()


def engine_config(window_capacity: int = 1_000_000):
    """The north-star engine (bench.py's settings)."""
    from skyline_tpu.stream import EngineConfig

    return EngineConfig(
        parallelism=4,
        algo="mr-angle",
        dims=DIMS,
        domain_max=DOMAIN,
        buffer_size=8192,
        initial_capacity=65536,
        flush_policy="lazy",
        window_capacity=window_capacity,
    )


def make_worker(config=None, **kw):
    """A worker on an in-memory bus with its serve plane on a free port
    (``--serve 0``): the engine publishes every answer as a snapshot."""
    from skyline_tpu.bridge.memory import MemoryBus
    from skyline_tpu.bridge.worker import SkylineWorker

    bus = MemoryBus()
    worker = SkylineWorker(bus, config or engine_config(), serve_port=0, **kw)
    return bus, worker


def feed_and_query(bus, worker, lines: list[str], qid: int) -> dict:
    """Produce one batch plus an immediate trigger; step until idle; return
    the result the worker emitted for that trigger."""
    from skyline_tpu.bridge.wire import format_trigger

    bus.produce_many("input-tuples", lines)
    bus.produce("queries", format_trigger(qid, 0))
    while worker.step() > 0:
        pass
    out = [json.loads(m) for m in bus._topics["output-skyline"]]
    mine = [r for r in out if str(r.get("query_id")) == str(qid)]
    check(len(mine) == 1, f"query {qid}: {len(mine)} results emitted")
    return mine[0]


def published_points(worker) -> np.ndarray:
    snap = worker.engine.snapshots.latest()
    check(snap is not None, "no snapshot published")
    return np.asarray(snap.points)


def variants_ran(port: int) -> dict:
    """Which kernel variants ran, and how often, from the worker's kernel
    profiler (``GET /profile``)."""
    doc = json.loads(http_get(port, "/profile"))
    ran: dict[str, int] = {}
    for row in doc["kernels"]:
        key = f"{row['variant']}(d={row['d']},mp={row['mp']},{row['backend']})"
        ran[key] = ran.get(key, 0) + row["calls"]
    return ran


def flush_has_kernel() -> bool:
    """Whether the lazy flush's SFS round lowers to a Pallas TPU kernel."""
    import jax
    import jax.numpy as jnp

    from skyline_tpu.stream.device_window import sfs_round_at

    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    text = sfs_round_at.lower(
        jax.ShapeDtypeStruct((65536, DIMS), jnp.float32),
        i32,
        jax.ShapeDtypeStruct((1_000_000, DIMS), jnp.float32),
        i32,
        i32,
        B=8192,
        active=65536,
    ).as_text()
    return "tpu_custom_call" in text


def run_one_chip(seed: int, rows: int, batches: int) -> None:
    from skyline_tpu.ops.dominance import skyline_np
    from skyline_tpu.ops.sorted_sfs import sorted_skyline_mask_np
    from skyline_tpu.workload.generators import anti_correlated

    rng = np.random.default_rng(seed)
    log(f"lazy flush lowers to tpu_custom_call: {flush_has_kernel()}")

    # audit-oracle leg: a small engine against the O(n^2 d) reference
    t0, c0 = time.perf_counter(), _compile_s[0]
    x = anti_correlated(rng, 8192, DIMS, 0, DOMAIN)
    bus, worker = make_worker(engine_config(8192))
    try:
        res = feed_and_query(bus, worker, wire_lines(np.arange(8192), x), 0)
        got = published_points(worker)
    finally:
        worker.close()
    want = skyline_np(x)
    check(
        canon_digest(got) == canon_digest(want),
        f"8192-row engine != audit oracle ({got.shape[0]} vs {want.shape[0]})",
    )
    check(res["skyline_size"] == want.shape[0], "8192-row result size")
    log(
        f"8192-row engine == audit oracle: {want.shape[0]} survivors "
        f"(wall {time.perf_counter() - t0:.2f} s, of it backend compile "
        f"{_compile_s[0] - c0:.2f} s)"
    )

    bus, worker = make_worker()
    try:
        port = worker.serve_server.port
        seen = []
        for k in range(batches):
            x = anti_correlated(rng, rows, DIMS, 0, DOMAIN)
            ids = np.arange(k * rows, (k + 1) * rows, dtype=np.int64)
            lines = wire_lines(ids, x)
            seen.append(x)
            t0, c0 = time.perf_counter(), _compile_s[0]
            res = feed_and_query(bus, worker, lines, k + 1)
            wall = time.perf_counter() - t0
            compiled = _compile_s[0] - c0
            got = published_points(worker)
            t1 = time.perf_counter()
            allx = np.concatenate(seen)
            want = allx[sorted_skyline_mask_np(allx)]
            ref_s = time.perf_counter() - t1
            digest = canon_digest(want)
            check(
                canon_digest(got) == digest,
                f"trigger {k + 1}: engine != host reference "
                f"({got.shape[0]} vs {want.shape[0]} rows)",
            )
            check(res["skyline_size"] == want.shape[0], f"trigger {k + 1}: size")
            served = csv_rows(http_get(port, "/skyline?format=csv"))
            check(
                canon_digest(served) == digest,
                f"trigger {k + 1}: GET /skyline?format=csv != host reference",
            )
            log(
                f"trigger {k + 1}: {allx.shape[0]} rows -> {want.shape[0]} "
                f"survivors; engine and /skyline csv == host reference "
                f"(sha256 {digest[:16]}); wall {wall:.2f} s, of it backend "
                f"compile {compiled:.2f} s; host reference {ref_s:.2f} s"
            )
        log(f"kernel variants that ran (calls): {variants_ran(port)}")
    finally:
        worker.close()


def run_four_chips(seed: int, rows: int) -> None:
    from skyline_tpu.parallel.mesh import make_mesh
    from skyline_tpu.workload.generators import anti_correlated

    rng = np.random.default_rng(seed)
    x = anti_correlated(rng, rows, DIMS, 0, DOMAIN)
    lines = wire_lines(np.arange(rows, dtype=np.int64), x)
    answers = {}
    for name, kw in (
        ("flat", {}),
        ("sharded", {"mesh_chips": 4}),
        ("meshed", {"mesh": make_mesh(4)}),
    ):
        bus, worker = make_worker(**kw)
        try:
            t0, c0 = time.perf_counter(), _compile_s[0]
            res = feed_and_query(bus, worker, lines, 1)
            wall = time.perf_counter() - t0
            compiled = _compile_s[0] - c0
            pts = published_points(worker)
            pset = worker.engine.pset
            if name == "sharded":
                homes = [next(iter(g.sky.devices())) for g in pset._chips]
                check(
                    len({d.id for d in homes}) == 4,
                    f"sharded chip groups share devices: {homes}",
                )
                where = [str(d) for d in homes]
            elif name == "meshed":
                homes = pset.sky.sharding.device_set
                check(len(homes) == 4, f"meshed state spans {len(homes)} devices")
                where = sorted(str(d) for d in homes)
            else:
                where = [str(d) for d in pset.sky.devices()]
        finally:
            worker.close()
        check(res["skyline_size"] == pts.shape[0], f"{name}: result size")
        answers[name] = pts
        log(
            f"{name}: {pts.shape[0]} survivors, wall {wall:.2f} s, of it "
            f"backend compile {compiled:.2f} s; state on {where}"
        )
    flat = answers["flat"]
    for name in ("sharded", "meshed"):
        same_set = canon_digest(answers[name]) == canon_digest(flat)
        same_order = (
            answers[name].shape == flat.shape
            and answers[name].tobytes() == flat.tobytes()
        )
        check(same_set, f"{name} answer != flat answer")
        log(f"{name} == flat: canonical bytes equal, row order equal: {same_order}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    try:
        devs = require_tpu(a.chips)
        import jax

        from skyline_tpu.utils.compile_cache import enable_compile_cache

        log(f"devices: {[str(d) for d in devs]}, kind {devs[0].device_kind}")
        log(f"compile cache: {enable_compile_cache()}")
        count_compiles()
        t0 = time.perf_counter()
        if a.chips == 4:
            run_four_chips(a.seed, ROWS)
        else:
            run_one_chip(a.seed, ROWS, BATCHES)
        log(f"total {time.perf_counter() - t0:.1f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": devs[0].platform,
                    "kind": devs[0].device_kind,
                    "count": len(devs),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
