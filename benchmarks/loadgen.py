"""Multi-tenant serve-plane load harness + body-store A/B (RUNBOOK §2u).

The missing "heavy traffic from millions of users" probe: a synthetic
tenant population (``BENCH_LOAD_TENANTS``, default 10k, zipf-skewed so a
few head tenants dominate like real fleets do) drives a mixed read load —
JSON polls (full payload / ``points=0`` / ``explain=1``), ``format=csv``
polls, ``/deltas`` catch-ups, long-lived SSE subscribers, and periodic
burst storms where every worker piles onto the hottest tenant — through
per-tenant admission, the SLO burn engine, and the Prometheus surface.

Two arms, identical traffic, identical admission:

- ``bodystore``: the zero-copy path — a ``serve/bodystore.py`` BodyStore
  attached to the snapshot store serializes each publish once; reads are
  fence-checked buffer handoffs. Read LRU off, so the store itself is on
  the hook for every body.
- ``baseline``: the pre-§2u hot path — no body store, read LRU off, native
  row encoder disabled: every read pays ``tolist()`` + ``json.dumps`` (or
  the csv line join) in Python.

Byte identity is asserted BEFORE any timing: for every (format × points ×
explain) combination both arms' HTTP bodies must match each other and the
direct ``json.dumps``/csv reference (JSON bodies compared up to the
volatile ``age_ms`` tail, which legitimately differs per request). A
mismatch raises — a fast wrong answer is not a result.

Writes ``artifacts/serve_load_ab.json``; ``bench.py`` stamps the same
block as ``serve_load`` (gated by ``BENCH_LOAD``), which
``scripts/bench_compare.py`` gates on ``read_p99_ms`` / ``shed_fraction``.

Usage: python benchmarks/loadgen.py [--tenants 10000] [--seconds 3]
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_ROWS = 512  # published skyline rows (body ~8 KB/format at d=8)
_DIMS = 8
_PUBLISH_PERIOD_S = 0.1  # background republish cadence during timing


def _publish(store, rng):
    pts = (rng.random((_ROWS, _DIMS)) * 10_000.0).astype(np.float32)
    return store.publish(pts)


def _request(port: int, path: str, tenant: str):
    """One keep-nothing HTTP GET; returns (status, body_bytes, ms)."""
    t0 = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path, headers={"X-Tenant": tenant})
        r = conn.getresponse()
        body = r.read()
        return r.status, body, (time.perf_counter() - t0) * 1000.0
    finally:
        conn.close()


_OPS = (  # (weight, path builder) — the poll/deltas traffic mix
    (0.50, lambda head: "/skyline"),
    (0.15, lambda head: "/skyline?points=0"),
    (0.10, lambda head: "/skyline?explain=1"),
    (0.15, lambda head: "/skyline?format=csv"),
    (0.10, lambda head: f"/deltas?since={max(0, head - 1)}"),
)


def _traffic_tables(rng, tenants: int, zipf: float, burst: float, n: int):
    """Precomputed per-slot (tenant, op) schedules. Burst storms: contiguous
    runs of slots (``burst`` of the total) retargeted at tenant 0 — the
    simultaneous-pile-on shape that makes per-tenant admission earn its
    keep."""
    t = rng.zipf(max(1.01, zipf), size=n) - 1
    t = np.minimum(t, tenants - 1)
    ops = rng.choice(
        len(_OPS), size=n, p=np.array([w for w, _ in _OPS], dtype=float)
    )
    storm = max(1, int(n * burst))
    run = 32  # slots per storm burst
    starts = rng.integers(0, max(1, n - run), size=max(1, storm // run))
    for s in starts:
        t[s : s + run] = 0
    return t, ops


class _SseTap(threading.Thread):
    """One held-open /subscribe stream; counts events until closed."""

    def __init__(self, port: int):
        super().__init__(daemon=True)
        self.port = port
        self.events = 0
        self._conn = None

    def run(self):
        try:
            self._conn = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=30
            )
            self._conn.request("GET", "/subscribe", headers={"X-Tenant": "sse"})
            r = self._conn.getresponse()
            while True:
                line = r.fp.readline()
                if not line:
                    return
                if line.startswith(b"event:"):
                    self.events += 1
        except Exception:
            return  # stream torn down at arm end

    def close(self):
        try:
            if self._conn is not None:
                self._conn.close()
        except Exception:
            pass


def _make_server(store, ring, use_bodystore: bool, telemetry):
    from skyline_tpu.serve import AdmissionController, SkylineServer

    bodystore = None
    if use_bodystore:
        from skyline_tpu.serve.bodystore import BodyStore

        bodystore = BodyStore(None).attach(store)
        # backfill the already-published head (attach only sees future
        # publishes)
        snap = store.latest()
        if snap is not None:
            bodystore.put_snapshot(snap)
    server = SkylineServer(
        store,
        deltas=ring,
        # tight per-tenant buckets: the zipf head tenant (plus the burst
        # storms aimed at it) must actually trip 429s, so shed_fraction
        # is a live signal, not a structural zero
        admission=AdmissionController(tenant_rate=100.0, tenant_burst=32),
        port=0,
        telemetry=telemetry,
        read_cache=0,  # the arms race the BODY paths, not the LRU
        bodystore=bodystore,
    )
    return server, bodystore


_VOLATILE = b', "age_ms":'


def _identity_check(port_a: int, port_b: int, snap) -> int:
    """Every (format × points × explain) body from both arms vs each other
    and the direct-serialization reference. Raises on any mismatch."""
    checked = 0
    from skyline_tpu.bridge.wire import format_tuple_line

    for path, ref in (
        ("/skyline", json.dumps(snap.to_doc(True))[:-1].encode()),
        ("/skyline?points=0", json.dumps(snap.to_doc(False))[:-1].encode()),
        ("/skyline?explain=1", json.dumps(snap.to_doc(True))[:-1].encode()),
        (
            "/skyline?points=0&explain=1",
            json.dumps(snap.to_doc(False))[:-1].encode(),
        ),
        (
            "/skyline?format=csv",
            "\n".join(
                format_tuple_line(i, row) for i, row in enumerate(snap.points)
            ).encode(),
        ),
    ):
        sa, ba, _ = _request(port_a, path, "identity")
        sb, bb, _ = _request(port_b, path, "identity")
        if sa != 200 or sb != 200:
            raise AssertionError(f"identity read failed: {path} {sa}/{sb}")
        if "csv" in path:
            pa, pb = ba, bb
        else:  # split off the per-request volatile tail before comparing
            pa, pb = ba.split(_VOLATILE)[0], bb.split(_VOLATILE)[0]
            if pa != ref:
                raise AssertionError(
                    f"bodystore body != reference for {path}: "
                    f"{pa[:80]!r} vs {ref[:80]!r}"
                )
        if pa != pb:
            raise AssertionError(
                f"arm bodies diverge for {path}: {pa[:80]!r} vs {pb[:80]!r}"
            )
        if "csv" in path and pa != ref:
            raise AssertionError(f"csv body != reference: {pa[:80]!r}")
        checked += 1
    return checked


def _run_arm(server, store, rng, cfg) -> dict:
    """Drive the traffic mix at one server for ``cfg['seconds']``."""
    lat: list[float] = []
    codes: list[int] = []
    bodies = [0]
    lock = threading.Lock()
    stop = threading.Event()

    def publisher():
        while not stop.wait(_PUBLISH_PERIOD_S):
            _publish(store, rng)

    ten_tab, ops = _traffic_tables(
        rng, cfg["tenants"], cfg["zipf"], cfg["burst"], 200_000
    )

    def worker(wid: int):
        my_lat, my_codes, my_bodies = [], [], 0
        i = wid * 7919  # de-phase the workers across the schedule
        deadline = time.perf_counter() + cfg["seconds"]
        while time.perf_counter() < deadline:
            i = (i + 1) % ten_tab.shape[0]
            path = _OPS[ops[i]][1](store.head_version)
            try:
                status, body, ms = _request(
                    server.port, path, f"t{ten_tab[i]}"
                )
            except OSError:
                continue
            my_codes.append(status)
            if status == 200:
                my_lat.append(ms)
                my_bodies += len(body)
        with lock:
            lat.extend(my_lat)
            codes.extend(my_codes)
            bodies[0] += my_bodies

    taps = [_SseTap(server.port) for _ in range(cfg["sse"])]
    for tap in taps:
        tap.start()
    pub = threading.Thread(target=publisher, daemon=True)
    pub.start()
    threads = [
        threading.Thread(target=worker, args=(w,))
        for w in range(cfg["workers"])
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stop.set()
    pub.join(timeout=5)
    for tap in taps:
        tap.close()
    ok = sum(1 for c in codes if c == 200)
    shed = sum(1 for c in codes if c == 429)
    pct = (
        np.percentile(np.asarray(lat), [50, 99])
        if lat
        else np.array([0.0, 0.0])
    )
    cores = os.cpu_count() or 1
    return {
        "reads_total": len(codes),
        "reads_ok": ok,
        "shed_429": shed,
        "shed_fraction": round(shed / max(1, len(codes)), 4),
        "read_p50_ms": round(float(pct[0]), 3),
        "read_p99_ms": round(float(pct[1]), 3),
        "bodies_per_sec": round(ok / wall, 1),
        "bodies_per_core_per_sec": round(ok / wall / cores, 1),
        "body_mb_per_sec": round(bodies[0] / wall / 1e6, 2),
        "sse_events": sum(t.events for t in taps),
        "wall_s": round(wall, 2),
    }


def run_load(
    tenants: int | None = None,
    seconds: float | None = None,
    workers: int | None = None,
    zipf: float | None = None,
    burst: float | None = None,
    sse: int | None = None,
) -> dict:
    """The full A/B: identity gate first, then both arms under the same
    synthetic tenant load. Returns the ``serve_load`` bench block."""
    from skyline_tpu.analysis.registry import (
        env_float,
        env_int,
    )
    from skyline_tpu.serve import DeltaRing, SnapshotStore
    from skyline_tpu.telemetry import Telemetry

    cfg = {
        "tenants": env_int("BENCH_LOAD_TENANTS", 10_000)
        if tenants is None
        else tenants,
        "seconds": env_float("BENCH_LOAD_SECONDS", 3.0)
        if seconds is None
        else seconds,
        "workers": env_int("BENCH_LOAD_WORKERS", 8)
        if workers is None
        else workers,
        "zipf": env_float("BENCH_LOAD_ZIPF", 1.1) if zipf is None else zipf,
        "burst": env_float("BENCH_LOAD_BURST", 0.05)
        if burst is None
        else burst,
        "sse": env_int("BENCH_LOAD_SSE", 4) if sse is None else sse,
    }
    rng = np.random.default_rng(7)

    # two stores (each arm owns its publish cadence), seeded identically so
    # the identity gate compares the same bytes
    seed = (rng.random((_ROWS, _DIMS)) * 10_000.0).astype(np.float32)
    store_a, store_b = SnapshotStore(), SnapshotStore()
    ring_a = DeltaRing(store_a, capacity=128)
    ring_b = DeltaRing(store_b, capacity=128)
    hub_a, hub_b = Telemetry(), Telemetry()
    # same bytes AND same stamped publish instant in both arms, so the
    # identity gate compares byte-identical prefixes
    seed_ms = time.time() * 1000.0
    snap_a = store_a.publish(seed.copy(), now_ms=seed_ms)
    store_b.publish(seed.copy(), now_ms=seed_ms)

    srv_a, bs_a = _make_server(store_a, ring_a, True, hub_a)
    # the baseline arm is the honest pre-bodystore path: Python
    # serialization per read (native row encoder off for the fallback)
    os.environ["SKYLINE_BODYSTORE_NATIVE"] = "0"
    try:
        srv_b, _ = _make_server(store_b, ring_b, False, hub_b)
        try:
            checked = _identity_check(srv_a.port, srv_b.port, snap_a)
            baseline = _run_arm(srv_b, store_b, np.random.default_rng(11), cfg)
        finally:
            srv_b.close()
    finally:
        os.environ.pop("SKYLINE_BODYSTORE_NATIVE", None)
    try:
        hot = _run_arm(srv_a, store_a, np.random.default_rng(11), cfg)
        # the sentinel/SLO surface must be live under load: bodystore
        # counter families on /metrics, burn windows on /slo
        _, metrics, _ = _request(srv_a.port, "/metrics", "probe")
        _, slo, _ = _request(srv_a.port, "/slo", "probe")
        if b"skyline_serve_bodystore_hits_total" not in metrics:
            raise AssertionError("bodystore counters missing from /metrics")
        slo_doc = json.loads(slo)
        arm_stats = dict(bs_a.stats())
    finally:
        srv_a.close()
        if bs_a is not None:
            bs_a.close()

    out = dict(hot)
    out.update(
        {
            "tenants": cfg["tenants"],
            "workers": cfg["workers"],
            "zipf": cfg["zipf"],
            "burst": cfg["burst"],
            "sse_subscribers": cfg["sse"],
            "identity_checked": checked,
            "baseline": baseline,
            "bodystore_counters": arm_stats,
            "speedup_p99": round(
                baseline["read_p99_ms"] / max(1e-9, hot["read_p99_ms"]), 2
            ),
            "speedup_bodies_per_sec": round(
                hot["bodies_per_sec"] / max(1e-9, baseline["bodies_per_sec"]),
                2,
            ),
            "slo_windows": len(slo_doc.get("slos", slo_doc)),
        }
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tenants", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--zipf", type=float, default=None)
    ap.add_argument("--burst", type=float, default=None)
    ap.add_argument("--sse", type=int, default=None)
    args = ap.parse_args()
    block = run_load(
        tenants=args.tenants,
        seconds=args.seconds,
        workers=args.workers,
        zipf=args.zipf,
        burst=args.burst,
        sse=args.sse,
    )
    os.makedirs(os.path.join(REPO, "artifacts"), exist_ok=True)
    path = os.path.join(REPO, "artifacts", "serve_load_ab.json")
    with open(path, "w") as f:
        json.dump({"serve_load": block}, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({"serve_load": block}, indent=2, sort_keys=True))
    print(f"\nwrote {path}")


if __name__ == "__main__":
    # run alone it is a host-side A/B; imported (bench.py) it runs on the
    # caller's devices
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # lint: allow-raw-env
    main()
