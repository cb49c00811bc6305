"""Replicated read fleet (ISSUE 15): WAL tail-following, fenced staleness,
supervised replica failover, per-tenant admission, and SSE delta push.

The acceptance test here is ``test_chaos_engine_kill_replicas_stay_honest``:
two live replicas under a concurrent reader burst, the primary killed
mid-burst — every replica answer afterwards reports monotonically aging
staleness, a tightly-fenced replica serves ZERO 200s past its fence, the
served bytes are sha256-identical to the primary's at every common
version, and after the primary restarts the replicas reconverge through
the tail alone (no re-bootstrap) unless corruption was injected.
"""

import hashlib
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from skyline_tpu.resilience.faults import FaultPlan, clear, install_plan
from skyline_tpu.resilience.wal import (
    WalSegmentGone,
    WalTailCorruption,
    WalTailer,
    WalWriter,
    list_segments,
    segment_first_record,
    tail_retention_floor,
)
from skyline_tpu.serve import (
    DeltaRing,
    ServeConfig,
    SkylineServer,
    SnapshotStore,
    apply_delta_record,
    delta_wal_record,
    snapshot_wal_record,
)
from skyline_tpu.serve.replica import ReplicaDivergence, SkylineReplica
from skyline_tpu.telemetry import Telemetry


@pytest.fixture(autouse=True)
def _clear_faults():
    clear()
    yield
    clear()


def _get(url, timeout=10, headers=None):
    """(status, json_doc, headers) — HTTPError surfaces as its status."""
    req = urllib.request.Request(url, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.load(r), dict(r.headers)
    except urllib.error.HTTPError as e:
        body = e.read()
        return e.code, (json.loads(body) if body else {}), dict(e.headers)


def _get_raw(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read(), dict(r.headers)


def _primary(directory, **writer_kw):
    """A primary-shaped publish pipeline: SnapshotStore whose publish hook
    shadows every transition into a WAL, exactly like the worker does."""
    writer = WalWriter(directory, fsync="off", **writer_kw)

    def shadow(prev, snap):
        writer.append(delta_wal_record(prev, snap))
        writer.flush(force=True)

    store = SnapshotStore()
    store.on_publish(shadow)
    return store, shadow, writer


def _barrier(writer, store):
    rec = {"type": "ckpt"}
    snap = store.latest()
    if snap is not None:
        rec["snap"] = snapshot_wal_record(snap)
    writer.barrier(rec)


# --------------------------------------------------------------------------
# WAL tail-follow API
# --------------------------------------------------------------------------


def test_tailer_reads_records_in_order_across_rotation(tmp_path):
    w = WalWriter(str(tmp_path), segment_bytes=256, fsync="off")
    for i in range(50):
        w.append({"i": i})
    t = WalTailer(str(tmp_path), "t0")
    recs = t.poll()
    assert [r["i"] for r in recs] == list(range(50))
    assert w.stats()["segments_created"] > 1  # the range really rotated
    # idle poll: nothing new, no exception
    assert t.poll() == []
    w.append({"i": 50})
    assert [r["i"] for r in t.poll()] == [50]
    w.close()
    t.close()
    assert not os.path.exists(os.path.join(str(tmp_path), "tail-t0.ack"))


def test_tailer_holds_at_live_torn_tail_then_resumes(tmp_path):
    w = WalWriter(str(tmp_path), fsync="off")
    w.append({"i": 0})
    t = WalTailer(str(tmp_path), "t0")
    assert [r["i"] for r in t.poll()] == [0]
    # simulate the writer mid-append: a bare frame prefix at the newest
    # segment's tail must HOLD (no records, no exception), because the
    # writer may still complete it
    seq, path = list_segments(str(tmp_path))[-1]
    import struct
    import zlib

    payload = json.dumps({"i": 1}).encode()
    frame = struct.pack("<II", len(payload), zlib.crc32(payload)) + payload
    with open(path, "ab") as f:
        f.write(frame[: len(frame) // 2])
    assert t.poll() == []
    assert t.poll() == []  # stable: still holding
    with open(path, "ab") as f:
        f.write(frame[len(frame) // 2 :])
    assert [r["i"] for r in t.poll()] == [1]
    w.close()
    t.close()


def test_tailer_skips_tear_when_newer_segment_exists(tmp_path):
    w = WalWriter(str(tmp_path), fsync="off")
    w.append({"i": 0})
    seq, path = list_segments(str(tmp_path))[-1]
    w.close()
    # crash artifact: a frame prefix that can never complete, because a
    # newer incarnation already opened the next segment
    with open(path, "ab") as f:
        f.write(b"\x40\x00\x00\x00")  # header prefix only
    w2 = WalWriter(str(tmp_path), fsync="off")
    w2.append({"i": 1})
    t = WalTailer(str(tmp_path), "t0")
    assert [r["i"] for r in t.poll()] == [0, 1]
    assert t.stats()["partial_retries"] == 1
    w2.close()
    t.close()


def test_tailer_raises_on_real_corruption(tmp_path):
    import struct

    w = WalWriter(str(tmp_path), fsync="off")
    w.append({"i": 0})
    seq, path = list_segments(str(tmp_path))[-1]
    # full-length frame with a bad CRC: os.write prefix-atomicity means a
    # torn append can NEVER produce this — it is authoritative corruption
    payload = b'{"i": 1}'
    with open(path, "ab") as f:
        f.write(struct.pack("<II", len(payload), 0xDEAD) + payload)
    t = WalTailer(str(tmp_path), "t0")
    with pytest.raises(WalTailCorruption):
        t.poll()
    w.close()
    t.close()


def test_tailer_segment_pruned_midread_raises_gone(tmp_path):
    w = WalWriter(str(tmp_path), fsync="off")
    for i in range(4):
        w.append({"i": i})
    t = WalTailer(str(tmp_path), "t0")
    t.poll()  # tailer is now positioned mid-segment at the live tail
    first_seq = t.stats()["segment_seq"]
    w.close()
    w2 = WalWriter(str(tmp_path), fsync="off")  # newer segment appears
    os.unlink(os.path.join(str(tmp_path), "wal-%08d.log" % first_seq))
    with pytest.raises(WalSegmentGone):
        t.poll()
    w2.close()
    t.close()


def test_segment_first_record_peek(tmp_path):
    w = WalWriter(str(tmp_path), fsync="off")
    w.append({"type": "ckpt", "snap": {"version": 3}})
    w.append({"type": "delta"})
    seq, path = list_segments(str(tmp_path))[-1]
    rec = segment_first_record(path)
    assert rec is not None and rec["type"] == "ckpt"
    assert segment_first_record(path + ".missing") is None
    w.close()


# --------------------------------------------------------------------------
# satellite 1: retention handshake
# --------------------------------------------------------------------------


def test_barrier_without_tailer_prunes_unconsumed_segments(tmp_path):
    """The pre-handshake regression: with no registered tailer, a barrier
    deletes segments a follower had not consumed yet — a late tailer loses
    that data outright. This documents WHY the ack handshake exists."""
    w = WalWriter(str(tmp_path), segment_bytes=128, fsync="off")
    for i in range(20):
        w.append({"i": i})
    w.barrier({"type": "ckpt"})
    assert w.segments_truncated > 0  # history really was deleted
    t = WalTailer(str(tmp_path), "late")
    got = [r["i"] for r in t.poll() if "i" in r]
    assert len(got) < 20  # the late tailer lost pre-barrier records
    w.close()
    t.close()


def test_barrier_retains_segments_for_registered_tailer(tmp_path):
    w = WalWriter(str(tmp_path), segment_bytes=128, fsync="off")
    t = WalTailer(str(tmp_path), "live")  # registered BEFORE the traffic
    for i in range(20):
        w.append({"i": i})
    w.barrier({"type": "ckpt"})
    assert w.segments_retained > 0
    assert w.segments_truncated == 0  # nothing the tailer needs was cut
    got = [r["i"] for r in t.poll() if "i" in r]
    assert got == list(range(20))  # every frame, exactly once
    # once the tailer has acked past them, the next barrier prunes
    w.barrier({"type": "ckpt"})
    assert w.segments_truncated > 0
    w.close()
    t.close()


def test_retention_floor_ttl_expires_dead_tailers(tmp_path):
    w = WalWriter(str(tmp_path), segment_bytes=128, fsync="off",
                  tailer_ttl_s=60.0)
    t = WalTailer(str(tmp_path), "dead")
    for i in range(20):
        w.append({"i": i})
    assert tail_retention_floor(str(tmp_path)) == 0  # acked -1 -> needs 0
    # age the ack past the TTL: the tailer is presumed dead
    ack = os.path.join(str(tmp_path), "tail-dead.ack")
    os.utime(ack, (time.time() - 3600, time.time() - 3600))
    w.barrier({"type": "ckpt"})
    assert w.segments_truncated > 0  # retention no longer pinned
    assert not os.path.exists(ack)  # stale registration was withdrawn
    w.close()
    t.close()


# --------------------------------------------------------------------------
# satellite 4: segment rotation racing a live tailer
# --------------------------------------------------------------------------


@pytest.mark.parametrize("plan", [None, "slow@wal.rotate_during_tail:3"])
def test_rotation_racing_live_tailer_every_frame_exactly_once(tmp_path, plan):
    if plan is not None:
        install_plan(FaultPlan.parse(plan))
    n = 300
    w = WalWriter(str(tmp_path), segment_bytes=96, fsync="off")
    t = WalTailer(str(tmp_path), "race")
    err = []

    def produce():
        try:
            for i in range(n):
                w.append({"i": i})
        except Exception as e:  # pragma: no cover - diagnostic
            err.append(e)

    th = threading.Thread(target=produce)
    th.start()
    got = []
    deadline = time.monotonic() + 30.0
    while len(got) < n and time.monotonic() < deadline:
        got.extend(r["i"] for r in t.poll())
    th.join()
    assert not err
    assert got == list(range(n))  # exactly once, in order, nothing torn
    w.close()
    t.close()


# --------------------------------------------------------------------------
# byte-exact delta records (the replication currency)
# --------------------------------------------------------------------------


def test_delta_record_reproduces_reordered_bytes(rng):
    store = SnapshotStore()
    recs = []
    store.on_publish(lambda prev, snap: recs.append(delta_wal_record(prev, snap)))
    a = rng.random((40, 3)).astype(np.float32)
    store.publish(a)
    # next version keeps a permuted subset of a's rows plus new ones: the
    # record must carry the permutation so a follower reproduces the BYTES
    keep = a[rng.permutation(40)[:25]]
    b = np.concatenate([rng.random((10, 3)).astype(np.float32), keep])
    store.publish(b)
    assert "perm" in recs[1] or "rows" in recs[1]
    folded = apply_delta_record(a, recs[1])
    assert folded.tobytes() == store.latest().points.tobytes()


def test_delta_record_duplicate_rows_fall_back_to_full_copy(rng):
    store = SnapshotStore()
    recs = []
    store.on_publish(lambda prev, snap: recs.append(delta_wal_record(prev, snap)))
    a = rng.random((10, 3)).astype(np.float32)
    store.publish(a)
    dup = np.concatenate([a[:4], a[:4]])  # duplicates defy a permutation
    store.publish(dup)
    assert "rows" in recs[1]
    folded = apply_delta_record(a, recs[1])
    assert folded.tobytes() == store.latest().points.tobytes()


# --------------------------------------------------------------------------
# replica: bootstrap, live tail, byte identity
# --------------------------------------------------------------------------


def test_replica_bootstraps_from_barrier_and_tails_byte_exact(tmp_path, rng):
    store, _, writer = _primary(str(tmp_path))
    for _ in range(3):
        store.publish(rng.random((30, 3)).astype(np.float32))
    _barrier(writer, store)
    for _ in range(2):
        store.publish(rng.random((30, 3)).astype(np.float32))
    rep = SkylineReplica(str(tmp_path), start=False)
    try:
        rep.bootstrap()
        assert rep.store.head_version == store.head_version == 5
        assert rep.store.latest().points.tobytes() == \
            store.latest().points.tobytes()
        assert rep.store.latest().digest == store.latest().digest
        assert rep.store.restored  # no live-tailed publish confirmed it yet
        # live tail: each publish folds in byte-exactly
        for _ in range(4):
            store.publish(rng.random((30, 3)).astype(np.float32))
            rep.apply_available()
            assert rep.store.head_version == store.head_version
            assert rep.store.latest().points.tobytes() == \
                store.latest().points.tobytes()
        assert not rep.store.restored  # live publishes supersede recovery
    finally:
        rep.close()
        writer.close()


def test_replica_http_bytes_identical_to_primary(tmp_path, rng):
    store, _, writer = _primary(str(tmp_path))
    primary_srv = SkylineServer(store, port=0)
    rep = SkylineReplica(str(tmp_path), start=False)
    try:
        for _ in range(3):
            store.publish(rng.random((50, 4)).astype(np.float32))
            rep.apply_available()
        # format=csv bodies are purely snapshot-derived (no volatile age
        # tail): the strongest equality the HTTP surface can state
        _, pb, ph = _get_raw(
            f"http://127.0.0.1:{primary_srv.port}/skyline?format=csv")
        _, rb, rh = _get_raw(
            f"http://127.0.0.1:{rep.port}/skyline?format=csv")
        assert ph["X-Skyline-Version"] == rh["X-Skyline-Version"]
        assert hashlib.sha256(pb).hexdigest() == hashlib.sha256(rb).hexdigest()
        assert ph["X-Skyline-Digest"] == rh["X-Skyline-Digest"]
    finally:
        rep.close()
        primary_srv.close()
        writer.close()


def test_replica_divergence_on_chain_break(tmp_path, rng):
    store, _, writer = _primary(str(tmp_path))
    store.publish(rng.random((10, 3)).astype(np.float32))
    rep = SkylineReplica(str(tmp_path), start=False)
    try:
        rep.apply_available()
        with pytest.raises(ReplicaDivergence):
            rep._apply({
                "type": "delta", "from": 7, "to": 8, "wm": -1, "d": 3,
                "entered": "", "left": "",
            })
    finally:
        rep.close()
        writer.close()


# --------------------------------------------------------------------------
# staleness fence
# --------------------------------------------------------------------------


def test_staleness_fence_refuses_old_reads_with_503(rng):
    store = SnapshotStore()
    srv = SkylineServer(store, port=0, max_stale_ms=100.0, role="replica")
    try:
        # a snapshot published 60s ago: way past the fence
        store.publish(rng.random((10, 3)).astype(np.float32),
                      now_ms=time.time() * 1000.0 - 60_000.0)
        url = f"http://127.0.0.1:{srv.port}/skyline"
        code, doc, headers = _get(url)
        assert code == 503
        assert doc["stale"] is True and doc["role"] == "replica"
        assert doc["staleness_ms"] > doc["max_stale_ms"] == 100.0
        assert "Retry-After" in headers
        # allow_stale bounds the CLIENT's tolerance — it never overrides
        # the server's own honesty fence
        code, doc, _ = _get(url + "?allow_stale=1&max_age_ms=600000")
        assert code == 503 and doc["error"] == "staleness fence exceeded"
        assert srv.admission.counters.snapshot()["fence_rejected"] == 2
        # healthz still answers (the fence guards data, not liveness)
        code, doc, _ = _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert code == 200 and doc["role"] == "replica"
        # a fresh publish clears the fence
        store.publish(rng.random((10, 3)).astype(np.float32))
        code, doc, _ = _get(url)
        assert code == 200 and doc["staleness_ms"] <= 100.0
    finally:
        srv.close()


# --------------------------------------------------------------------------
# satellite 2: /deltas past ring capacity -> explicit resync marker
# --------------------------------------------------------------------------


def test_deltas_resync_marker_past_ring_capacity(rng):
    store = SnapshotStore()
    ring = DeltaRing(store, capacity=2)
    srv = SkylineServer(store, deltas=ring, port=0)
    try:
        for _ in range(5):
            store.publish(rng.random((10, 3)).astype(np.float32))
        base = f"http://127.0.0.1:{srv.port}/deltas"
        code, doc, _ = _get(base + "?since=1")  # fell off the 2-deep ring
        assert code == 410
        assert doc["resync"] is True and doc["head_version"] == 5
        code, doc, _ = _get(base + "?since=4")
        assert code == 200 and doc["resync"] is False
        assert doc["to_version"] == 5 and doc["staleness_ms"] is not None
    finally:
        srv.close()


# --------------------------------------------------------------------------
# SSE push (/subscribe)
# --------------------------------------------------------------------------


def _sse_connect(port, query=""):
    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    s.sendall(
        f"GET /subscribe{query} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
    )
    f = s.makefile("rb")
    status = f.readline()
    assert b"200" in status, status
    while f.readline() not in (b"\r\n", b"\n", b""):
        pass  # drain headers
    return s, f


def _sse_read_event(f, timeout_s=10.0):
    """Next (event, data_doc) pair, skipping keepalive comments."""
    kind = data = None
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            line = f.readline()
        except OSError:
            return None
        if not line:
            return None
        line = line.strip()
        if line.startswith(b":"):
            continue
        if line.startswith(b"event:"):
            kind = line.split(b":", 1)[1].strip().decode()
        elif line.startswith(b"data:"):
            data = json.loads(line.split(b":", 1)[1].strip())
        elif not line and kind is not None:
            return kind, data
    return None


def _wait_for_subscribers(srv, n=1, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if len(srv._sse_queues) >= n:
            return True
        time.sleep(0.01)
    return False


def test_sse_subscribe_pushes_deltas(rng):
    store = SnapshotStore()
    ring = DeltaRing(store)
    srv = SkylineServer(store, deltas=ring, port=0)
    try:
        store.publish(rng.random((8, 3)).astype(np.float32))
        s, f = _sse_connect(srv.port)
        assert _wait_for_subscribers(srv)
        a = rng.random((8, 3)).astype(np.float32)
        store.publish(a)
        kind, doc = _sse_read_event(f)
        assert kind == "delta"
        assert doc["from_version"] == 1 and doc["to_version"] == 2
        assert doc["entered"]  # the new rows rode the push
        s.close()
    finally:
        srv.close()


def test_sse_since_catchup_and_overflow_resync(rng, monkeypatch):
    monkeypatch.setenv("SKYLINE_SERVE_SSE_QUEUE", "1")
    store = SnapshotStore()
    ring = DeltaRing(store, capacity=2)
    srv = SkylineServer(store, deltas=ring, port=0)
    try:
        for _ in range(5):
            store.publish(rng.random((8, 3)).astype(np.float32))
        # ?since= fell off the ring: the FIRST event must be an explicit
        # resync marker, not silence (satellite 2's push-side surface)
        s, f = _sse_connect(srv.port, "?since=1")
        kind, doc = _sse_read_event(f)
        assert kind == "resync" and doc["head_version"] == 5
        # overflow: a 1-deep queue with a subscriber that cannot keep up
        # drops to a resync signal instead of silently losing deltas. Park
        # the event loop briefly so all fanouts land before the consumer
        # coroutine can drain — deterministic backpressure.
        assert _wait_for_subscribers(srv)
        srv._loop.call_soon_threadsafe(time.sleep, 0.3)
        for _ in range(6):
            store.publish(rng.random((8, 3)).astype(np.float32))
        kinds = []
        for _ in range(8):
            ev = _sse_read_event(f, timeout_s=5.0)
            if ev is None:
                break
            kinds.append(ev[0])
            if ev[0] == "resync":
                break
        assert "resync" in kinds
        s.close()
    finally:
        srv.close()


# --------------------------------------------------------------------------
# satellite 3: degraded answers are never laundered by replication
# --------------------------------------------------------------------------


def test_degraded_meta_propagates_byte_faithfully(tmp_path, rng):
    store, _, writer = _primary(str(tmp_path))
    store.publish(rng.random((10, 3)).astype(np.float32))
    # a PR-14 degraded publish: partial answer with excluded chips
    store.publish(rng.random((10, 3)).astype(np.float32),
                  partial=True, excluded_chips=[1, 3])
    rep = SkylineReplica(str(tmp_path), start=False)
    try:
        rep.apply_available()
        snap = rep.store.latest()
        assert snap.meta == {"partial": True, "excluded_chips": [1, 3]}
        code, doc, _ = _get(f"http://127.0.0.1:{rep.port}/skyline?points=0")
        assert code == 200
        assert doc["partial"] is True  # meta flattens into the read doc
        assert doc["excluded_chips"] == [1, 3]
        # the degraded head survives a checkpoint barrier + re-bootstrap
        # honestly (never laundered clean by recovery)
        _barrier(writer, store)
        rep2 = SkylineReplica(str(tmp_path), start=False,
                              replica_id="rep2")
        try:
            rep2.bootstrap()
            assert rep2.store.latest().meta == {
                "partial": True, "excluded_chips": [1, 3],
            }
            code, doc, _ = _get(
                f"http://127.0.0.1:{rep2.port}/skyline?points=0")
            assert doc["partial"] is True
            assert doc["restored"] is True  # recovery marked, not hidden
        finally:
            rep2.close()
        # a clean publish clears the degraded mark on the tail too
        store.publish(rng.random((10, 3)).astype(np.float32))
        rep.apply_available()
        assert rep.store.latest().meta == {}
    finally:
        rep.close()
        writer.close()


# --------------------------------------------------------------------------
# supervised failover + fault points
# --------------------------------------------------------------------------


def test_replica_fault_points_registered():
    from skyline_tpu.resilience.faults import KILL_POINTS

    for point in ("replica.tail", "replica.restore",
                  "wal.rotate_during_tail"):
        assert point in KILL_POINTS


def test_replica_tail_crash_is_supervised(tmp_path, rng):
    store, _, writer = _primary(str(tmp_path))
    store.publish(rng.random((10, 3)).astype(np.float32))
    install_plan(FaultPlan.parse("crash@replica.tail:1"))
    rep = SkylineReplica(str(tmp_path), poll_interval_s=0.005,
                         backoff_base_s=0.01, start=True)
    try:
        store.publish(rng.random((10, 3)).astype(np.float32))
        assert rep.wait_for_version(2, timeout_s=10.0)
        assert rep.supervisor.stats()["restarts"] >= 1
        assert rep.store.latest().points.tobytes() == \
            store.latest().points.tobytes()
    finally:
        rep.close()
        writer.close()


def test_replica_corruption_rebootstraps_and_converges(tmp_path, rng):
    import struct

    store, _, writer = _primary(str(tmp_path))
    for _ in range(3):
        store.publish(rng.random((20, 3)).astype(np.float32))
    rep = SkylineReplica(str(tmp_path), poll_interval_s=0.005, start=True)
    try:
        assert rep.wait_for_version(3, timeout_s=10.0)
        # corrupt the live segment under the tailer: a full-length frame
        # with a bad CRC (what bitrot looks like, not what a torn write
        # looks like)
        seq, path = list_segments(str(tmp_path))[-1]
        payload = b'{"type":"delta"}'
        with open(path, "ab") as f:
            f.write(struct.pack("<II", len(payload), 0xBAD) + payload)
        deadline = time.monotonic() + 10.0
        while rep.rebootstraps == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rep.rebootstraps >= 1
        # replica keeps serving its last verified state while damaged
        code, doc, _ = _get(f"http://127.0.0.1:{rep.port}/skyline?points=0")
        assert code == 200 and doc["version"] == 3
        # the primary's next barrier lands past the damage; the replica's
        # re-bootstrap converges from it
        _barrier(writer, store)
        store.publish(rng.random((20, 3)).astype(np.float32))
        assert rep.wait_for_version(4, timeout_s=10.0)
        assert rep.store.latest().points.tobytes() == \
            store.latest().points.tobytes()
    finally:
        rep.close()
        writer.close()


# --------------------------------------------------------------------------
# per-tenant admission
# --------------------------------------------------------------------------


def test_per_tenant_buckets_shed_independently(rng, prom_parse):
    store = SnapshotStore()
    cfg = ServeConfig(tenant_rate=0.001, tenant_burst=2)
    srv = SkylineServer(store, admission=cfg.admission(), port=0)
    try:
        store.publish(rng.random((10, 3)).astype(np.float32))
        url = f"http://127.0.0.1:{srv.port}/skyline?points=0"
        codes_a = [
            _get(url, headers={"X-Tenant": "alpha"})[0] for _ in range(5)
        ]
        # alpha burned its 2-token burst; later reads shed with 429
        assert codes_a[:2] == [200, 200]
        assert 429 in codes_a[2:]
        # beta's bucket is untouched by alpha's burn
        code_b, _, _ = _get(url, headers={"X-Tenant": "beta"})
        assert code_b == 200
        # anonymous reads bypass tenant buckets entirely
        assert _get(url)[0] == 200
        ts = srv.admission.tenant_stats()
        assert ts["alpha"]["shed"] >= 1 and ts["beta"]["shed"] == 0
        # labeled per-tenant counter families on /metrics
        _, body, _ = _get_raw(f"http://127.0.0.1:{srv.port}/metrics")
        series = prom_parse(body.decode())
        shed = {
            labels["tenant"]: v
            for labels, v in series["skyline_serve_tenant_reads_shed_total"]
        }
        admitted = {
            labels["tenant"]: v
            for labels, v in
            series["skyline_serve_tenant_reads_admitted_total"]
        }
        assert shed["alpha"] >= 1
        assert admitted["beta"] >= 1
    finally:
        srv.close()


def test_tenant_slo_burn_row(rng):
    from skyline_tpu.telemetry.slo import SloEngine

    tel = Telemetry()
    t = {"now": 0.0}
    slo = SloEngine(tel, clock=lambda: t["now"])
    cfg = ServeConfig(tenant_rate=0.001, tenant_burst=1)
    adm = cfg.admission()
    slo.attach_admission(adm)
    for _ in range(10):
        adm.admit_read(tenant="alpha")
    doc = slo.evaluate()
    row = doc["slos"]["tenant_shed_fraction"]
    assert row["kind"] == "fraction"
    assert row["windows"]["fast"]["bad"] >= 1
    assert doc["tenants"]["alpha"]["shed"] >= 1
    assert doc["tenants"]["alpha"]["shed_fraction"] > 0


# --------------------------------------------------------------------------
# config / CLI wiring
# --------------------------------------------------------------------------


def test_replica_flags_parse_and_validate(tmp_path):
    from skyline_tpu.utils.config import parse_job_args

    cfg = parse_job_args(["--replica-of", str(tmp_path)])
    assert cfg.replica_of == str(tmp_path)
    cfg = parse_job_args([
        "--replicas", "2", "--checkpoint-dir", str(tmp_path), "--serve", "0",
    ])
    assert cfg.replicas == 2
    with pytest.raises(ValueError):
        parse_job_args(["--replicas", "2"])  # needs --checkpoint-dir
    with pytest.raises(ValueError):
        parse_job_args([
            "--replicas", "1", "--checkpoint-dir", str(tmp_path), "--serve",
            "0", "--replica-of", str(tmp_path),
        ])


# --------------------------------------------------------------------------
# chaos acceptance: engine kill mid-burst
# --------------------------------------------------------------------------


def test_chaos_engine_kill_replicas_stay_honest(tmp_path, rng):
    store, shadow, writer = _primary(str(tmp_path))
    primary_srv = SkylineServer(store, port=0)
    # replica A: generous fence (keeps answering, honestly aging);
    # replica B: 250ms fence (must refuse once the primary is gone)
    rep_a = SkylineReplica(str(tmp_path), replica_id="rep-a",
                           poll_interval_s=0.005, start=True)
    rep_b = SkylineReplica(str(tmp_path), replica_id="rep-b",
                           poll_interval_s=0.005, max_stale_ms=250.0,
                           start=True)
    stop_readers = threading.Event()
    reader_errors = []

    def reader(port):
        while not stop_readers.is_set():
            try:
                code, doc, _ = _get(
                    f"http://127.0.0.1:{port}/skyline?points=0", timeout=5)
            except Exception as e:  # pragma: no cover - diagnostic
                reader_errors.append(repr(e))
                return
            if code == 200 and doc.get("staleness_ms") is None:
                reader_errors.append("200 without staleness watermark")
                return
            time.sleep(0.002)

    threads = [
        threading.Thread(target=reader, args=(p,))
        for p in (rep_a.port, rep_b.port)
        for _ in range(4)
    ]
    writer_lock = threading.Lock()
    try:
        for t in threads:
            t.start()
        # burst: publishes land while readers hammer both replicas; verify
        # byte identity with the primary at every common version
        for v in range(1, 9):
            store.publish(rng.random((64, 4)).astype(np.float32))
            assert rep_a.wait_for_version(v, timeout_s=10.0)
            assert rep_b.wait_for_version(v, timeout_s=10.0)
            _, pb, ph = _get_raw(
                f"http://127.0.0.1:{primary_srv.port}/skyline?format=csv")
            for rep in (rep_a, rep_b):
                _, rb, rh = _get_raw(
                    f"http://127.0.0.1:{rep.port}/skyline?format=csv")
                assert rh["X-Skyline-Version"] == ph["X-Skyline-Version"]
                assert hashlib.sha256(rb).hexdigest() == \
                    hashlib.sha256(pb).hexdigest()
        # ---- kill the engine mid-burst ----
        writer.close()
        primary_srv.close()
        # replica A: answers keep flowing with monotonically aging,
        # honestly-reported staleness
        stalenesses = []
        for _ in range(5):
            code, doc, _ = _get(
                f"http://127.0.0.1:{rep_a.port}/skyline?points=0")
            assert code == 200
            stalenesses.append(doc["staleness_ms"])
            time.sleep(0.05)
        assert stalenesses == sorted(stalenesses)
        assert stalenesses[-1] > stalenesses[0]
        # replica B: past its 250ms fence every answer is an honest 503 —
        # zero 200s once the fence is crossed
        time.sleep(0.3)
        for _ in range(5):
            code, doc, _ = _get(
                f"http://127.0.0.1:{rep_b.port}/skyline?points=0")
            assert code == 503 and doc["stale"] is True
        # ---- primary restarts (same store, fresh WAL incarnation) ----
        writer2 = WalWriter(str(tmp_path), fsync="off")

        def shadow2(prev, snap):
            with writer_lock:
                writer2.append(delta_wal_record(prev, snap))
                writer2.flush(force=True)

        store._subscribers = [shadow2]  # replace the dead writer's hook
        try:
            for v in range(9, 12):
                store.publish(rng.random((64, 4)).astype(np.float32))
            # replicas reconverge through the tail alone: no re-bootstrap
            assert rep_a.wait_for_version(11, timeout_s=10.0)
            assert rep_b.wait_for_version(11, timeout_s=10.0)
            for rep in (rep_a, rep_b):
                assert rep.rebootstraps == 0
                assert rep.bootstraps == 1
                assert rep.store.latest().points.tobytes() == \
                    store.latest().points.tobytes()
            # B's fence clears with fresh data
            code, _, _ = _get(
                f"http://127.0.0.1:{rep_b.port}/skyline?points=0")
            assert code == 200
        finally:
            writer2.close()
    finally:
        stop_readers.set()
        for t in threads:
            t.join(timeout=10)
        rep_a.close()
        rep_b.close()
    assert not reader_errors, reader_errors


# --------------------------------------------------------------------------
# worker integration: in-process replicas
# --------------------------------------------------------------------------


def test_worker_spawns_replicas_and_they_track_publishes(tmp_path, rng):
    from skyline_tpu.bridge import MemoryBus, SkylineWorker
    from skyline_tpu.bridge.wire import format_trigger, format_tuple_line
    from skyline_tpu.resilience import ResilienceConfig
    from skyline_tpu.stream import EngineConfig

    bus = MemoryBus()
    worker = SkylineWorker(
        bus,
        EngineConfig(parallelism=2, algo="mr-angle", dims=3,
                     domain_max=10000.0, buffer_size=512),
        serve_port=0,
        serve_config=ServeConfig(),
        resilience=ResilienceConfig(checkpoint_dir=str(tmp_path),
                                    wal_fsync="off"),
        replicas=2,
    )
    try:
        assert len(worker.replicas) == 2
        pts = rng.random((400, 3)).astype(np.float32) * 10000.0
        bus.produce_many(
            "input-tuples",
            [format_tuple_line(i, row) for i, row in enumerate(pts)],
        )
        bus.produce("queries", format_trigger(0, 0))
        while worker.step() > 0:
            pass
        head = worker.serve_server.store.head_version
        assert head >= 1
        for rep in worker.replicas:
            assert rep.wait_for_version(head, timeout_s=10.0)
            assert rep.store.latest().points.tobytes() == \
                worker.serve_server.store.latest().points.tobytes()
            code, doc, _ = _get(
                f"http://127.0.0.1:{rep.port}/healthz")
            assert code == 200 and doc["role"] == "replica"
    finally:
        worker.close()
