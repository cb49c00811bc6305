#!/usr/bin/env bash
# Smoke the observability plane end-to-end on one host, no broker, no TPU:
# a SkylineWorker over the in-memory bus with BOTH HTTP surfaces up
# (--stats-port 0 and --serve 0) plus --trace-out, then assert
#   * GET /metrics on the stats server AND the serve server parses as
#     Prometheus text exposition (minimal inline parser),
#   * GET /trace is Chrome trace-event JSON carrying the ingest -> local
#     -> merge -> publish spans of the query just answered,
#   * /stats carries latency_ms histogram summaries (p50/p99 tiles),
#   * the --trace-out file written on close() validates the same way,
# then run the per-plane legs and the mesh, chaos and lint gates.
#
#   scripts/obs_smoke.sh
#
# Exits non-zero on any failed assertion. CPU-only (JAX_PLATFORMS=cpu).
set -euo pipefail
cd "$(dirname "$0")/.."

TRACE_OUT="$(mktemp -d)/obs_smoke_trace.json"
export TRACE_OUT

JAX_PLATFORMS=cpu python - <<'EOF'
import json
import os
import urllib.request

import numpy as np

from skyline_tpu.bridge import MemoryBus, SkylineWorker
from skyline_tpu.bridge.wire import format_trigger, format_tuple_line
from skyline_tpu.utils.config import parse_job_args
from skyline_tpu.workload.generators import anti_correlated

trace_out = os.environ["TRACE_OUT"]


def parse_prom(text):
    """Minimal Prometheus text parser: {name: [(labels, value), ...]}."""
    series = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        assert head and val, f"malformed sample line: {line!r}"
        if "{" in head:
            name, _, rest = head.partition("{")
            assert rest.endswith("}"), f"malformed labels: {line!r}"
        else:
            name = head
        float(val)  # must parse
        series.setdefault(name, []).append(val)
    assert series, "no samples in exposition"
    return series


cfg = parse_job_args(
    ["--serve", "0", "--stats-port", "0", "--parallelism", "2",
     "--dims", "3", "--trace-out", trace_out]
)
bus = MemoryBus()
worker = SkylineWorker(
    bus,
    cfg.engine_config(),
    stats_port=cfg.stats_port,
    serve_port=cfg.serve_port,
    serve_config=cfg.serve_config(),
    trace_out=cfg.trace_out,
)
try:
    rng = np.random.default_rng(7)
    x = anti_correlated(rng, 3000, 3, 0, 10000)
    bus.produce_many(
        "input-tuples",
        [format_tuple_line(i, row) for i, row in enumerate(x)],
    )
    bus.produce("queries", format_trigger(0, 0))
    while worker.step() > 0:
        pass

    stats_base = f"http://127.0.0.1:{worker.stats_server.port}"
    serve_base = f"http://127.0.0.1:{worker.serve_server.port}"

    # serve a read so serve_read_ms has a sample too
    with urllib.request.urlopen(f"{serve_base}/skyline", timeout=5) as r:
        assert json.load(r)["version"] == 1

    # read-side result cache: an identical second read serves the cached
    # serialized body (and still carries the per-read volatile fields)
    with urllib.request.urlopen(f"{serve_base}/skyline", timeout=5) as r:
        doc = json.load(r)
        assert doc["version"] == 1 and "age_ms" in doc and "stale" in doc

    # merge cache + snapshot dedupe: a second trigger over UNCHANGED state
    # must hit the epoch-keyed merge cache and dedupe the publish (the
    # snapshot version stays 1 — no spurious delta, no history churn)
    bus.produce("queries", format_trigger(1, 0))
    while worker.step() > 0:
        pass
    with urllib.request.urlopen(f"{serve_base}/skyline", timeout=5) as r:
        assert json.load(r)["version"] == 1, "dedupe minted a version"

    with urllib.request.urlopen(f"{stats_base}/stats", timeout=5) as r:
        stats = json.load(r)
    mc = stats["merge_cache"]
    assert mc["hits"] >= 1 and mc["misses"] >= 1, mc
    assert stats["serve"]["read_cache_hits"] >= 1, stats["serve"]
    assert stats["snapshot_store"]["deduped"] >= 1, stats["snapshot_store"]
    print(f"[obs-smoke] merge cache ok: {mc['hits']} hit(s), "
          f"{stats['serve']['read_cache_hits']} read-cache hit(s), "
          f"{stats['snapshot_store']['deduped']} publish dedupe(s)")

    for label, base in (("stats", stats_base), ("serve", serve_base)):
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
            ctype = r.headers.get("Content-Type", "")
            series = parse_prom(r.read().decode())
        assert "version=0.0.4" in ctype, ctype
        assert any(k.startswith("skyline_") for k in series), sorted(series)
        print(f"[obs-smoke] {label} /metrics ok: {len(series)} series")
    with urllib.request.urlopen(f"{stats_base}/metrics", timeout=5) as r:
        body = r.read().decode()
    for want in ("skyline_ingest_batch_ms_bucket",
                 "skyline_query_latency_ms_count",
                 "skyline_merge_cache_hit_total",
                 "skyline_merge_cache_miss_total",
                 # tournament-tree merge (dims 3 > 2, so the tree ran and
                 # registered its series even if nothing got pruned)
                 "skyline_merge_tree_levels_total",
                 "skyline_merge_partitions_pruned_total",
                 # flush cascade (dims 3 > 2, so the grid prefilter ran at
                 # flush and registered its series even with zero drops;
                 # bf16_resolved registers whenever mixed precision is on
                 # and /stats above harvested the device counter)
                 "skyline_flush_prefilter_dropped_total",
                 "skyline_flush_bf16_resolved_total",
                 # freshness lineage (ISSUE 8): per-stage lag histograms as
                 # one labeled family, plus the span-ring drop counter and
                 # compile-cache effectiveness (always exported, zeros incl.)
                 "skyline_freshness_lag_ms_bucket",
                 "skyline_telemetry_spans_dropped_total",
                 "skyline_compile_cache_hits_total",
                 "skyline_compile_cache_misses_total",
                 # EXPLAIN plane (ISSUE 9): per-query plans recorded
                 # (registered at engine ctor, so exported even at zero)
                 "skyline_explain_records_total",
                 # audit plane (ISSUE 10): shadow-verification totals
                 # (registered at engine ctor, so exported even at zero)
                 "skyline_audit_checks_total",
                 "skyline_audit_divergence_total"):
        assert want in body, f"{want} missing from exposition"
    for stage in ("ingest", "flush", "merge", "publish", "read"):
        assert f'stage="{stage}"' in body, \
            f"freshness stage {stage!r} missing from exposition"
    with urllib.request.urlopen(f"{serve_base}/metrics", timeout=5) as r:
        serve_body = r.read().decode()
    assert "skyline_serve_read_cache_hits_total" in serve_body, \
        "read-cache counter missing from serve exposition"

    with urllib.request.urlopen(f"{stats_base}/stats", timeout=5) as r:
        stats = json.load(r)
    lat = stats["latency_ms"]
    assert lat["query_latency_ms"]["count"] >= 1, lat
    assert "p99" in lat["query_latency_ms"], lat
    print(f"[obs-smoke] /stats latency tiles ok: "
          f"{[k for k, v in lat.items() if v['count'] > 0]}")

    # per-kernel profile: the answered queries above dispatched real merge
    # kernels, so the registry must be non-empty on BOTH surfaces
    for base in (stats_base, serve_base):
        with urllib.request.urlopen(f"{base}/profile", timeout=5) as r:
            prof = json.load(r)
        assert prof["signatures"] >= 1 and prof["kernels"], prof
        assert prof["dispatches"] >= prof["signatures"], prof
    variants = {k["variant"] for k in prof["kernels"]}
    print(f"[obs-smoke] /profile ok: {prof['signatures']} signature(s), "
          f"{prof['dispatches']} dispatch(es), variants={sorted(variants)}")

    # SLO burn-rate table: well-formed, every declared SLO evaluated over
    # both windows, and nothing breaching on this tiny healthy run
    with urllib.request.urlopen(f"{stats_base}/slo", timeout=5) as r:
        slo = json.load(r)
    assert slo["ok"] is True, slo
    assert set(slo["slos"]) == {"read_p99", "freshness_p99",
                                "shed_fraction", "restart_rate",
                                "audit_divergence", "degraded_answers",
                                "tenant_shed_fraction",
                                "replication_lag_p99", "promote_p99"}, slo
    for name, s in slo["slos"].items():
        assert {"fast", "slow"} <= set(s["windows"]), (name, s)
        assert s["breach"] is False, (name, s)
    print(f"[obs-smoke] /slo ok: {len(slo['slos'])} SLOs, no breach")

    # ops plane (ISSUE 17): /ops and /cluster/overview answer on BOTH
    # surfaces — probe-friendly on this flat worker (no WAL directory, no
    # fleet membership), never a 404; the live-journal path is exercised
    # in the replica leg below and in scripts/chaos_smoke.sh
    for base in (stats_base, serve_base):
        with urllib.request.urlopen(f"{base}/ops", timeout=5) as r:
            doc = json.load(r)
        assert doc == {"ok": True, "enabled": False}, doc
        with urllib.request.urlopen(f"{base}/cluster/overview",
                                    timeout=5) as r:
            doc = json.load(r)
        assert doc["ok"] is True and doc["enabled"] is False, doc
    print("[obs-smoke] /ops + /cluster/overview probe-friendly on both "
          "surfaces (plane off)")

    # EXPLAIN plane (ISSUE 9): every answered query left a complete plan
    # in the ring; both surfaces serve it and /skyline inlines it. The
    # second (deduped) trigger is the latest plan: a cache hit republished
    # as version 1.
    for base in (stats_base, serve_base):
        with urllib.request.urlopen(f"{base}/explain", timeout=5) as r:
            plan = json.load(r)
        for block in ("merge", "cascade", "kernels", "publish", "timing"):
            assert plan.get(block) is not None, (base, block, plan)
        assert plan["merge"]["path"] == "cache_hit", plan["merge"]
        assert plan["publish"]["version"] == 1, plan["publish"]
        assert plan["publish"]["deduped"] is True, plan["publish"]
        assert plan["trace_id"], plan
        with urllib.request.urlopen(f"{base}/explain?version=1",
                                    timeout=5) as r:
            assert json.load(r)["publish"]["version"] == 1
    with urllib.request.urlopen(f"{serve_base}/skyline?explain=1",
                                timeout=5) as r:
        inline = json.load(r)["explain"]
    assert inline["trace_id"] == plan["trace_id"], inline
    assert stats["explain"]["recorded_total"] >= 2, stats["explain"]
    print(f"[obs-smoke] /explain ok: {stats['explain']['recorded_total']} "
          f"plan(s), latest path={plan['merge']['path']} "
          f"(v{plan['publish']['version']}, deduped)")

    # audit plane (ISSUE 10): every answer above was shadow-verified
    # against the host oracle at publish time (sample defaults to 1.0),
    # and one canary sweep proves every merge decision path — with zero
    # divergence across the lot
    worker.engine.auditor.run_canaries()
    for base in (stats_base, serve_base):
        with urllib.request.urlopen(f"{base}/audit", timeout=5) as r:
            audit = json.load(r)
        assert audit["ok"] is True, audit
        assert audit["checks_total"] >= 2 + 5, audit  # organic + canaries
        assert audit["divergence_total"] == 0, audit
        assert set(audit["canaries"]) == {
            "flat", "tree", "cache_hit", "tree_delta", "host",
        }, audit["canaries"]
        assert all(c["last_ok"] for c in audit["canaries"].values()), audit
        # the trace join back into /explain and /trace: an organic check
        # answers under its audited snapshot's trace_id (the dedupe kept
        # the FIRST query's snapshot, so join on the ring's own record)
        organic = [c for c in worker.telemetry.audit.snapshot()
                   if c["kind"] == "organic"]
        assert organic and organic[-1]["trace_id"], organic
        with urllib.request.urlopen(
            f"{base}/audit?trace_id={organic[-1]['trace_id']}", timeout=5
        ) as r:
            assert json.load(r)["ok"] is True
    print(f"[obs-smoke] /audit ok: {audit['checks_total']} check(s), "
          f"0 divergence, canary paths {sorted(audit['canaries'])}")

    # flight recorder: flushes + merges above left dispatch decisions in
    # the ring
    with urllib.request.urlopen(f"{stats_base}/debug/flight", timeout=5) as r:
        flight = json.load(r)
    kinds = {e["kind"] for e in flight["entries"]}
    assert "merge.launch" in kinds, sorted(kinds)
    print(f"[obs-smoke] /debug/flight ok: {flight['recorded_total']} "
          f"decision(s), kinds={sorted(kinds)}")

    # freshness lineage end-to-end: all five stages saw samples
    with urllib.request.urlopen(f"{stats_base}/stats", timeout=5) as r:
        fr = json.load(r)["freshness"]
    counts = {s: fr["stages"][s]["count"] for s in fr["stages"]}
    assert all(c >= 1 for c in counts.values()), counts
    assert fr["published_wm_ms"] is not None, fr
    print(f"[obs-smoke] freshness lineage ok: stage samples {counts}")

    with urllib.request.urlopen(f"{stats_base}/trace", timeout=5) as r:
        doc = json.load(r)
    names = {e["name"] for e in doc["traceEvents"]}
    for want in ("ingest", "local", "merge", "publish", "query"):
        assert want in names, (want, names)
    for e in doc["traceEvents"]:
        assert e["ph"] == "X" and "ts" in e and "dur" in e, e
    print(f"[obs-smoke] /trace ok: {len(doc['traceEvents'])} events")
finally:
    worker.close()

# close() wrote the span ring as a Chrome trace file
with open(trace_out) as f:
    doc = json.load(f)
names = {e["name"] for e in doc["traceEvents"]}
for want in ("ingest", "local", "merge", "publish"):
    assert want in names, (want, names)
print(f"[obs-smoke] --trace-out ok: {len(doc['traceEvents'])} events "
      f"at {trace_out} (load at https://ui.perfetto.dev)")
print("[obs-smoke] PASS")
EOF

# pruned tournament-tree merge: the witness prefilter must not change a
# single output byte — merge identical state with pruning on and off and
# compare the emitted point buffers digest-for-digest
JAX_PLATFORMS=cpu python - <<'EOF'
import os

import numpy as np

from skyline_tpu.stream.batched import PartitionSet
from skyline_tpu.workload.generators import anti_correlated

os.environ["SKYLINE_MERGE_CACHE"] = "0"
os.environ["SKYLINE_MERGE_TREE"] = "1"
digests = {}
for prune in ("1", "0"):
    os.environ["SKYLINE_MERGE_PRUNE"] = prune
    rng = np.random.default_rng(23)
    pset = PartitionSet(4, 3)
    x = anti_correlated(rng, 4000, 3, 0, 10000).astype(np.float32)
    pids = rng.integers(0, 4, len(x))
    for p in range(4):
        rows = np.ascontiguousarray(x[pids == p])
        if rows.shape[0]:
            pset.add_batch(p, rows, max_id=len(x), now_ms=0.0)
    pset.flush_all()
    counts, surv, g, pts = pset.global_merge_stats(emit_points=True)
    digests[prune] = (int(g), np.asarray(surv).tobytes(), pts.tobytes())
assert digests["1"] == digests["0"], \
    "prune on/off merge results diverge (g or point bytes differ)"
print(f"[obs-smoke] prune digest ok: g={digests['1'][0]} identical "
      "with SKYLINE_MERGE_PRUNE=1 and =0")
EOF

# flush dominance cascade: the quantized grid prefilter + bf16 margin pass
# must not change a single output byte — run an identical TWO-round flush
# stream (round 1 publishes the grid summaries the round-2 prefilter uses)
# with the cascade on and off and compare global-merge digests
JAX_PLATFORMS=cpu python - <<'EOF'
import os

import numpy as np

from skyline_tpu.stream.batched import PartitionSet
from skyline_tpu.workload.generators import anti_correlated

os.environ["SKYLINE_MERGE_CACHE"] = "0"
digests = {}
dropped = {}
for on in ("1", "0"):
    os.environ["SKYLINE_FLUSH_PREFILTER"] = on
    os.environ["SKYLINE_MIXED_PRECISION"] = on
    rng = np.random.default_rng(23)
    pset = PartitionSet(4, 4)
    x = anti_correlated(rng, 4000, 4, 0, 10000).astype(np.float32)
    pids = rng.integers(0, 4, len(x))
    half = len(x) // 2
    for lo, hi in ((0, half), (half, len(x))):
        for p in range(4):
            rows = np.ascontiguousarray(x[lo:hi][pids[lo:hi] == p])
            if rows.shape[0]:
                pset.add_batch(p, rows, max_id=len(x), now_ms=0.0)
        pset.flush_all()
    counts, surv, g, pts = pset.global_merge_stats(emit_points=True)
    digests[on] = (int(g), np.asarray(surv).tobytes(), pts.tobytes())
    dropped[on] = pset.flush_cascade_stats()["prefilter_dropped"]
assert digests["1"] == digests["0"], \
    "cascade on/off merge results diverge (g or point bytes differ)"
assert dropped["1"] > 0, "prefilter dropped nothing — cascade not live"
assert dropped["0"] == 0, dropped
print(f"[obs-smoke] flush cascade digest ok: g={digests['1'][0]} identical "
      f"with cascade on ({dropped['1']} rows prefiltered) and off")
EOF

# sorted-order SFS cascade (ISSUE 11): the host dominance path the flush
# chooser can swap in for the device kernels must not change a single
# output byte — drive an identical lazy-policy stream with the cascade
# forced on and off, compare global-merge digests, and assert the sorted
# path actually ran (flush.sorted_sfs counter + flush_sorted_sfs profiler
# variant), i.e. the identity was proven against a LIVE cascade
JAX_PLATFORMS=cpu python - <<'EOF'
import os

import numpy as np

from skyline_tpu.stream.batched import PartitionSet
from skyline_tpu.telemetry import Telemetry
from skyline_tpu.workload.generators import anti_correlated

os.environ["SKYLINE_MERGE_CACHE"] = "0"
digests = {}
tels = {}
for mode in ("on", "off"):
    os.environ["SKYLINE_SORTED_SFS"] = mode
    tel = Telemetry()
    rng = np.random.default_rng(23)
    pset = PartitionSet(4, 4, flush_policy="lazy", counters=tel.counters)
    x = anti_correlated(rng, 4000, 4, 0, 10000).astype(np.float32)
    pids = rng.integers(0, 4, len(x))
    for p in range(4):
        rows = np.ascontiguousarray(x[pids == p])
        if rows.shape[0]:
            pset.add_batch(p, rows, max_id=len(x), now_ms=0.0)
    pset.flush_all()
    counts, surv, g, pts = pset.global_merge_stats(emit_points=True)
    digests[mode] = (int(g), np.asarray(surv).tobytes(), pts.tobytes())
    tels[mode] = (dict(tel.counters.snapshot()), pset._flush_prof)
os.environ.pop("SKYLINE_SORTED_SFS", None)
assert digests["on"] == digests["off"], \
    "sorted-SFS on/off merge results diverge (g or point bytes differ)"
on_counters, on_prof = tels["on"]
assert on_counters.get("flush.sorted_sfs", 0) > 0, \
    "sorted path never engaged under SKYLINE_SORTED_SFS=on"
variants = {k["variant"] for k in on_prof.doc()["kernels"]}
assert "flush_sorted_sfs" in variants, variants
off_counters, _ = tels["off"]
assert off_counters.get("flush.sorted_sfs", 0) == 0, off_counters
print(f"[obs-smoke] sorted-SFS digest ok: g={digests['on'][0]} identical "
      f"with cascade on ({on_counters['flush.sorted_sfs']:.0f} sorted "
      "flush(es)) and off")
EOF

# device cascade (ISSUE 18): the jit-safe sorted dominance cascade must
# be LIVE UNDER JIT — the trace-count witness proves the cascade core
# actually compiled inside a jax.jit trace, the flush counter + profiler
# variant prove the flush arbitration took it, and the forced on/off
# engine digests must stay byte-identical
JAX_PLATFORMS=cpu python - <<'EOF'
import os

import jax
import jax.numpy as jnp
import numpy as np

from skyline_tpu.ops.device_cascade import (
    cascade_trace_count,
    device_cascade_mask,
)
from skyline_tpu.ops.dominance import skyline_mask
from skyline_tpu.stream.batched import PartitionSet
from skyline_tpu.telemetry import Telemetry
from skyline_tpu.workload.generators import anti_correlated

os.environ["SKYLINE_MERGE_CACHE"] = "0"
os.environ["SKYLINE_SORTED_SFS"] = "off"

# LIVE-under-jit witness: a fresh-shape jitted call must bump the
# Python-side trace counter (the core entered a jit trace) and match the
# quadratic referee bit for bit
rng = np.random.default_rng(29)
x = jnp.asarray(anti_correlated(rng, 1117, 5, 0, 10000))
before = cascade_trace_count()
got = np.asarray(jax.jit(device_cascade_mask)(x))
assert cascade_trace_count() > before, \
    "cascade core never entered the jit trace"
assert np.array_equal(got, np.asarray(skyline_mask(x))), \
    "jitted cascade mask diverges from the quadratic referee"

digests = {}
tels = {}
for mode in ("on", "off"):
    os.environ["SKYLINE_DEVICE_CASCADE"] = mode
    tel = Telemetry()
    rng = np.random.default_rng(23)
    pset = PartitionSet(4, 4, flush_policy="lazy", counters=tel.counters)
    pts_in = anti_correlated(rng, 4000, 4, 0, 10000).astype(np.float32)
    pids = rng.integers(0, 4, len(pts_in))
    for p in range(4):
        rows = np.ascontiguousarray(pts_in[pids == p])
        if rows.shape[0]:
            pset.add_batch(p, rows, max_id=len(pts_in), now_ms=0.0)
    pset.flush_all()
    counts, surv, g, pts = pset.global_merge_stats(emit_points=True)
    digests[mode] = (int(g), np.asarray(surv).tobytes(), pts.tobytes())
    tels[mode] = (dict(tel.counters.snapshot()), pset._flush_prof)
os.environ.pop("SKYLINE_DEVICE_CASCADE", None)
os.environ.pop("SKYLINE_SORTED_SFS", None)
assert digests["on"] == digests["off"], \
    "device-cascade on/off merge results diverge (g or point bytes differ)"
on_counters, on_prof = tels["on"]
assert on_counters.get("flush.device_cascade", 0) > 0, \
    "cascade path never engaged under SKYLINE_DEVICE_CASCADE=on"
variants = {k["variant"] for k in on_prof.doc()["kernels"]}
assert "flush_device_cascade" in variants, variants
off_counters, _ = tels["off"]
assert off_counters.get("flush.device_cascade", 0) == 0, off_counters
print(f"[obs-smoke] device cascade ok: live under jit "
      f"(trace count {cascade_trace_count()}), g={digests['on'][0]} "
      f"identical with cascade on "
      f"({on_counters['flush.device_cascade']:.0f} cascade flush(es)) "
      "and off")
EOF

# dispatch cascade + closed-loop tuner (ISSUE 20, RUNBOOK §2v): drive a
# uniform -> anti-correlated drift through a live worker with the
# controller at accelerated cadence — the workload plane must count the
# drift (skyline_workload_drift_total), the tuner must leave a decision
# in the flight ring and serve its block on GET /dispatch on BOTH HTTP
# surfaces, and an engine-level on/off re-run of the same stream must
# publish byte-identical skylines (the controller moves WHEN work
# happens, never WHAT is computed)
JAX_PLATFORMS=cpu python - <<'EOF'
import hashlib
import json
import os
import urllib.request

import numpy as np

os.environ["SKYLINE_TUNER"] = "1"
os.environ["SKYLINE_TUNER_EPOCH_S"] = "0"
os.environ["SKYLINE_TUNER_HYSTERESIS"] = "1"
# several epochs must close per phase for the kind flip to register:
# sample every row (cap above the 1500-row phases) and close every 256
os.environ["SKYLINE_WORKLOAD_EPOCH_ROWS"] = "256"
os.environ["SKYLINE_WORKLOAD_SAMPLE_CAP"] = "2000"

from skyline_tpu.bridge import MemoryBus, SkylineWorker
from skyline_tpu.bridge.wire import format_trigger, format_tuple_line
from skyline_tpu.utils.config import parse_job_args
from skyline_tpu.workload.generators import anti_correlated, uniform


def _phases(d):
    rng = np.random.default_rng(11)
    return [uniform(rng, 1500, d, 0, 10000),
            anti_correlated(rng, 1500, d, 0, 10000)]


cfg = parse_job_args(["--serve", "0", "--stats-port", "0",
                      "--parallelism", "2", "--dims", "4"])
bus = MemoryBus()
worker = SkylineWorker(bus, cfg.engine_config(), stats_port=cfg.stats_port,
                       serve_port=cfg.serve_port,
                       serve_config=cfg.serve_config())
try:
    rid = 0
    for qid, x in enumerate(_phases(4)):
        bus.produce_many("input-tuples",
                         [format_tuple_line(rid + i, row)
                          for i, row in enumerate(x)])
        rid += len(x)
        bus.produce("queries", format_trigger(qid, 0))
        while worker.step() > 0:
            pass
    for _ in range(4):  # idle ticks drive maybe_tune at zero cadence
        worker.step()

    counters = dict(worker.telemetry.counters.snapshot())
    assert counters.get("workload.drift", 0) >= 1, \
        "regime flip never counted as drift"
    assert counters.get("tuner.epochs", 0) >= 1, \
        "controller never ran an epoch"

    stats_base = f"http://127.0.0.1:{worker.stats_server.port}"
    serve_base = f"http://127.0.0.1:{worker.serve_server.port}"
    with urllib.request.urlopen(f"{stats_base}/metrics", timeout=5) as r:
        body = r.read().decode()
    for want in ("skyline_workload_drift_total",
                 "skyline_tuner_epochs_total",
                 "skyline_tuner_moves_total",
                 "skyline_tuner_switches_total"):
        assert want in body, f"{want} missing from exposition"

    for base in (stats_base, serve_base):
        with urllib.request.urlopen(f"{base}/dispatch", timeout=5) as r:
            doc = json.load(r)
        assert doc["table"]["rows"], "cascade table empty on /dispatch"
        assert doc["tuner"]["enabled"] is True, doc["tuner"]
        assert doc["tuner"]["epochs"] >= 1, doc["tuner"]

    with urllib.request.urlopen(f"{stats_base}/debug/flight",
                                timeout=5) as r:
        kinds = {e["kind"] for e in json.load(r)["entries"]}
    assert "workload.drift" in kinds, sorted(kinds)
    assert any(k.startswith("tuner.") for k in kinds), sorted(kinds)
    tuner_doc = doc["tuner"]
    print(f"[obs-smoke] tuner live ok: {counters['workload.drift']:.0f} "
          f"drift(s) counted, {tuner_doc['epochs']} controller epoch(s), "
          f"{tuner_doc['switches']} regime switch(es), decision kinds "
          f"{sorted(k for k in kinds if k.startswith('tuner.'))} "
          f"on /debug/flight, /dispatch live on both surfaces")
finally:
    worker.close()

# engine-level identity: same drift stream, tuner on vs off, published
# skyline (count + point bytes) must match digest-for-digest per trigger
from skyline_tpu.ops import cascade
from skyline_tpu.stream import EngineConfig, SkylineEngine
from skyline_tpu.telemetry import Telemetry

digests = {}
for mode in ("1", "0"):
    os.environ["SKYLINE_TUNER"] = mode
    cascade.clear_pins()
    for k in cascade.TUNABLE_KNOBS:
        cascade.clear_override(k)
    eng = SkylineEngine(
        EngineConfig(parallelism=2, algo="mr-angle", dims=4,
                     domain_max=10000.0, flush_policy="lazy",
                     emit_skyline_points=True),
        telemetry=Telemetry(),
    )
    out = []
    ingested = 0
    for qid, x in enumerate(_phases(4)):
        ids = np.arange(ingested, ingested + len(x), dtype=np.int64)
        eng.process_records(ids, x)
        ingested += len(x)
        eng.process_trigger(f"tuner-smoke-{qid},0")
        res = eng.poll_results()
        assert len(res) == 1, f"trigger {qid} unanswered"
        h = hashlib.sha256()
        h.update(str(res[0]["skyline_size"]).encode())
        pts = res[0].get("skyline_points")
        if pts is not None:
            h.update(np.ascontiguousarray(
                np.asarray(pts, dtype=np.float32)).tobytes())
        out.append(h.hexdigest()[:16])
    digests[mode] = out
cascade.clear_pins()
for k in cascade.TUNABLE_KNOBS:
    cascade.clear_override(k)
assert digests["1"] == digests["0"], \
    "tuner on/off published skylines diverge (controller moved WHAT)"
print(f"[obs-smoke] tuner digest ok: {len(digests['1'])} trigger(s) "
      "byte-identical with the controller on and off")
EOF

# replicated read fleet (RUNBOOK §2q): a WAL-tailing replica must expose
# the full serve surface byte-identically (role-marked /healthz, labeled
# per-tenant admission families on /metrics, SSE delta push on
# /subscribe)
JAX_PLATFORMS=cpu python - <<'EOF'
import hashlib
import json
import shutil
import socket
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np

from skyline_tpu.resilience.wal import WalWriter
from skyline_tpu.serve import (
    ServeConfig,
    SkylineServer,
    SnapshotStore,
    delta_wal_record,
)
from skyline_tpu.serve.replica import SkylineReplica

wal_dir = tempfile.mkdtemp(prefix="skyline-replica-obs-")
rng = np.random.default_rng(31)
writer = WalWriter(wal_dir, fsync="off")


def shadow(prev, snap):
    writer.append(delta_wal_record(prev, snap))
    writer.flush(force=True)


store = SnapshotStore()
store.on_publish(shadow)
primary = SkylineServer(store, port=0)
cfg = ServeConfig(tenant_rate=0.001, tenant_burst=2)
rep = SkylineReplica(wal_dir, serve_config=cfg, replica_id="obs-rep",
                     poll_interval_s=0.005, start=True)
try:
    store.publish(rng.random((64, 3)).astype(np.float32))
    assert rep.wait_for_version(1, timeout_s=10.0)

    # role-marked health + byte identity with the primary
    with urllib.request.urlopen(
        f"http://127.0.0.1:{rep.port}/healthz", timeout=5
    ) as r:
        assert json.load(r)["role"] == "replica"
    bodies = []
    for port in (primary.port, rep.port):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/skyline?format=csv", timeout=5
        ) as r:
            bodies.append(hashlib.sha256(r.read()).hexdigest())
    assert bodies[0] == bodies[1], "replica served different bytes"

    # SSE: subscribe, publish, the delta must be pushed
    sk = socket.create_connection(("127.0.0.1", rep.port), timeout=10)
    sk.sendall(b"GET /subscribe HTTP/1.1\r\nHost: x\r\n\r\n")
    f = sk.makefile("rb")
    while f.readline().strip():  # drain response headers
        pass
    deadline = time.monotonic() + 5.0
    while not rep.server._sse_queues:  # registration is async
        assert time.monotonic() < deadline, "SSE subscriber never registered"
        time.sleep(0.01)
    store.publish(rng.random((64, 3)).astype(np.float32))
    assert rep.wait_for_version(2, timeout_s=10.0)
    event = None
    while event is None:
        line = f.readline()
        if line.startswith(b"event:"):
            event = line.split(b":", 1)[1].strip().decode()
    assert event == "delta", event
    sk.close()

    # per-tenant admission: burst tenant "t1" past its 2-token bucket,
    # then the labeled shed family must appear on the replica's /metrics
    shed = 0
    for _ in range(6):
        req = urllib.request.Request(
            f"http://127.0.0.1:{rep.port}/skyline?points=0",
            headers={"X-Tenant": "t1"},
        )
        try:
            urllib.request.urlopen(req, timeout=5).read()
        except urllib.error.HTTPError as e:
            assert e.code == 429, e.code
            shed += 1
    assert shed >= 1, "tenant bucket never shed"
    with urllib.request.urlopen(
        f"http://127.0.0.1:{rep.port}/metrics", timeout=5
    ) as r:
        prom = r.read().decode()
    assert 'skyline_serve_tenant_reads_shed_total{tenant="t1"}' in prom, \
        "labeled tenant shed family missing from replica exposition"
    assert 'skyline_serve_tenant_reads_admitted_total{tenant="t1"}' in prom
    print(f"[obs-smoke] replica surface ok: byte-identical read, "
          f"role-marked healthz, SSE delta push, {shed} tenant shed(s) "
          f"labeled on /metrics")

    # ops plane (ISSUE 17, RUNBOOK §2s): replication telemetry as LABELED
    # families on the live replica exposition, the durable ops journal on
    # /ops and the fleet overview on /cluster/overview
    from skyline_tpu.telemetry.clusterview import ClusterView
    from skyline_tpu.telemetry.opslog import OpsLog

    assert 'skyline_replica_head_version{replica="obs-rep"}' in prom, \
        "labeled replica head gauge missing from exposition"
    assert 'skyline_replica_lag_ms{replica="obs-rep"}' in prom, \
        "labeled replica lag gauge missing from exposition"
    assert 'skyline_replica_records_applied_total{replica="obs-rep"}' \
        in prom, "labeled replica applied counter missing from exposition"
    ops = OpsLog(wal_dir, process_id="worker-obs-1", fsync="off")
    ops.record("promoted", epoch=2, holder="obs-rep")
    ops.flush(force=True)
    rep.telemetry.opslog = ops
    rep.telemetry.clusterview = ClusterView(
        [f"http://127.0.0.1:{primary.port}",
         f"http://127.0.0.1:{rep.port}"])
    with urllib.request.urlopen(
        f"http://127.0.0.1:{rep.port}/ops?limit=8", timeout=5
    ) as r:
        opsdoc = json.load(r)
    assert opsdoc["enabled"] and opsdoc["total"] >= 1, opsdoc
    assert any(rec["type"] == "promoted" for rec in opsdoc["records"]), \
        opsdoc
    with urllib.request.urlopen(
        f"http://127.0.0.1:{rep.port}/cluster/overview", timeout=5
    ) as r:
        ov = json.load(r)
    assert ov["enabled"] is True and ov["ok"] is True, ov
    assert ov["fleet"]["size"] == 2 and ov["fleet"]["live"] == 2, ov
    assert ov["findings"] == [], ov["findings"]
    ops.close()
    print(f"[obs-smoke] ops plane ok: labeled replica families on "
          f"/metrics, {opsdoc['total']} journal record(s) on /ops, "
          f"fleet overview {ov['fleet']['live']}/{ov['fleet']['size']} "
          f"live with zero findings")
finally:
    rep.close()
    primary.close()
    writer.close()
    shutil.rmtree(wal_dir, ignore_errors=True)
EOF

# zero-copy body store (ISSUE 19, RUNBOOK §2u): the publish-time body
# store must be LIVE on the serve path — bodystore hit/torn/retry and
# read-cache counters as Prometheus families on /metrics — and a
# WAL-tailing replica must serve the primary's EXACT bytes (sha256)
# out of the shared store
JAX_PLATFORMS=cpu python - <<'EOF'
import hashlib
import json
import os
import shutil
import tempfile
import urllib.request

import numpy as np

from skyline_tpu.resilience.wal import WalWriter
from skyline_tpu.serve import (
    SkylineServer,
    SnapshotStore,
    delta_wal_record,
)
from skyline_tpu.serve.bodystore import BodyStore
from skyline_tpu.serve.replica import SkylineReplica

wal_dir = tempfile.mkdtemp(prefix="skyline-bodystore-obs-")
rng = np.random.default_rng(47)
writer = WalWriter(wal_dir, fsync="off")


def shadow(prev, snap):
    writer.append(delta_wal_record(prev, snap))
    writer.flush(force=True)


store = SnapshotStore()
store.on_publish(shadow)
body = BodyStore(os.path.join(wal_dir, "bodystore.dat")).attach(store)
primary = SkylineServer(store, port=0, read_cache=0, bodystore=body)
rep = SkylineReplica(wal_dir, replica_id="obs-body-rep",
                     poll_interval_s=0.005, start=True)
try:
    assert rep.bodystore is not None, \
        "replica did not open the shared body store"
    store.publish(rng.random((96, 4)).astype(np.float32),
                  watermark_id=7, partial=True)
    assert rep.wait_for_version(1, timeout_s=10.0)

    # every wire shape must hash identically primary vs replica: the
    # replica is serving the primary's preserialized bytes, not its own.
    # JSON bodies splice a per-request volatile tail (age/staleness and
    # the replica's restored marker) after the store-served prefix, so
    # the identity claim — and the hash — covers the prefix; csv has no
    # tail and hashes whole
    paths = ("/skyline", "/skyline?points=0", "/skyline?explain=1",
             "/skyline?format=csv")
    for path in paths:
        digests = []
        for port in (primary.port, rep.port):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5
            ) as r:
                raw = r.read()
            if b"csv" not in path.encode():
                raw = raw.split(b', "age_ms":')[0]
            digests.append(hashlib.sha256(raw).hexdigest())
        assert digests[0] == digests[1], \
            f"replica served different bytes for {path}"

    stats = rep.bodystore.stats()
    assert stats["hits"] >= 1, stats  # replica reads actually hit the ring
    assert body.stats()["bodies_published"] >= 1, body.stats()

    # bodystore + read-cache counter families must be live on /metrics
    # (the primary runs read_cache=0 so every read exercises the store:
    # misses family on the primary, hits family on the LRU'd replica
    # after a repeated read)
    with urllib.request.urlopen(
        f"http://127.0.0.1:{primary.port}/metrics", timeout=5
    ) as r:
        prom = r.read().decode()
    for fam in ("skyline_serve_bodystore_hits_total",
                "skyline_serve_bodystore_misses_total",
                "skyline_serve_bodystore_torn_reads_total",
                "skyline_serve_bodystore_retries_total",
                "skyline_serve_read_cache_misses_total"):
        assert fam in prom, f"{fam} missing from exposition"
    urllib.request.urlopen(
        f"http://127.0.0.1:{rep.port}/skyline?format=csv", timeout=5
    ).read()
    with urllib.request.urlopen(
        f"http://127.0.0.1:{rep.port}/metrics", timeout=5
    ) as r:
        rprom = r.read().decode()
    assert "skyline_serve_read_cache_hits_total" in rprom, \
        "read_cache_hits family missing from replica exposition"
    with urllib.request.urlopen(
        f"http://127.0.0.1:{primary.port}/stats", timeout=5
    ) as r:
        sdoc = json.load(r)
    assert sdoc["bodystore"]["bodies_published"] >= 1, sdoc["bodystore"]
    print(f"[obs-smoke] bodystore ok: {len(paths)} wire shapes "
          f"sha256-identical primary vs replica out of the shared store "
          f"({stats['hits']} replica ring hit(s), 0 torn), counter "
          f"families live on /metrics")
finally:
    rep.close()
    primary.close()
    body.close()
    writer.close()
    shutil.rmtree(wal_dir, ignore_errors=True)
EOF

# sharded-engine gate: the two-level chip tournament lands byte-identical
# to the flat worker and the chip-witness prefilter is live (RUNBOOK 2n)
scripts/mesh_smoke.sh
echo "[obs-smoke] mesh gate ok"

# crash-safety gate: supervised crash/restart cycle lands byte-identical
# to an uninterrupted run, resilience counters move (RUNBOOK 2i)
scripts/chaos_smoke.sh
echo "[obs-smoke] chaos gate ok"

# static-analysis gate: knob registry lint, jaxpr invariant audit,
# lock-discipline lint, docs/KNOBS.md drift (scripts/lint.sh, RUNBOOK 2h)
scripts/lint.sh
echo "[obs-smoke] static-analysis gate ok"
echo "[obs-smoke] ALL PASS"
