#!/usr/bin/env bash
# Smoke the sharded streaming engine end-to-end on one host, no broker, no
# TPU (RUNBOOK 2n): four XLA host-platform virtual chips, a flat worker and
# a --mesh-chips 4 worker over IDENTICAL streams, then assert
#   * the sharded worker's published skyline is byte-identical (survivor
#     count AND point-buffer sha256) to the flat worker's,
#   * /stats carries the sharded block and its chip-prune counter is
#     non-zero (the witness prefilter skipped whole chips on a live run),
#   * /explain's latest plan carries per-chip attribution
#     (merge.path=sharded_tree, pruned/survivor lists consistent with
#     /stats),
#   * the flat worker stamps NO sharded block (the plane is gated),
#   * /fleet on the sharded worker is live (RUNBOOK 2o): per-chip ingest
#     series non-zero, imbalance gauge present, chip 0 ships 0
#     interconnect rows; the flat worker answers {"enabled": false},
#   * /metrics carries the labeled skyline_chip_* families and the
#     skyline_workload_drift_total counter,
#   * the chip-health join rides /fleet with every chip healthy and the
#     skyline_degraded_answers_total counter exposed at 0 on a clean run
#     (RUNBOOK 2p).
#
#   scripts/mesh_smoke.sh
#
# Exits non-zero on any failed assertion. CPU-only (JAX_PLATFORMS=cpu).
set -euo pipefail
cd "$(dirname "$0")/.."

XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
python - <<'EOF'
import hashlib
import json
import urllib.request

import numpy as np

from skyline_tpu.bridge import MemoryBus, SkylineWorker
from skyline_tpu.bridge.wire import format_trigger, format_tuple_line
from skyline_tpu.utils.config import parse_job_args
from skyline_tpu.workload.generators import anti_correlated

import jax

assert jax.device_count() >= 4, jax.devices()


def run(mesh_chips):
    argv = ["--stats-port", "0", "--parallelism", "8", "--dims", "4"]
    if mesh_chips:
        argv += ["--mesh-chips", str(mesh_chips)]
    cfg = parse_job_args(argv)
    bus = MemoryBus()
    w = SkylineWorker(bus, cfg.engine_config(), stats_port=cfg.stats_port)
    try:
        rng = np.random.default_rng(11)
        x = anti_correlated(rng, 6000, 4, 0, 10000)
        bus.produce_many("input-tuples",
                         [format_tuple_line(i, r) for i, r in enumerate(x)])
        bus.produce("queries", format_trigger(0, 0))
        while w.step() > 0:
            pass
        # the published answer's exact bytes: survivor count + point buffer
        # (the facade cache serves the same epoch, so this is the answer
        # the query above published)
        counts, surv, g, pts = w.engine.pset.global_merge_stats(
            emit_points=True
        )
        digest = hashlib.sha256(np.asarray(pts).tobytes()).hexdigest()
        base = f"http://127.0.0.1:{w.stats_server.port}"
        with urllib.request.urlopen(f"{base}/stats", timeout=5) as r:
            stats = json.load(r)
        with urllib.request.urlopen(f"{base}/explain", timeout=5) as r:
            plan = json.load(r)
        with urllib.request.urlopen(f"{base}/fleet", timeout=5) as r:
            fleet = json.load(r)
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
            metrics = r.read().decode()
    finally:
        w.close()
    return int(g), digest, stats, plan, fleet, metrics


g_flat, d_flat, s_flat, _, fleet_flat, _ = run(0)
assert "sharded" not in s_flat, "flat worker stamped a sharded block"
# flat worker: the /fleet route answers rather than 404s, but reports the
# plane off (RUNBOOK 2o) — scrapers can probe unconditionally
assert fleet_flat["enabled"] is False, fleet_flat

g_sh, d_sh, s_sh, plan, fleet, metrics = run(4)
sh = s_sh["sharded"]
assert sh["chips"] == 4 and sh["group_size"] >= 1, sh
assert sh["merges"] >= 1, sh
assert sh["chips_pruned"] >= 1, \
    f"chip-witness prefilter never fired: {sh}"
assert 0.0 < sh["pruned_chip_fraction"] <= 0.75, sh

assert (g_flat, d_flat) == (g_sh, d_sh), (
    f"sharded worker diverges from flat: g {g_flat} vs {g_sh}, "
    f"digest {d_flat[:12]} vs {d_sh[:12]}"
)

ch = plan["chips"]
assert ch is not None, "EXPLAIN plan lacks per-chip attribution"
assert plan["merge"]["path"] == "sharded_tree", plan["merge"]
assert ch["chips"] == 4, ch
pruned_ids = {p["chip"] for p in ch["pruned"]}
assert pruned_ids and pruned_ids.isdisjoint(ch["survivors"]), ch
assert len(ch["per_chip"]) == 4, ch

# fleet plane (RUNBOOK 2o): the /fleet join on a live sharded worker
assert fleet["enabled"] is True and fleet["chips"] == 4, fleet
per = {pc["chip"]: pc for pc in fleet["per_chip"]}
assert len(per) == 4 and all(pc["ingest_rows"] > 0 for pc in per.values()), \
    f"per-chip ingest series dead: {fleet}"
assert fleet["imbalance_index"] >= 1.0, fleet
assert per[0]["interconnect_rows"] == 0, \
    f"root chip shipped rows to itself: {per[0]}"
assert 'skyline_chip_ingest_rows_total{chip="0"}' in metrics, \
    "labeled per-chip family missing from /metrics"
assert "skyline_fleet_imbalance_index" in metrics, metrics[-400:]
assert "skyline_workload_drift_total" in metrics, \
    "workload drift counter missing from /metrics"

# chip fault tolerance (RUNBOOK §2p): the health join rides /fleet — a
# clean run reports every chip healthy with nothing quarantined — and the
# honest-degradation counter is exposed (and zero) even when no answer
# has ever degraded, so dashboards can alert on the first increment
hdoc = fleet["health"]
assert hdoc is not None and hdoc["chips"] == 4, hdoc
assert hdoc["quarantined"] == [], \
    f"clean run quarantined chips: {hdoc['quarantined']}"
assert all(pc["status"] == "healthy" for pc in hdoc["per_chip"]), hdoc
assert "skyline_degraded_answers_total 0" in metrics, \
    "degraded-answer counter missing from /metrics on a clean run"

print(f"[mesh-smoke] identity ok: g={g_sh}, sha256 {d_sh[:16]}… identical "
      "flat vs 4 chips")
print(f"[mesh-smoke] chip prune ok: {sh['chips_pruned']} chip(s) pruned, "
      f"fraction={sh['pruned_chip_fraction']}")
print(f"[mesh-smoke] explain ok: path={plan['merge']['path']}, "
      f"pruned={sorted(pruned_ids)}, survivors={ch['survivors']}")
print(f"[mesh-smoke] fleet ok: imbalance={fleet['imbalance_index']}, "
      f"interconnect_rows_total={fleet['interconnect_rows_total']}, "
      "labeled chip families on /metrics")
print("[mesh-smoke] PASS")
EOF
