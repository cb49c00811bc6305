#!/usr/bin/env bash
# Validate the containerized stack end-to-end: build the image, bring up
# broker + worker + collector, run a bounded producer, and capture a result
# row from inside the containers into deploy/data/results.csv plus a log
# bundle under deploy/validate_logs/.
#
# Records an honest blocker into artifacts/container_stack.json when no
# container runtime exists (this build image has none — docker/podman/nerdctl
# all absent and no package egress; see the JSON for the probe).
set -euo pipefail
cd "$(dirname "$0")/.."

RUNTIME=""
for c in docker podman nerdctl; do
  if command -v "$c" >/dev/null 2>&1; then RUNTIME="$c"; break; fi
done

mkdir -p artifacts
if [ -z "$RUNTIME" ]; then
  cat > artifacts/container_stack.json <<EOF
{
 "status": "blocked",
 "probed_at": "$(date -u +%FT%TZ)",
 "probe": {"docker": null, "podman": null, "nerdctl": null},
 "blocker": "no container runtime in this image and no package egress to install one; deploy/docker-compose.yml is untested here. Bare-metal equivalent of the same topology (kafkalite broker + worker + collector + producer as separate OS processes) runs via deploy/launch.py.",
 "how_to_run": "on a docker host: deploy/validate_stack.sh"
}
EOF
  echo "no container runtime found; blocker recorded in artifacts/container_stack.json" >&2
  exit 0
fi

LOGS=deploy/validate_logs
mkdir -p "$LOGS" deploy/data
COMPOSE="$RUNTIME compose -f deploy/docker-compose.yml"

$COMPOSE build worker 2>&1 | tee "$LOGS/build.log"
$COMPOSE up -d kafka worker collector 2>&1 | tee "$LOGS/up.log"
trap '$COMPOSE down -v 2>/dev/null || true' EXIT
# bounded stream + trigger; collector writes /data/results.csv
$COMPOSE run --rm producer 2>&1 | tee "$LOGS/producer.log"
for _ in $(seq 1 120); do
  if [ -s deploy/data/results.csv ] && [ "$(wc -l < deploy/data/results.csv)" -ge 2 ]; then
    break
  fi
  sleep 2
done
cp deploy/data/results.csv "$LOGS/results.csv"
python - <<'EOF'
import csv, json
rows = list(csv.reader(open("deploy/validate_logs/results.csv")))
assert len(rows) >= 2, "no result row captured"
row = dict(zip(rows[0], rows[1]))
import datetime
json.dump(
    {"status": "ran",
     "probed_at": datetime.datetime.now(datetime.timezone.utc)
         .strftime("%Y-%m-%dT%H:%M:%SZ"),
     "result_row": row, "logs": "deploy/validate_logs/"},
    open("artifacts/container_stack.json", "w"), indent=1,
)
print("container stack validated:", row)
EOF
