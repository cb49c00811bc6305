"""Observability integration tests: the StatsServer surface (/healthz,
/stats, dashboard, /metrics, /trace), the serving plane's /metrics and the
worker's --trace-out Chrome trace file."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from skyline_tpu.bridge import MemoryBus, SkylineWorker
from skyline_tpu.bridge.wire import format_trigger, format_tuple_line
from skyline_tpu.metrics.httpstats import StatsServer
from skyline_tpu.telemetry import Telemetry


def _get(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


# -------------------------------------------------------------- StatsServer


def test_statsserver_healthz():
    srv = StatsServer(lambda: {"x": 1}, port=0)
    try:
        status, _, body = _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert status == 200
        assert json.loads(body) == {"ok": True}
    finally:
        srv.close()


def test_statsserver_stats_500_on_callback_exception():
    def boom():
        raise RuntimeError("stats backend unavailable")

    srv = StatsServer(boom, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"http://127.0.0.1:{srv.port}/stats")
        assert ei.value.code == 500
        assert "stats backend unavailable" in json.load(ei.value)["error"]
        # /metrics flattens the same callback — same contract
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"http://127.0.0.1:{srv.port}/metrics")
        assert ei.value.code == 500
    finally:
        srv.close()


def test_statsserver_dashboard_html():
    srv = StatsServer(lambda: {"records_in": 5}, port=0)
    try:
        status, ctype, body = _get(f"http://127.0.0.1:{srv.port}/")
        assert status == 200 and "text/html" in ctype
        html = body.decode()
        assert "tpu-skyline worker" in html
        assert "/stats" in html
        # the serve-plane and latency tile blocks ship with the page
        assert "serving plane" in html
        assert "p50 / p99" in html
        assert "reads shed (429)" in html
    finally:
        srv.close()


def test_statsserver_metrics_prometheus(prom_parse):
    tel = Telemetry()
    tel.histogram("query_latency_ms").observe_many([1.0, 5.0, 20.0])
    tel.counters.inc("results_total", 3)
    stats = {
        "records_in": 1000,
        "nested": {"depth": 2},
        "latency_ms": tel.latency_snapshot(),  # must not double-export
        "label": "text",  # non-numeric: dropped from gauges
    }
    srv = StatsServer(lambda: stats, port=0, telemetry=tel)
    try:
        status, ctype, body = _get(f"http://127.0.0.1:{srv.port}/metrics")
        assert status == 200
        assert "version=0.0.4" in ctype
        series = prom_parse(body.decode())
        types = series.pop("__types__")
        assert series["skyline_records_in"] == [({}, 1000.0)]
        assert series["skyline_nested_depth"] == [({}, 2.0)]
        assert series["skyline_results_total_total"] == [({}, 3.0)]
        assert types["skyline_query_latency_ms"] == "histogram"
        assert series["skyline_query_latency_ms_count"] == [({}, 3.0)]
        buckets = series["skyline_query_latency_ms_bucket"]
        assert buckets[-1][0] == {"le": "+Inf"}
        # latency_ms summaries must not leak in as gauges next to the
        # real histogram series
        assert not any("latency_ms_p50" in k for k in series)
    finally:
        srv.close()


def test_statsserver_metrics_without_telemetry(prom_parse):
    srv = StatsServer(lambda: {"records_in": 7}, port=0)
    try:
        _, _, body = _get(f"http://127.0.0.1:{srv.port}/metrics")
        series = prom_parse(body.decode())
        assert series["skyline_records_in"] == [({}, 7.0)]
    finally:
        srv.close()


def test_statsserver_trace_endpoint():
    tel = Telemetry()
    with tel.spans.span("unit", trace_id="t-9"):
        pass
    srv = StatsServer(lambda: {}, port=0, telemetry=tel)
    try:
        _, _, body = _get(f"http://127.0.0.1:{srv.port}/trace")
        doc = json.loads(body)
        assert doc["traceEvents"][0]["name"] == "unit"
        assert doc["traceEvents"][0]["args"]["trace_id"] == "t-9"
    finally:
        srv.close()
    # without a hub the endpoint still answers with an empty trace
    srv = StatsServer(lambda: {}, port=0)
    try:
        _, _, body = _get(f"http://127.0.0.1:{srv.port}/trace")
        assert json.loads(body) == {"traceEvents": []}
    finally:
        srv.close()


# ------------------------------------------------- worker + serving plane


@pytest.fixture
def traced_worker(tmp_path):
    from skyline_tpu.stream.engine import EngineConfig

    trace_out = str(tmp_path / "trace.json")
    bus = MemoryBus()
    worker = SkylineWorker(
        bus,
        EngineConfig(parallelism=2, dims=3),
        stats_port=0,
        serve_port=0,
        trace_out=trace_out,
    )
    rng = np.random.default_rng(2)
    x = rng.uniform(1, 9999, size=(2000, 3)).astype(np.float32)
    bus.produce_many(
        "input-tuples",
        [format_tuple_line(i, row) for i, row in enumerate(x)],
    )
    bus.produce("queries", format_trigger(0, 0))
    while worker.step() > 0:
        pass
    try:
        yield worker, trace_out
    finally:
        worker.close()


def test_serve_server_metrics_prometheus(traced_worker, prom_parse):
    worker, _ = traced_worker
    base = f"http://127.0.0.1:{worker.serve_server.port}"
    # one admitted read so serve counters and serve_read_ms move
    status, _, _ = _get(f"{base}/skyline")
    assert status == 200
    status, ctype, body = _get(f"{base}/metrics")
    assert status == 200
    assert "version=0.0.4" in ctype
    series = prom_parse(body.decode())
    series.pop("__types__")
    assert series["skyline_serve_reads_admitted_total"][0][1] >= 1.0
    assert series["skyline_snapshot_store_head_version"] == [({}, 1.0)]
    assert "skyline_serve_read_ms_bucket" in series
    assert series["skyline_serve_bridge_depth"] == [({}, 0.0)]


def test_worker_stats_latency_section(traced_worker):
    worker, _ = traced_worker
    stats = worker.stats()
    lat = stats["latency_ms"]
    for name in ("ingest_batch_ms", "global_merge_ms", "query_latency_ms"):
        assert lat[name]["count"] >= 1, (name, lat)
        assert lat[name]["p50"] <= lat[name]["p99"]


def test_worker_trace_out_chrome_schema(traced_worker):
    # acceptance: a captured --trace-out file validates against the Chrome
    # trace-event schema and contains the spans of one query's life:
    # ingest -> local -> merge -> publish (serve plane attached)
    worker, trace_out = traced_worker
    worker.close()
    with open(trace_out) as f:
        doc = json.loads(f.read())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for e in doc["traceEvents"]:
        assert e["ph"] == "X"
        assert isinstance(e["name"], str)
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    by_name = {}
    for e in doc["traceEvents"]:
        by_name.setdefault(e["name"], []).append(e)
    for phase in ("ingest", "local", "merge", "publish"):
        assert phase in by_name, (phase, sorted(by_name))
    # local/merge/publish of the same query share its trace_id
    tid = by_name["merge"][0]["args"]["trace_id"]
    assert tid
    assert by_name["publish"][0]["args"]["trace_id"] == tid
    assert any(
        e["args"].get("trace_id") == tid for e in by_name["local"]
    )


def test_worker_metrics_on_stats_server(traced_worker, prom_parse):
    worker, _ = traced_worker
    base = f"http://127.0.0.1:{worker.stats_server.port}"
    _, _, body = _get(f"{base}/metrics")
    series = prom_parse(body.decode())
    series.pop("__types__")
    assert "skyline_ingest_batch_ms_bucket" in series
    assert series["skyline_query_latency_ms_count"][0][1] >= 1.0
    assert series["skyline_results_emitted"] == [({}, 1.0)]
