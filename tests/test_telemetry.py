"""Telemetry plane unit tests: histograms, spans, Prometheus rendering,
trace-id propagation through the engine, and the collector's CSV contract
(header race + optional TraceID column)."""

import csv
import json
import threading

import numpy as np
import pytest

from skyline_tpu.metrics.collector import (
    CSV_HEADERS,
    append_result_row,
)
from skyline_tpu.stream.engine import EngineConfig, SkylineEngine
from skyline_tpu.telemetry import (
    Histogram,
    SpanRecorder,
    Telemetry,
    flatten_gauges,
    mint_trace_id,
    render_prometheus,
)
from tests.conftest import parse_prometheus_text


# ---------------------------------------------------------------- histogram


def test_histogram_small_sample_quantiles_exact():
    # below sample_cap the quantiles are true order statistics — identical
    # to np.percentile(..., interpolation='linear')
    rng = np.random.default_rng(3)
    vals = rng.uniform(0.1, 5000.0, size=200)
    h = Histogram("t")
    h.observe_many(vals)
    for q in (0, 5, 50, 90, 99, 100):
        assert h.quantile(q / 100.0) == pytest.approx(
            float(np.percentile(vals, q)), rel=1e-12
        )


def test_histogram_bucketed_quantiles_bounded_error():
    # past sample_cap quantiles interpolate inside log buckets (~12% wide)
    rng = np.random.default_rng(4)
    vals = rng.lognormal(mean=2.0, sigma=1.0, size=20_000)
    h = Histogram("t", sample_cap=64)
    h.observe_many(vals)
    for q in (50, 90, 99):
        exact = float(np.percentile(vals, q))
        est = h.quantile(q / 100.0)
        assert abs(est - exact) / exact < 0.15, (q, est, exact)
    assert h.count == 20_000
    assert h.quantile(0.0) >= float(vals.min())
    assert h.quantile(1.0) == pytest.approx(float(vals.max()))


def test_histogram_empty_and_validation():
    h = Histogram("t")
    assert h.quantile(0.5) == 0.0
    assert h.snapshot() == {"count": 0}
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        Histogram("bad", edges=(1.0, 1.0))


def test_histogram_thread_safety():
    h = Histogram("t", sample_cap=128)
    n_threads, per = 8, 5_000

    def work(seed):
        r = np.random.default_rng(seed)
        for v in r.uniform(0.5, 100.0, size=per):
            h.observe(v)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert h.count == n_threads * per
    # every observation landed in exactly one bucket
    assert h.bucket_counts()[-1] == (float("inf"), n_threads * per)


def test_histogram_snapshot_fields():
    h = Histogram("t")
    h.observe_many([1.0, 2.0, 3.0, 4.0])
    s = h.snapshot()
    assert s["count"] == 4
    assert s["min"] == 1.0 and s["max"] == 4.0
    assert s["mean"] == pytest.approx(2.5)
    assert {"p50", "p90", "p99"} <= set(s)


# -------------------------------------------------------------------- spans


def test_span_ring_bounded_and_ordered():
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        rec.record(f"s{i}", i * 10, i * 10 + 5)
    spans = rec.snapshot()
    assert len(spans) == 8
    assert [s["name"] for s in spans] == [f"s{i}" for i in range(12, 20)]
    assert rec.recorded == 20


def test_span_chrome_export_schema():
    rec = SpanRecorder()
    with rec.span("phase_a", trace_id="t-1", rows=5):
        pass
    rec.record("phase_b", 100, 250, tid=3)
    doc = rec.to_chrome()
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert len(doc["traceEvents"]) == 2
    for e in doc["traceEvents"]:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0
        assert {"name", "pid", "tid", "cat", "args"} <= set(e)
    a = doc["traceEvents"][0]
    assert a["args"] == {"rows": 5, "trace_id": "t-1"}
    json.dumps(doc)  # must be JSON-serializable as-is


def test_span_write_chrome(tmp_path):
    rec = SpanRecorder()
    rec.record("x", 0, 1000)
    out = tmp_path / "trace.json"
    assert rec.write_chrome(str(out)) == 1
    doc = json.loads(out.read_text())
    assert doc["traceEvents"][0]["name"] == "x"


def test_mint_trace_id_unique_across_threads():
    seen = []
    lock = threading.Lock()

    def mint_many():
        ids = [mint_trace_id() for _ in range(500)]
        with lock:
            seen.extend(ids)

    ts = [threading.Thread(target=mint_many) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(set(seen)) == len(seen) == 2000


# --------------------------------------------------------------- prometheus


def test_render_prometheus_parses(prom_parse):
    h = Histogram("lat_ms")
    h.observe_many([0.5, 2.0, 700.0])
    text = render_prometheus(
        counters={"reads_served": 7},
        gauges={"depth": 3, "ratio": 0.5},
        histograms=[h],
    )
    series = prom_parse(text)
    types = series.pop("__types__")
    assert types["skyline_reads_served_total"] == "counter"
    assert types["skyline_lat_ms"] == "histogram"
    assert series["skyline_reads_served_total"] == [({}, 7.0)]
    assert series["skyline_depth"] == [({}, 3.0)]
    buckets = series["skyline_lat_ms_bucket"]
    # cumulative and +Inf-terminated
    assert buckets[-1][0]["le"] == "+Inf" and buckets[-1][1] == 3.0
    counts = [v for _, v in buckets]
    assert counts == sorted(counts)
    assert series["skyline_lat_ms_count"] == [({}, 3.0)]


def test_flatten_gauges_nested():
    flat = flatten_gauges(
        {
            "a": 1,
            "nested": {"x": 2.5, "deep": {"y": 3}},
            "flag": True,
            "skip_str": "text",
            "skip_list": [1, 2],
            "skip_none": None,
        }
    )
    assert flat == {"a": 1, "nested_x": 2.5, "nested_deep_y": 3, "flag": 1}


def test_telemetry_hub_get_or_create():
    tel = Telemetry()
    h1 = tel.histogram("x")
    h2 = tel.histogram("x")
    assert h1 is h2
    tel.counters.inc("evt")
    text = tel.render_prometheus(gauges={"g": 1}, extra_counters={"extra": 2})
    series = parse_prometheus_text(text)
    assert series["skyline_evt_total"] == [({}, 1.0)]
    assert series["skyline_extra_total"] == [({}, 2.0)]


# --------------------------------------------- engine trace-id propagation


def _run_traced_query(with_store: bool):
    tel = Telemetry()
    eng = SkylineEngine(EngineConfig(parallelism=2, dims=2), telemetry=tel)
    store = None
    if with_store:
        from skyline_tpu.serve import SnapshotStore

        store = SnapshotStore()
        eng.attach_snapshots(store)
    rng = np.random.default_rng(0)
    ids = np.arange(1, 201, dtype=np.int64)
    vals = rng.uniform(1, 999, size=(200, 2)).astype(np.float32)
    eng.process_records(ids, vals)
    eng.process_trigger("q1,0")
    (result,) = eng.poll_results()
    return tel, store, result


def test_engine_trace_id_propagation():
    tel, store, result = _run_traced_query(with_store=True)
    tid = result["trace_id"]
    assert tid and "-" in tid
    # the published snapshot carries the same correlation key
    assert store.latest().meta["trace_id"] == tid
    names = {s["name"] for s in tel.spans.snapshot()}
    assert {"ingest", "local", "merge", "publish", "query"} <= names
    # every query-scoped span is stamped with the query's trace id
    for s in tel.spans.snapshot():
        if s["name"] in ("local", "merge", "publish", "query"):
            assert s.get("trace_id") == tid, s
    assert tel.histogram("query_latency_ms").count == 1
    assert tel.histogram("global_merge_ms").count == 1
    assert tel.histogram("ingest_batch_ms").count == 1


def test_engine_without_telemetry_unchanged():
    eng = SkylineEngine(EngineConfig(parallelism=2, dims=2))
    rng = np.random.default_rng(0)
    eng.process_records(
        np.arange(1, 101, dtype=np.int64),
        rng.uniform(1, 999, size=(100, 2)).astype(np.float32),
    )
    eng.process_trigger("q1,0")
    (result,) = eng.poll_results()
    assert "trace_id" not in result


def test_sliding_engine_trace_id():
    from skyline_tpu.stream.sliding_engine import SlidingEngine

    tel = Telemetry()
    eng = SlidingEngine(
        EngineConfig(parallelism=2, dims=2),
        window_size=100,
        slide=50,
        telemetry=tel,
    )
    rng = np.random.default_rng(0)
    eng.process_records(
        np.arange(100, dtype=np.int64),
        rng.uniform(1, 999, size=(100, 2)).astype(np.float32),
    )
    eng.process_trigger("w1,0")
    (result,) = eng.poll_results()
    assert result["trace_id"]
    names = {s["name"] for s in tel.spans.snapshot()}
    assert {"ingest", "merge", "query"} <= names
    assert tel.histogram("query_latency_ms").count == 1


# ----------------------------------------------------------- collector CSV


def test_collector_header_race_two_threads(tmp_path):
    # regression: both writers once saw "no file" and both wrote the header
    path = str(tmp_path / "out.csv")
    barrier = threading.Barrier(2)
    rows_per = 50

    def writer(qid):
        barrier.wait()
        for i in range(rows_per):
            append_result_row(
                path, {"query_id": f"{qid}-{i}", "skyline_size": i}
            )

    ts = [threading.Thread(target=writer, args=(q,)) for q in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    with open(path, newline="") as f:
        rows = list(csv.reader(f))
    assert rows[0] == CSV_HEADERS
    assert sum(1 for r in rows if r == CSV_HEADERS) == 1
    assert len(rows) == 1 + 2 * rows_per


def test_collector_without_trace_id_byte_stable(tmp_path):
    # untraced results keep the reference 10-column shape byte-for-byte
    a, b = str(tmp_path / "a.csv"), str(tmp_path / "b.csv")
    data = {"query_id": "q", "skyline_size": 3, "query_latency_ms": 1.5}
    append_result_row(a, data)
    append_result_row(b, dict(data))  # same payload, fresh file
    assert open(a, "rb").read() == open(b, "rb").read()
    with open(a, newline="") as f:
        rows = list(csv.reader(f))
    assert rows[0] == CSV_HEADERS
    assert len(rows[1]) == len(CSV_HEADERS)
    assert "TraceID" not in rows[0]


def test_collector_with_trace_id_column(tmp_path):
    path = str(tmp_path / "out.csv")
    append_result_row(
        path, {"query_id": "q1", "skyline_size": 3, "trace_id": "abc-1"}
    )
    append_result_row(
        path, {"query_id": "q2", "skyline_size": 4, "trace_id": "abc-2"}
    )
    with open(path, newline="") as f:
        rows = list(csv.reader(f))
    assert rows[0] == CSV_HEADERS + ["TraceID"]
    assert rows[1][-1] == "abc-1" and rows[2][-1] == "abc-2"
