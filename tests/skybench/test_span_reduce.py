"""Host time and device idle time per program layer
(skybench/span_reduce.py), on hand-made intervals checked by hand, and the
readers of the layer metrics (skybench/program_spans.py).

The window is 0-100 ns on one worker thread:
  worker/ingest (worker)  10-60
    ingest/batch (ingest) 15-40
      flush/auto (flush)  20-30
  publish (publish)       70-90
so the innermost span is: none 0-10, worker 10-15, ingest 15-20, flush
20-30, ingest 30-40, worker 40-60, none 60-70, publish 70-90, none 90-100.
"""

import importlib.util
import os
from types import SimpleNamespace as NS

import pytest

from skybench import run as bench_run
from skybench import span_reduce
from skyline_tpu.metrics import tracing

SPANS = [
    (10, 60, "worker/ingest", "worker"),
    (15, 40, "ingest/batch", "ingest"),
    (20, 30, "flush/auto", "flush"),
    (70, 90, "publish", "publish"),
]
# 5-25 crosses four pieces (none, worker, ingest, flush); 62-68 has no span
IDLE = [(5, 25), (62, 68), (75, 85)]


def test_innermost_tiles_the_window():
    got = [(a, b, s[2] if s else None) for a, b, s in span_reduce.innermost(SPANS, 0, 100)]
    assert got == [
        (0, 10, None), (10, 15, "worker/ingest"), (15, 20, "ingest/batch"),
        (20, 30, "flush/auto"), (30, 40, "ingest/batch"), (40, 60, "worker/ingest"),
        (60, 70, None), (70, 90, "publish"), (90, 100, None),
    ]


def test_host_and_idle_time_per_layer():
    out = span_reduce.attribute(0, 100, SPANS, [IDLE])
    assert out["layers_s"] == pytest.approx(
        {"worker": 25e-9, "ingest": 15e-9, "flush": 10e-9, "publish": 20e-9}
    )
    assert out["unspanned_s"] == pytest.approx(30e-9)
    assert sum(out["layers_s"].values()) + out["unspanned_s"] == pytest.approx(100e-9)
    assert out["idle_layers_s"] == pytest.approx(
        {"worker": 5e-9, "ingest": 5e-9, "flush": 5e-9, "publish": 10e-9}
    )
    assert out["idle_unattributed_s"] == pytest.approx(11e-9)  # 0-10 part + 62-68
    idle = sum(b - a for a, b in IDLE) * 1e-9
    assert sum(out["idle_layers_s"].values()) + out["idle_unattributed_s"] == pytest.approx(idle)


def test_idle_is_averaged_over_chips():
    # a second chip idle the whole window adds every piece once, halved
    out = span_reduce.attribute(0, 100, SPANS, [IDLE, [(0, 100)]])
    assert out["idle_layers_s"]["worker"] == pytest.approx((5 + 25) / 2 * 1e-9)
    assert out["idle_unattributed_s"] == pytest.approx((11 + 30) / 2 * 1e-9)


def test_spans_are_clipped_to_the_window():
    out = span_reduce.attribute(12, 35, SPANS, [[]])
    assert out["layers_s"] == pytest.approx({"worker": 3e-9, "ingest": 10e-9, "flush": 10e-9})
    assert out["unspanned_s"] == 0


@pytest.mark.parametrize(
    "gap, label",
    [
        ((75, 85), "worker.step:publish"),  # one span over all of it
        ((5, 25), "worker.step"),  # four pieces, none over half
        ((62, 68), "worker.step"),  # no span open
        ((18, 30), "worker.step:flush/auto"),  # 10 of 12 ns
    ],
)
def test_gap_labels(gap, label):
    pieces = span_reduce.innermost(SPANS, 0, 100)
    assert span_reduce.label_gap(*gap, [(0, 100, "worker.step")], pieces) == label


def test_a_gap_outside_every_harness_annotation_is_other():
    pieces = span_reduce.innermost(SPANS, 0, 100)
    assert span_reduce.label_gap(75, 85, [], pieces) == "other:publish"


def _event(name, start, end, **stats):
    return NS(name=name, start_ns=start, end_ns=end, stats=list(stats.items()))


def test_reduce_trace_reads_the_window_line_and_the_ops_line():
    worker = [_event("window", 0, 100), _event("worker.step", 0, 100)]
    worker += [_event(n, a, b, layer=layer) for a, b, n, layer in SPANS]
    worker.append(_event("PjitFunction(step)", 40, 45))  # JAX's own: no layer
    other = [_event("flush/auto", 0, 100, layer="flush")]  # another thread
    ops = [_event("%fusion.1", 0, 5), _event("%fusion.2", 25, 62), _event("%sort", 68, 75),
           _event("%sort", 85, 120)]
    pd = NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=worker), NS(name="t2", events=other)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)]),
    ])
    out = span_reduce.reduce_trace(pd)
    assert out["spans"] == 4
    assert out["layers_s"] == pytest.approx(
        {"worker": 25e-9, "ingest": 15e-9, "flush": 10e-9, "publish": 20e-9}
    )
    assert out["idle_layers_s"] == pytest.approx(
        {"worker": 5e-9, "ingest": 5e-9, "flush": 5e-9, "publish": 10e-9}
    )
    assert out["idle_gaps"] == [
        ["worker.step", pytest.approx(20e-9)],
        ["worker.step:publish", pytest.approx(10e-9)],
        ["worker.step", pytest.approx(6e-9)],
    ]


def test_reduce_trace_needs_a_window_and_a_chip():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[_event("window", 0, 10)])])
    with pytest.raises(ValueError, match="no TPU plane"):
        span_reduce.reduce_trace(NS(planes=[host]))
    with pytest.raises(ValueError, match="no 'window'"):
        span_reduce.reduce_trace(NS(planes=[NS(name="/host:CPU", lines=[])]))


READERS = [
    ("ingest_ms_per_mtuple.tumbling", "ingest"),
    ("flush_ms_per_mtuple.tumbling", "flush"),
    ("global_merge_ms_per_mtuple.tumbling", "merge"),
    ("publish_ms_per_mtuple.tumbling", "publish"),
    ("audit_ms_per_mtuple.tumbling", "audit"),
]


@pytest.mark.parametrize("metric, layer", READERS)
def test_layer_readers(monkeypatch, metric, layer):
    read = bench_run.load_reader(metric).read
    clock = NS(self_ms=lambda: {layer: 250.0, "worker": 1.0})
    monkeypatch.setattr(tracing, "CAPTURED", clock)
    record = {"trace": {}, "counters": {"traced_tuples": 500_000}}
    assert read(record) == pytest.approx(500.0)  # 250 ms over 0.5 Mtuples
    # nothing to read: an untraced run, a layer with no span, a program
    # without the clock (an older commit)
    assert read({"trace": {}, "counters": {"traced_tuples": 0}}) is None
    monkeypatch.setattr(tracing, "CAPTURED", NS(self_ms=lambda: {"worker": 1.0}))
    assert read(record) is None
    monkeypatch.delattr(tracing, "CAPTURED")
    assert read(record) is None


def test_span_reduce_is_a_command(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "span_reduce_cli", os.path.join(bench_run.HERE, "span_reduce.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(FileNotFoundError):
        mod.reduce_dir(str(tmp_path))


@pytest.mark.parametrize(
    "cell", ["anticorr8d-angle.tumbling-1m", "uniform4d-dim.tumbling-1m-warm-pool"]
)
def test_a_traced_window_gives_every_layer_metric(monkeypatch, cell):
    """One small window of the cell through the harness's traced
    sub-window on the CPU: the program's own phases fill every layer."""
    import shutil

    from skybench import system
    from skybench.traffic import tumbling

    monkeypatch.setenv("SKYLINE_PALLAS_INTERPRET", "1")
    bench = bench_run.load_json("BENCHMARK.json")
    w = {c["name"]: c for c in bench["workloads"]}[cell]
    config = bench_run.load_json("skybench", "configs", w["config"] + ".json")
    mix = bench_run.load_json("skybench", "mixes", w["traffic"] + ".json")
    mix.update(window=3000, pool_windows=1, answer_wait_s=2)
    run = bench_run.Run(w, config, mix, 3000000125, 0.0, True)
    drv = tumbling.Driver(run, system.WorkerSystem)
    lines = drv._window(0, 0)[1]
    tracing.CAPTURED.reset()
    try:
        with run.traced():
            sut, answers = drv._one(lines, 1)
        sut.close()
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    assert [a.qid for a in answers] == [1]
    assert {"build", "transport", "parse", "worker"} <= set(tracing.CAPTURED.self_ms())
    record = {"trace": {}, "counters": {"traced_tuples": 3000}}
    for metric, _ in READERS:
        assert bench_run.load_reader(metric).read(record) > 0, metric
    tracing.CAPTURED.reset()
