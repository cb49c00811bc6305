"""The phase tracer on the profiler's clock (skyline_tpu/metrics/tracing.py):
phases land in a JAX profiler capture with their layer, every phase the
program opens has a layer, and one timing per boundary feeds the span ring."""

import glob
import os
import re

import pytest
from jax.profiler import ProfileData, TraceAnnotation

import jax
from skyline_tpu.metrics import tracing
from skyline_tpu.metrics.tracing import NULL_TRACER, Tracer, layer_of
from skyline_tpu.telemetry.spans import SpanRecorder

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "skyline_tpu")
# a phase name as the program passes it: to phase(...) / span_phase(...), to
# sky_counts(...), or as a parameter's default
PHASE_LITERAL = re.compile(
    r'(?:\.(?:span_)?phase\(|sky_counts\(|phase: str = )\s*"([^"]+)"'
)


def _capture(tmp_path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        body()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    return [
        (e.name, dict(e.stats), e.start_ns, e.end_ns)
        for p in ProfileData.from_file(path).planes
        if p.name.startswith("/host")
        for ln in p.lines
        for e in ln.events
    ]


def test_a_phase_lands_in_a_capture_with_its_layer(tmp_path):
    tr = Tracer()
    tracing.CAPTURED.reset()

    def body():
        with tr.phase("worker/ingest"):
            with tr.phase("ingest/batch"):
                with tr.phase("flush/auto"):
                    pass

    events = _capture(tmp_path, body)
    got = {name: stats for name, stats, _, _ in events if "layer" in stats}
    assert got == {
        "worker/ingest": {"layer": "worker"},
        "ingest/batch": {"layer": "ingest"},
        "flush/auto": {"layer": "flush"},
    }
    # the same entries still count in the phase totals
    assert {k: v["count"] for k, v in tr.report().items()} == {
        "worker/ingest": 1, "ingest/batch": 1, "flush/auto": 1,
    }


def test_outside_a_capture_a_phase_emits_nothing():
    assert not TraceAnnotation.is_enabled()
    tracing.CAPTURED.reset()
    tr = Tracer()
    with tr.phase("flush/sort") as ph:
        pass
    assert ph._ann is None
    assert tracing.CAPTURED.self_ms() == {}
    assert tr.report()["flush/sort"]["count"] == 1


def test_captured_self_time_counts_each_instant_once(tmp_path):
    tr = Tracer()
    tracing.CAPTURED.reset()
    outer = []

    def body():
        with tr.phase("worker/query") as ph:
            with tr.phase("global_merge"):
                with tr.phase("query/global_stats_sync"):
                    sum(range(20000))
                sum(range(20000))
            with tr.phase("publish/emit"):
                with tr.phase("audit/check"):
                    sum(range(20000))
        outer.append(ph)

    _capture(tmp_path, body)
    ms = tracing.CAPTURED.self_ms()
    assert set(ms) == {"worker", "merge", "publish", "audit"}
    assert all(v >= 0 for v in ms.values())
    # self times of nested phases tile the outermost one exactly
    assert sum(ms.values()) == pytest.approx(outer[0].ms, abs=1e-6)


def test_every_phase_the_program_opens_has_a_layer():
    names = set()
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        with open(path) as f:
            names |= set(PHASE_LITERAL.findall(f.read()))
    assert len(names) > 30, sorted(names)  # the scan found the call sites
    missing = []
    for n in sorted(names):
        try:
            layer_of(n)
        except KeyError:
            missing.append(n)
    assert not missing
    assert set(tracing.LAYERS.values()) == {
        "transport", "parse", "ingest", "flush", "merge", "publish", "audit",
        "build", "worker", "cross_chip",
    }


@pytest.mark.parametrize(
    "name, layer",
    [
        ("worker/poll", "transport"),
        ("worker/parse", "parse"),
        ("worker/build", "build"),
        ("worker/ingest", "worker"),
        ("route", "ingest"),
        ("ingest/devroute", "ingest"),
        ("flush/count_sync", "flush"),
        ("global_merge", "merge"),
        ("global_merge/harvest", "merge"),
        ("query/points_transfer", "merge"),
        ("publish", "publish"),
        ("publish/produce", "publish"),
        ("audit/state_sync", "audit"),
        ("cross_chip/level1", "cross_chip"),
        ("cross_chip/level2", "cross_chip"),
    ],
)
def test_layer_of(name, layer):
    assert layer_of(name) == layer


@pytest.mark.parametrize("name", ["worker/other", "merge", "flushing", "other"])
def test_an_unknown_phase_has_no_layer(name):
    with pytest.raises(KeyError):
        layer_of(name)


@pytest.mark.parametrize("tracer", [Tracer(), NULL_TRACER], ids=["tracer", "null"])
def test_span_phase_times_once_for_ring_and_totals(tracer):
    ring = SpanRecorder()
    args = {}
    with tracer.span_phase(
        "global_merge", ring, "merge", trace_id="t-1", tid=3, args=args
    ) as ph:
        args["skyline_size"] = 7
    (span,) = ring.snapshot()
    assert span == {
        "name": "merge", "start_ns": ph.t0, "dur_ns": ph.t1 - ph.t0, "tid": 3,
        "trace_id": "t-1", "args": {"skyline_size": 7},
    }
    assert ph.ms == (ph.t1 - ph.t0) / 1e6
    totals = tracer.report()
    if tracer is NULL_TRACER:
        assert totals == {}
    else:
        assert totals["global_merge"]["total_ms"] == ph.ms


def test_null_tracer_phase_is_a_no_op():
    with NULL_TRACER.phase("flush/assemble") as ph:
        pass
    assert not hasattr(ph, "t0")
    assert NULL_TRACER.report() == {}
