"""The reader of ``merge_tile_skip_share``, the share of the incremental
merge's dominance-kernel tile pairs whose body the sum-bound skip spares,
and a traced window of each incremental cell that fills it."""

import shutil
from types import SimpleNamespace as NS

import pytest

from skybench import run as bench_run
from skybench import system
from skybench.traffic import tumbling
from skyline_tpu.metrics import tracing

METRIC = "merge_tile_skip_share.tumbling"
INCREMENTAL = ["anticorr4d-grid.tumbling-1m-warm-pool", "uniform4d-dim.tumbling-1m-warm-pool"]


def test_metric_is_a_dominance_kernel_counter():
    bench = bench_run.load_json("BENCHMARK.json")
    m = {c["name"]: c for c in bench["per_layer"]}[METRIC]
    assert (m["unit"], m["better"], m["source"]) == ("%", "higher", "program_counter")
    assert (m["layer"], m["moves"]) == ("dominance kernels", "tuples_per_s")
    assert set(m["workloads"]) <= set(INCREMENTAL)
    for cell in m["workloads"]:
        assert METRIC in {x["name"] for x in bench_run.cell_metrics(bench, cell)[1]}


@pytest.mark.parametrize("cell", INCREMENTAL)
def test_a_traced_window_counts_the_merge_tiles(monkeypatch, cell):
    """One small window of each incremental cell through the harness's
    traced sub-window: the merge's tile counts are kept and read."""
    monkeypatch.setenv("SKYLINE_PALLAS_INTERPRET", "1")
    bench = bench_run.load_json("BENCHMARK.json")
    w = {c["name"]: c for c in bench["workloads"]}[cell]
    config = bench_run.load_json("skybench", "configs", w["config"] + ".json")
    mix = bench_run.load_json("skybench", "mixes", w["traffic"] + ".json")
    mix.update(window=3000, pool_windows=1, answer_wait_s=2)
    run = bench_run.Run(w, config, mix, 3000000127, 0.0, True)
    drv = tumbling.Driver(run, system.WorkerSystem)
    lines = drv._window(0, 0)[1]
    tracing.CAPTURED.reset()
    try:
        with run.traced():
            sut, answers = drv._one(lines, 1)
        sut.close()
        assert [a.qid for a in answers] == [1]
        counts = tracing.CAPTURED.counts()
        assert 0 <= counts["flush/merge_tiles_run"] <= counts["flush/merge_tiles"]
        share = bench_run.load_reader(METRIC).read({"trace": {}, "counters": {}})
        assert 0.0 <= share <= 100.0
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        tracing.CAPTURED.reset()


@pytest.mark.parametrize(
    "counts, want",
    [
        ({"flush/merge_tiles": 1000, "flush/merge_tiles_run": 150}, 85.0),
        ({"flush/merge_tiles": 1000, "flush/merge_tiles_run": 1000}, 0.0),
        ({"flush/merge_tiles": 1000, "flush/merge_tiles_run": 0}, 100.0),
        # launched but never harvested inside the capture: nothing to read
        ({"flush/merge_tiles": 1000}, None),
        ({"flush/merge_tiles_run": 5}, None),
        ({"flush/merge_pairs": 1000, "flush/merge_live_pairs": 250}, None),
        ({}, None),
    ],
)
def test_merge_tile_skip_share_reader(monkeypatch, counts, want):
    read = bench_run.load_reader(METRIC).read
    record = {"trace": {}, "counters": {"traced_tuples": 1000}}
    monkeypatch.setattr(tracing, "CAPTURED", NS(counts=lambda: counts))
    got = read(record)
    assert got == (None if want is None else pytest.approx(want))


def test_reads_nothing_from_an_older_program(monkeypatch):
    """A clock without counts (the program before them), or no clock."""
    read = bench_run.load_reader(METRIC).read
    record = {"trace": {}, "counters": {"traced_tuples": 1000}}
    monkeypatch.setattr(tracing, "CAPTURED", NS(self_ms=lambda: {"flush": 1.0}))
    assert read(record) is None
    monkeypatch.delattr(tracing, "CAPTURED")
    assert read(record) is None
