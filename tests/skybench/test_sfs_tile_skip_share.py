"""The reader of ``sfs_tile_skip_share``, the share of the lazy flush's SFS
rounds' dominance-kernel tile pairs whose body the sum-bound skip spares,
and a traced window of the lazy-flush cell that fills it."""

import shutil
from types import SimpleNamespace as NS

import pytest

from skybench import run as bench_run
from skybench import system
from skybench.traffic import tumbling
from skyline_tpu.metrics import tracing

METRIC = "sfs_tile_skip_share.tumbling"
LAZY = "anticorr8d-angle.tumbling-1m"


def test_metric_is_a_dominance_kernel_counter():
    bench = bench_run.load_json("BENCHMARK.json")
    m = {c["name"]: c for c in bench["per_layer"]}[METRIC]
    assert (m["unit"], m["better"], m["source"]) == ("%", "higher", "program_counter")
    assert (m["layer"], m["moves"]) == ("dominance kernels", "tuples_per_s")
    assert m["workloads"] == [LAZY]
    assert METRIC in {x["name"] for x in bench_run.cell_metrics(bench, LAZY)[1]}
    for w in bench["workloads"]:
        if w["name"] != LAZY:
            assert METRIC not in {x["name"] for x in bench_run.cell_metrics(bench, w["name"])[1]}


def test_a_traced_window_counts_the_sfs_tiles(monkeypatch):
    """One small window of the lazy-flush cell through the harness's traced
    sub-window: the SFS rounds' tile counts are kept and read."""
    monkeypatch.setenv("SKYLINE_PALLAS_INTERPRET", "1")
    # the chip never lists the host cascade; off, the CPU runs the SFS
    # rounds the chip runs
    monkeypatch.setenv("SKYLINE_SORTED_SFS", "off")
    bench = bench_run.load_json("BENCHMARK.json")
    w = {c["name"]: c for c in bench["workloads"]}[LAZY]
    config = bench_run.load_json("skybench", "configs", w["config"] + ".json")
    mix = bench_run.load_json("skybench", "mixes", w["traffic"] + ".json")
    mix.update(window=3000, pool_windows=1, answer_wait_s=2)
    run = bench_run.Run(w, config, mix, 3000000128, 0.0, True)
    drv = tumbling.Driver(run, system.WorkerSystem)
    lines = drv._window(0, 0)[1]
    tracing.CAPTURED.reset()
    try:
        with run.traced():
            sut, answers = drv._one(lines, 1)
        sut.close()
        assert [a.qid for a in answers] == [1]
        counts = tracing.CAPTURED.counts()
        assert 0 <= counts["flush/sfs_tiles_run"] <= counts["flush/sfs_tiles"]
        share = bench_run.load_reader(METRIC).read({"trace": {}, "counters": {}})
        assert 0.0 <= share <= 100.0
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        tracing.CAPTURED.reset()


@pytest.mark.parametrize(
    "counts, want",
    [
        ({"flush/sfs_tiles": 1000, "flush/sfs_tiles_run": 40}, 96.0),
        ({"flush/sfs_tiles": 1000, "flush/sfs_tiles_run": 1000}, 0.0),
        ({"flush/sfs_tiles": 1000, "flush/sfs_tiles_run": 0}, 100.0),
        # launched but never harvested inside the capture: nothing to read
        ({"flush/sfs_tiles": 1000}, None),
        ({"flush/sfs_tiles_run": 5}, None),
        # the incremental merge's counts are another metric's
        ({"flush/merge_tiles": 1000, "flush/merge_tiles_run": 150}, None),
        ({}, None),
    ],
)
def test_sfs_tile_skip_share_reader(monkeypatch, counts, want):
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
