"""The ``anticorr4d-grid`` cell end to end in one process on the CPU, at a
tiny size, with the Pallas kernels in interpret mode, and the reader of
``merge_pad_share``, the incremental merge's padded share."""

import shutil
from types import SimpleNamespace as NS

import pytest

from skybench import run as bench_run
from skybench import system
from skybench.traffic import tumbling
from skyline_tpu.metrics import tracing
from test_skybench_run import run_cell, small  # noqa: F401  (a fixture)

CELL = "anticorr4d-grid.tumbling-1m-warm-pool"
# the readers of the program's capture clock
CLOCK_READERS = [
    "ingest_ms_per_mtuple.tumbling",
    "flush_ms_per_mtuple.tumbling",
    "global_merge_ms_per_mtuple.tumbling",
    "publish_ms_per_mtuple.tumbling",
    "audit_ms_per_mtuple.tumbling",
    "merge_pad_share.tumbling",
]


def test_configuration_is_the_reference_run():
    bench = bench_run.load_json("BENCHMARK.json")
    w = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert w["chips"] == 1 and w["traffic"] == "tumbling-1m-warm-pool"
    config = bench_run.load_json("skybench", "configs", w["config"] + ".json")
    assert config["generator"] == {
        "distribution": "anti_correlated", "dims": 4, "d_min": 0, "d_max": 10000,
    }
    assert config["engine"] == {
        "parallelism": 4, "algo": "mr-grid", "dims": 4, "domain_max": 10000.0,
        "buffer_size": 4096, "flush_policy": "incremental",
    }
    assert config["reduced"] == []
    e2e, layer = bench_run.cell_metrics(bench, CELL)
    assert {m["name"] for m in e2e} == {"tuples_per_s", "setup_s"}
    assert sorted(m["name"] for m in layer) == sorted(
        CLOCK_READERS
        + [
            "dominance_kernel_ms_per_mtuple.tumbling",
            "device_idle_share.tumbling",
            "parse_ms_per_mtuple.tumbling",
        ]
    )


def test_cell_end_to_end_is_correct(small, capsys):  # noqa: F811
    last = run_cell(capsys, CELL)
    assert last["correct"] is True, last
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["metrics"]) == {"setup_s", "tuples_per_s"}
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert all(c["limit"] == 0 for c in last["checks"].values())


def test_control_is_not_correct(small, capsys):  # noqa: F811
    """The reference in the program's place, one trigger stale."""
    last = run_cell(capsys, CELL, make_system=system.ControlSystem)
    assert last["correct"] is False


@pytest.mark.parametrize("cell", [CELL, "uniform4d-dim.tumbling-1m-warm-pool"])
def test_a_traced_window_gives_the_merge_pad_share(monkeypatch, cell):
    """One small window of each incremental cell through the harness's
    traced sub-window: the program's phases fill every layer and the
    merge's counts are read."""
    monkeypatch.setenv("SKYLINE_PALLAS_INTERPRET", "1")
    bench = bench_run.load_json("BENCHMARK.json")
    w = {c["name"]: c for c in bench["workloads"]}[cell]
    assert cell in {c["name"]: c for c in bench["per_layer"]}[
        "merge_pad_share.tumbling"]["workloads"]
    config = bench_run.load_json("skybench", "configs", w["config"] + ".json")
    mix = bench_run.load_json("skybench", "mixes", w["traffic"] + ".json")
    mix.update(window=3000, pool_windows=1, answer_wait_s=2)
    run = bench_run.Run(w, config, mix, 3000000126, 0.0, True)
    drv = tumbling.Driver(run, system.WorkerSystem)
    lines = drv._window(0, 0)[1]
    tracing.CAPTURED.reset()
    try:
        with run.traced():
            sut, answers = drv._one(lines, 1)
        sut.close()
        assert [a.qid for a in answers] == [1]
        counts = tracing.CAPTURED.counts()
        assert 0 <= counts["flush/merge_live_pairs"] <= counts["flush/merge_pairs"]
        record = {"trace": {}, "counters": {"traced_tuples": 3000}}
        for metric in CLOCK_READERS:
            assert bench_run.load_reader(metric).read(record) > 0, metric
        assert bench_run.load_reader("merge_pad_share.tumbling").read(record) <= 100.0
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        tracing.CAPTURED.reset()


@pytest.mark.parametrize(
    "counts, want",
    [
        ({"flush/merge_pairs": 1000, "flush/merge_live_pairs": 250}, 75.0),
        ({"flush/merge_pairs": 1000, "flush/merge_live_pairs": 1000}, 0.0),
        ({"flush/merge_pairs": 1000}, 100.0),
        ({"flush/merge_live_pairs": 5}, None),
        ({}, None),
    ],
)
def test_merge_pad_share_reader(monkeypatch, counts, want):
    read = bench_run.load_reader("merge_pad_share.tumbling").read
    record = {"trace": {}, "counters": {"traced_tuples": 1000}}
    monkeypatch.setattr(tracing, "CAPTURED", NS(counts=lambda: counts))
    got = read(record)
    assert got == (None if want is None else pytest.approx(want))


def test_merge_pad_share_reads_nothing_from_an_older_program(monkeypatch):
    """A clock without counts (the program before them), or no clock."""
    read = bench_run.load_reader("merge_pad_share.tumbling").read
    record = {"trace": {}, "counters": {"traced_tuples": 1000}}
    monkeypatch.setattr(tracing, "CAPTURED", NS(self_ms=lambda: {"flush": 1.0}))
    assert read(record) is None
    monkeypatch.delattr(tracing, "CAPTURED")
    assert read(record) is None
