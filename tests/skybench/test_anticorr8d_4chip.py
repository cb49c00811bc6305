"""The ``anticorr8d-angle-4chip`` cell end to end in one process on the CPU,
at a tiny size, with the Pallas kernels in interpret mode: the reference's
parallelism-4 MR-Angle job with one subtask per chip, run by the sharded
engine over four of the test harness's virtual devices; the readers of
``cross_chip_merge_ms_per_mtuple`` and ``chip_row_share_max``; and a chip
left out of the tournament caught by the check."""

import shutil
from types import SimpleNamespace as NS

import pytest

from skybench import run as bench_run
from skybench import system
from skybench.traffic import tumbling
from skyline_tpu.metrics import tracing
from skyline_tpu.resilience import faults
from test_skybench_run import run_cell, small  # noqa: F401  (a fixture)

CELL = "anticorr8d-angle-4chip.tumbling-1m-warm-pool"
ONE_CHIP = "anticorr8d-angle"
# the readers of the program's capture clock
CLOCK_READERS = [
    "ingest_ms_per_mtuple.tumbling",
    "flush_ms_per_mtuple.tumbling",
    "global_merge_ms_per_mtuple.tumbling",
    "publish_ms_per_mtuple.tumbling",
    "audit_ms_per_mtuple.tumbling",
    "cross_chip_merge_ms_per_mtuple.tumbling",
]


@pytest.fixture
def sfs_rounds(monkeypatch):
    """The chip never lists the host cascade; off, the CPU runs the SFS
    rounds the chip runs, on each chip's group."""
    monkeypatch.setenv("SKYLINE_SORTED_SFS", "off")


def test_configuration_is_the_reference_job_on_four_chips():
    bench = bench_run.load_json("BENCHMARK.json")
    w = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert w["chips"] == 4 and w["traffic"] == "tumbling-1m-warm-pool"
    config = bench_run.load_json("skybench", "configs", w["config"] + ".json")
    flat = bench_run.load_json("skybench", "configs", ONE_CHIP + ".json")
    assert config["generator"] == flat["generator"] == {
        "distribution": "anti_correlated", "dims": 8, "d_min": 0, "d_max": 10000,
    }
    assert config["engine"] == {**flat["engine"], "mesh_chips": 4}
    assert config["chips"] == 4 and config["reduced"] == []
    assert config["guarantees"] == flat["guarantees"]
    bench_config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    assert config["source"] == bench_config["source"] != flat["source"]
    assert "one subtask per chip" in config["source"]
    e2e, layer = bench_run.cell_metrics(bench, CELL)
    assert {m["name"] for m in e2e} == {"tuples_per_s", "setup_s"}
    assert sorted(m["name"] for m in layer) == sorted(
        CLOCK_READERS
        + [
            "dominance_kernel_ms_per_mtuple.tumbling",
            "device_idle_share.tumbling",
            "parse_ms_per_mtuple.tumbling",
            "chip_row_share_max.tumbling",
        ]
    )
    new = {m["name"]: m for m in bench["per_layer"]}
    assert new["cross_chip_merge_ms_per_mtuple.tumbling"]["layer"] == "cross_chip"
    assert new["chip_row_share_max.tumbling"]["layer"] == "flush"
    for name in ("cross_chip_merge_ms_per_mtuple.tumbling", "chip_row_share_max.tumbling"):
        assert new[name]["workloads"] == [CELL]


def test_cell_end_to_end_is_correct(small, sfs_rounds, capsys):  # noqa: F811
    last = run_cell(capsys, CELL)
    assert last["correct"] is True, last
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["metrics"]) == {"setup_s", "tuples_per_s"}
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert all(c["limit"] == 0 for c in last["checks"].values())
    assert last["device"]["count"] == 4


def test_control_is_not_correct(small, capsys):  # noqa: F811
    """The reference in the program's place, one trigger stale."""
    last = run_cell(capsys, CELL, make_system=system.ControlSystem)
    assert last["correct"] is False


def test_a_chip_left_out_of_the_tournament_is_not_correct(small, capsys, monkeypatch):  # noqa: F811
    """Chip 0's group crashes at every level-1 merge (the existing
    ``sharded.chip_merge`` fault point) under a merge deadline: each answer
    is the skyline of chips 1-3 alone, marked partial, and the check sees
    it."""
    monkeypatch.setenv("SKYLINE_CHIP_MERGE_DEADLINE_MS", "600000")
    monkeypatch.setenv("SKYLINE_CHIP_MERGE_RETRIES", "0")
    plan = ",".join(f"crash@sharded.chip_merge#0:{k}" for k in range(1, 65))
    faults.install_plan(faults.FaultPlan.parse(plan))
    try:
        last = run_cell(capsys, CELL, seconds=1.0)
    finally:
        faults.clear()
    assert last["correct"] is False
    assert last["checks"]["size_off"]["value"] > 0


def test_a_traced_window_reads_the_cross_chip_metrics(monkeypatch, sfs_rounds):
    """One small window of the cell through the harness's traced
    sub-window: the chip tournament's phases and the per-chip flush counts
    are kept and read."""
    monkeypatch.setenv("SKYLINE_PALLAS_INTERPRET", "1")
    bench = bench_run.load_json("BENCHMARK.json")
    w = {c["name"]: c for c in bench["workloads"]}[CELL]
    config = bench_run.load_json("skybench", "configs", w["config"] + ".json")
    mix = bench_run.load_json("skybench", "mixes", w["traffic"] + ".json")
    mix.update(window=3000, pool_windows=1, answer_wait_s=2)
    run = bench_run.Run(w, config, mix, 3000000129, 0.0, True)
    drv = tumbling.Driver(run, system.WorkerSystem)
    lines = drv._window(0, 0)[1]
    tracing.CAPTURED.reset()
    try:
        with run.traced():
            sut, answers = drv._one(lines, 1)
        engine = sut.worker.engine
        assert type(engine).__name__ == "ShardedEngine" and engine.pset.chips == 4
        sut.close()
        assert [a.qid for a in answers] == [1]
        counts = tracing.CAPTURED.counts()
        assert counts["flush/chip_rows"] == 3000
        assert 750 <= counts["flush/chip_rows_max"] <= 3000
        assert 1 <= counts["cross_chip/chips_considered"] <= 4
        assert 0 <= counts["cross_chip/chips_pruned"] < counts["cross_chip/chips_considered"]
        assert counts["cross_chip/rows_shipped"] >= 0
        # each chip's lazy flush ran its own SFS rounds
        assert 0 <= counts["flush/sfs_tiles_run"] <= counts["flush/sfs_tiles"]
        assert counts["flush/sfs_tiles"] > 0
        record = {"trace": {}, "counters": {"traced_tuples": 3000}}
        for metric in CLOCK_READERS:
            assert bench_run.load_reader(metric).read(record) > 0, metric
        share = bench_run.load_reader("chip_row_share_max.tumbling").read(record)
        assert share == pytest.approx(100.0 * counts["flush/chip_rows_max"] / 3000)
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        tracing.CAPTURED.reset()


@pytest.mark.parametrize(
    "counts, want",
    [
        ({"flush/chip_rows": 1000, "flush/chip_rows_max": 533}, 53.3),
        ({"flush/chip_rows": 1000, "flush/chip_rows_max": 250}, 25.0),
        ({"flush/chip_rows": 1000, "flush/chip_rows_max": 1000}, 100.0),
        ({"flush/chip_rows": 0, "flush/chip_rows_max": 0}, None),
        ({"flush/chip_rows_max": 5}, None),
        ({}, None),
    ],
)
def test_chip_row_share_max_reader(monkeypatch, counts, want):
    read = bench_run.load_reader("chip_row_share_max.tumbling").read
    record = {"trace": {}, "counters": {"traced_tuples": 1000}}
    monkeypatch.setattr(tracing, "CAPTURED", NS(counts=lambda: counts))
    got = read(record)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize(
    "metric", ["chip_row_share_max.tumbling", "cross_chip_merge_ms_per_mtuple.tumbling"]
)
def test_new_readers_read_nothing_from_a_one_chip_or_older_program(monkeypatch, metric):
    """A clock without the layer or the counts (one chip, or the program
    before them), or no clock at all."""
    read = bench_run.load_reader(metric).read
    record = {"trace": {}, "counters": {"traced_tuples": 1000}}
    monkeypatch.setattr(
        tracing, "CAPTURED", NS(self_ms=lambda: {"flush": 1.0}, counts=lambda: {})
    )
    assert read(record) is None
    monkeypatch.delattr(tracing, "CAPTURED")
    assert read(record) is None
