"""Config validation, checkpoint/resume, tracing."""

import numpy as np
import pytest

from skyline_tpu.metrics.tracing import Tracer
from skyline_tpu.ops import skyline_np
from skyline_tpu.stream import EngineConfig, SkylineEngine
from skyline_tpu.utils.checkpoint import load_engine, save_engine
from skyline_tpu.utils.config import JobConfig, parse_job_args


def test_job_config_defaults_match_reference():
    # FlinkSkyline.java:62-72 defaults
    cfg = JobConfig()
    assert cfg.parallelism == 4
    assert cfg.algo == "mr-angle"
    assert cfg.input_topic == "input-tuples"
    assert cfg.query_topic == "queries"
    assert cfg.output_topic == "output-skyline"
    assert cfg.domain == 1000.0
    assert cfg.dims == 2
    assert cfg.engine_config().num_partitions == 8


def test_job_config_validation():
    with pytest.raises(ValueError):
        JobConfig(algo="nope")
    with pytest.raises(ValueError):
        JobConfig(parallelism=0)
    with pytest.raises(ValueError):
        JobConfig(domain=-1)


def test_parse_job_args_flags():
    cfg = parse_job_args(["--parallelism", "2", "--algo", "mr-grid",
                          "--dims", "4", "--domain", "500"])
    assert cfg.parallelism == 2 and cfg.algo == "mr-grid"
    assert cfg.dims == 4 and cfg.domain == 500.0


def test_parse_job_args_env_override(monkeypatch):
    monkeypatch.setenv("SKYLINE_DIMS", "6")
    assert parse_job_args([]).dims == 6
    # CLI beats env
    assert parse_job_args(["--dims", "3"]).dims == 3


def test_checkpoint_resume_same_results(rng, tmp_path):
    cfg = EngineConfig(parallelism=2, algo="mr-angle", dims=3, buffer_size=128)
    x = rng.uniform(0, 1000, size=(2000, 3)).astype(np.float32)
    x1, x2 = x[:1200], x[1200:]

    # run A: straight through
    ea = SkylineEngine(cfg)
    ea.process_records(np.arange(1200, dtype=np.int64), x1)
    ea.process_records(np.arange(1200, 2000, dtype=np.int64), x2)
    ea.process_trigger("0,0")
    (ra,) = ea.poll_results()

    # run B: checkpoint mid-stream (with pending rows + a pending query),
    # restore into a fresh engine, continue
    eb = SkylineEngine(cfg)
    eb.process_records(np.arange(1200, dtype=np.int64), x1)
    eb.process_trigger("9,1900")  # deferred: barrier beyond current ids
    assert eb.poll_results() == []
    ckpt = str(tmp_path / "engine.npz")
    save_engine(eb, ckpt)
    restored = load_engine(ckpt)
    assert restored.inflight_queries == 1
    restored.process_records(np.arange(1200, 2000, dtype=np.int64), x2)
    results = restored.poll_results()
    assert len(results) == 1  # the deferred query fires after resume
    assert results[0]["query_id"] == "9"
    assert results[0]["skyline_size"] == skyline_np(x).shape[0]
    assert ra["skyline_size"] == results[0]["skyline_size"]


def test_checkpoint_preserves_counters(rng, tmp_path):
    cfg = EngineConfig(parallelism=1, algo="mr-dim", dims=2, buffer_size=64)
    e = SkylineEngine(cfg)
    e.process_records(np.arange(500, dtype=np.int64),
                      rng.uniform(0, 1000, size=(500, 2)).astype(np.float32))
    ckpt = str(tmp_path / "c.npz")
    save_engine(e, ckpt)
    r = load_engine(ckpt)
    assert r.records_in == 500
    assert [p.max_seen_id for p in r.partitions] == [p.max_seen_id for p in e.partitions]
    assert [p.records_seen for p in r.partitions] == [p.records_seen for p in e.partitions]


def test_tracer_phases():
    tr = Tracer()
    with tr.phase("a"):
        with tr.phase("b"):
            pass
    with tr.phase("a"):
        pass
    rep = tr.report()
    assert rep["a"]["count"] == 2
    assert rep["b"]["count"] == 1
    assert rep["a"]["total_ms"] >= 0


def test_checkpoint_preserves_all_config_flags(rng, tmp_path):
    """Watchdog/prefilter flags must survive restore — a reverted
    query_timeout_ms=0 would resurrect the reference's wait-forever latch."""
    from skyline_tpu.utils.checkpoint import load_engine, save_engine

    cfg = EngineConfig(parallelism=2, algo="mr-grid", dims=2,
                       domain_max=100.0, query_timeout_ms=1234.5,
                       grid_prefilter=True)
    eng = SkylineEngine(cfg)
    x = rng.uniform(0, 100, size=(100, 2)).astype(np.float32)
    eng.process_records(np.arange(100), x)
    path = str(tmp_path / "ck.npz")
    save_engine(eng, path)
    restored = load_engine(path)
    assert restored.config == cfg


def test_checkpoint_lazy_policy_roundtrip(rng, tmp_path):
    # a lazy-policy engine (unflushed window accumulated on host) must
    # restore with its policy AND its pending rows intact, and answer the
    # same query identically
    from skyline_tpu.ops import skyline_np
    from skyline_tpu.stream import EngineConfig, SkylineEngine
    from skyline_tpu.utils.checkpoint import load_engine, save_engine

    cfg = EngineConfig(parallelism=2, algo="mr-angle", dims=3,
                       domain_max=1000.0, flush_policy="lazy",
                       emit_skyline_points=True)
    eng = SkylineEngine(cfg)
    x = rng.uniform(0, 1000, size=(4000, 3)).astype(np.float32)
    ids = np.arange(4000, dtype=np.int64)
    eng.process_records(ids[:2500], x[:2500])
    path = str(tmp_path / "lazy.npz")
    save_engine(eng, path)
    restored = load_engine(path)
    assert restored.config.flush_policy == "lazy"
    assert restored.pset.flush_policy == "lazy"
    restored.process_records(ids[2500:], x[2500:])
    restored.process_trigger("0,0")
    (r,) = restored.poll_results()
    oracle = skyline_np(x)
    assert r["skyline_size"] == oracle.shape[0]
    got = np.asarray(r["skyline_points"])
    assert set(map(tuple, got.round(3))) == set(map(tuple, oracle.round(3)))
