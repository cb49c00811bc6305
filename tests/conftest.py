"""Test harness: force an 8-virtual-device CPU platform BEFORE jax imports.

Multi-chip TPU hardware is not available in CI; per SURVEY.md §4 item 5 the
reference simulates distribution with a local Flink mini-cluster — our
equivalent is XLA's host-platform device-count override, which exercises the
full shard_map/collective path on 8 virtual devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# A pytest plugin may have imported jax before this file, so the env vars
# above can be too late; the backend itself is still uninitialized at
# conftest time, so a config update takes effect.
import jax

jax.config.update("jax_platforms", "cpu")

import warnings

# ops.sfs jits donate their sky buffers (in-place append rounds on TPU);
# the CPU backend does not implement donation and warns per compile
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def sorted_rows(a):
    """Canonical row order for comparing point sets as multisets."""
    a = np.asarray(a, dtype=np.float64)
    return a[np.lexsort(a.T[::-1])]


def assert_same_set(a, b):
    np.testing.assert_allclose(sorted_rows(a), sorted_rows(b))


def gen_points(rng, n, d, kind) -> np.ndarray:
    """Shared workload shapes for the byte-identity property grids
    (uniform / correlated / anti-correlated), float32 in [0, 1]."""
    if kind == "uniform":
        return rng.random((n, d)).astype(np.float32)
    if kind == "correlated":
        base = rng.random((n, 1))
        return np.clip(
            base + rng.normal(0.0, 0.05, (n, d)), 0.0, 1.0
        ).astype(np.float32)
    # anti-correlated: first dim fights the second, rest random
    base = rng.random((n, d))
    x = base.copy()
    x[:, 0] = 1.0 - base[:, min(1, d - 1)]
    return x.astype(np.float32)


def fill_pset(pset, rng, x, P, max_id=None) -> None:
    """Route ``x`` across ``P`` partitions at random and flush once — the
    shared per-test state builder."""
    if max_id is None:
        max_id = x.shape[0]
    pids = rng.integers(0, P, x.shape[0])
    for p in range(P):
        rows = np.ascontiguousarray(x[pids == p])
        if rows.shape[0]:
            pset.add_batch(p, rows, max_id=max_id, now_ms=0.0)
    pset.flush_all()


def merge_state(pset):
    """One global merge with points: (counts, survivors, global_count,
    points) as host arrays — the digest the identity asserts compare."""
    counts, surv, g, pts = pset.global_merge_stats(emit_points=True)
    return np.asarray(counts), np.asarray(surv), int(g), np.asarray(pts)


def assert_same_merge(a, b, ctx="") -> None:
    """Byte-identity of two ``merge_state`` results (order included)."""
    assert (a[0] == b[0]).all(), f"counts diverge {ctx}"
    assert (a[1] == b[1]).all(), f"survivors diverge {ctx}"
    assert a[2] == b[2], f"global count diverges {ctx}"
    assert a[3].tobytes() == b[3].tobytes(), f"points diverge {ctx}"


def host_oracle(rows) -> np.ndarray:
    """The independent O(n^2 d) numpy skyline oracle, rows in canonical
    order as float32 — what the audit plane compares published answers
    against (skyline_tpu/audit)."""
    from skyline_tpu.audit import canonical_rows
    from skyline_tpu.ops.dominance import skyline_np

    rows = np.asarray(rows, dtype=np.float32)
    if rows.shape[0] == 0:
        return rows
    return canonical_rows(np.asarray(skyline_np(rows), dtype=np.float32))


def points_digest_of(points) -> str:
    """Digest of a point buffer under the serve plane's scheme — lets
    tests compare engine output to a published snapshot's ``digest``."""
    from skyline_tpu.serve.snapshot import points_digest

    return points_digest(
        np.ascontiguousarray(np.asarray(points, dtype=np.float32))
    )


def parse_prometheus_text(text: str) -> dict:
    """Minimal Prometheus text-exposition (0.0.4) parser for assertions.

    Returns ``{metric_name: [(labels_dict, float_value), ...]}`` and
    raises AssertionError on any malformed line — the tests' contract
    that /metrics stays scrapeable. Handles ``# TYPE``/``# HELP``
    comments, label sets, and ``+Inf``/``-Inf``/``NaN`` values.
    """
    series: dict = {}
    types: dict = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            assert len(parts) >= 3 and parts[1] in ("TYPE", "HELP"), (
                f"malformed comment line: {raw!r}"
            )
            if parts[1] == "TYPE":
                assert parts[3] in (
                    "counter", "gauge", "histogram", "summary", "untyped"
                ), f"bad TYPE: {raw!r}"
                types[parts[2]] = parts[3]
            continue
        head, _, val = line.rpartition(" ")
        assert head, f"malformed sample line: {raw!r}"
        labels: dict = {}
        if "{" in head:
            name, _, rest = head.partition("{")
            assert rest.endswith("}"), f"malformed labels: {raw!r}"
            for pair in filter(None, rest[:-1].split(",")):
                k, _, v = pair.partition("=")
                assert v.startswith('"') and v.endswith('"'), (
                    f"unquoted label value: {raw!r}"
                )
                labels[k] = v[1:-1]
        else:
            name = head
        assert name and name[0] not in "0123456789", f"bad name: {raw!r}"
        assert all(
            c.isalnum() or c in "_:" for c in name
        ), f"bad metric name char: {raw!r}"
        series.setdefault(name, []).append((labels, float(val)))
    series["__types__"] = types
    return series


@pytest.fixture
def prom_parse():
    return parse_prometheus_text
