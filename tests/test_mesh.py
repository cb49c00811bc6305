"""Sharded two-phase skyline: correctness + invariance over device counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skyline_tpu.ops import skyline_np, pad_window
from skyline_tpu.parallel import make_mesh
from skyline_tpu.parallel.mesh import build_two_phase, shard_rows

from conftest import sorted_rows as _sorted_rows


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_two_phase_matches_oracle(rng, n_dev):
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    mesh = make_mesh(n_dev)
    step = build_two_phase(mesh, local_block=64, cross_block=128)
    n, d = 512, 3
    x = rng.uniform(0, 1000, size=(n, d)).astype(np.float32)
    vals, valid = pad_window(x, n)  # no-op pad; exact fit
    xs, vs = shard_rows(mesh, np.asarray(vals), np.asarray(valid))
    local_keep, global_keep = step(xs, vs)
    got = x[np.asarray(global_keep)]
    np.testing.assert_allclose(_sorted_rows(got), _sorted_rows(skyline_np(x)))
    # local phase must be a superset of the global skyline
    assert (np.asarray(local_keep) | ~np.asarray(global_keep)).all()


def test_device_count_invariance(rng):
    # The result must not depend on how many devices the window is sharded
    # over (the invariant the reference checks only by comparing CSVs by eye,
    # SURVEY.md §4 item 3).
    n, d = 1024, 4
    x = rng.uniform(0, 1000, size=(n, d)).astype(np.float32)
    valid = np.ones(n, dtype=bool)
    results = []
    for n_dev in (1, 4, 8):
        mesh = make_mesh(n_dev)
        step = build_two_phase(mesh, local_block=64, cross_block=256)
        xs, vs = shard_rows(mesh, x, valid)
        _, gk = step(xs, vs)
        results.append(_sorted_rows(x[np.asarray(gk)]))
    np.testing.assert_allclose(results[0], results[1])
    np.testing.assert_allclose(results[0], results[2])


def test_two_phase_with_invalid_rows(rng):
    # padding rows sharded onto devices must never surface as survivors
    mesh = make_mesh(4)
    step = build_two_phase(mesh, local_block=32, cross_block=64)
    n, d = 256, 2
    x = rng.uniform(0, 1000, size=(200, d)).astype(np.float32)
    vals, valid = pad_window(x, n)
    # scatter the valid rows across shards unevenly: interleave pads
    perm = rng.permutation(n)
    vals = np.asarray(vals)[perm]
    valid = np.asarray(valid)[perm]
    xs, vs = shard_rows(mesh, vals, valid)
    _, gk = step(xs, vs)
    gk = np.asarray(gk)
    assert not (gk & ~valid).any()
    np.testing.assert_allclose(
        _sorted_rows(vals[gk]), _sorted_rows(skyline_np(x))
    )


class _FakeTpu:
    platform = "tpu"

    def __init__(self, i):
        self.id = i


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_too_few_devices_raise_on_accelerators_only(monkeypatch, platform):
    """Asking for more chips than an accelerator host has raises (chip
    groups and meshes never wrap or shrink silently there); the CPU
    backend keeps wrapping / taking what it has."""
    from skyline_tpu.parallel import chips, mesh

    if platform == "tpu":
        fake = [_FakeTpu(0)]
        monkeypatch.setattr(chips.jax, "devices", lambda: fake)
        with pytest.raises(ValueError, match="only 1 tpu"):
            chips.chip_devices(4)
        with pytest.raises(ValueError, match="only 1 tpu"):
            mesh.make_mesh(4)
    else:
        devs = jax.devices()[:1]
        monkeypatch.setattr(chips.jax, "devices", lambda: devs)
        assert chips.chip_devices(4) == devs * 4
        assert mesh.make_mesh(4).devices.size == 1
