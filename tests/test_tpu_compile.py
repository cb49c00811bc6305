"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed here and compiles for a topology that is
described but not attached (``jax.experimental.topologies``). Interpret-mode
tests cannot see what Mosaic refuses (layouts, scoped VMEM); these can. Each
compile takes a second or a few; nothing runs, so they say nothing about
results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and under xdist each
worker imports every test file. Code that asks ``jax.default_backend()``
still sees the CPU, so the tests steer ``on_tpu`` themselves and compile
fresh jits of the undecorated functions (no trace cached by a CPU test is
reused).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

D = 8
CAP = 65536  # north-star initial_capacity bucket
BLOCK = 8192  # north-star buffer_size
WINDOW = 1_000_000  # north-star window_capacity
# Kernels that end in an XLA sort/compaction compile at a smaller bucket:
# a 65536-row argsort alone takes ~25 s to compile for the chip.
SMALL = 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else libtpu logs under /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Make trace-time ``on_tpu()`` checks take their TPU branch."""
    from skyline_tpu.ops import dispatch, sfs
    from skyline_tpu.stream import device_window

    for mod in (dispatch, sfs, device_window):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
    monkeypatch.delenv("SKYLINE_PALLAS_INTERPRET", raising=False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mp", [False, True])
def test_dominated_by_any_triangular(one_chip, mp):
    from skyline_tpu.ops.pallas_dominance import dominated_by_any_pallas

    n = CAP
    compiled = dominated_by_any_pallas.lower(
        _spec((D, n), jnp.float32, one_chip),
        _spec((n,), jnp.bool_, one_chip),
        triangular=True,
        mp=mp,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("mp", [False, True])
def test_dominated_by_rectangular(one_chip, mp):
    from skyline_tpu.ops.pallas_dominance import dominated_by_pallas

    compiled = dominated_by_pallas.lower(
        _spec((D, CAP), jnp.float32, one_chip),
        _spec((CAP,), jnp.bool_, one_chip),
        _spec((D, BLOCK), jnp.float32, one_chip),
        mp=mp,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("mp", [False, True])
def test_skyline_mask(one_chip, mp):
    from skyline_tpu.ops.pallas_dominance import skyline_mask_pallas

    compiled = skyline_mask_pallas.lower(
        _spec((SMALL, D), jnp.float32, one_chip),
        _spec((SMALL,), jnp.bool_, one_chip),
        mp=mp,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("mp", [False, True])
def test_sfs_round_north_star(one_chip, as_tpu, mp):
    """The lazy flush's device-window SFS round at the north-star buckets."""
    from skyline_tpu.stream.device_window import sfs_round_at

    fn = jax.jit(
        sfs_round_at.__wrapped__,
        static_argnames=("B", "active", "mp"),
        donate_argnums=(0,),
    )
    i32 = jnp.int32
    compiled = fn.lower(
        _spec((CAP, D), jnp.float32, one_chip),
        _spec((), i32, one_chip),
        _spec((WINDOW, D), jnp.float32, one_chip),
        _spec((), i32, one_chip),
        _spec((), i32, one_chip),
        B=BLOCK,
        active=CAP,
        mp=mp,
    ).compile()
    _assert_kernel(compiled)


def test_sfs_cleanup(one_chip, as_tpu):
    from skyline_tpu.ops.sfs import sfs_cleanup

    P = 8
    fn = jax.jit(
        sfs_cleanup.__wrapped__,
        static_argnames=("old_active", "active"),
        donate_argnums=(0,),
    )
    compiled = fn.lower(
        _spec((P, SMALL, D), jnp.float32, one_chip),
        _spec((P,), jnp.int32, one_chip),
        _spec((P,), jnp.int32, one_chip),
        old_active=SMALL // 2,
        active=SMALL,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("mp", [False, True])
def test_flush_merge_step(one_chip, mp):
    from skyline_tpu.stream.window import _merge_step_pallas_core

    fn = jax.jit(_merge_step_pallas_core, static_argnames=("out_cap", "mp"))
    compiled = fn.lower(
        _spec((SMALL, D), jnp.float32, one_chip),
        _spec((SMALL,), jnp.bool_, one_chip),
        _spec((BLOCK, D), jnp.float32, one_chip),
        _spec((BLOCK,), jnp.bool_, one_chip),
        out_cap=SMALL + BLOCK,
        mp=mp,
    ).compile()
    _assert_kernel(compiled)


def test_tree_pair_merge(one_chip, as_tpu):
    """The global-merge tournament's pairwise node merge."""
    from skyline_tpu.stream.window import tree_pair_merge

    fn = jax.jit(tree_pair_merge.__wrapped__, static_argnames=("out_cap",))
    i32 = jnp.int32
    compiled = fn.lower(
        _spec((SMALL, D), jnp.float32, one_chip),
        _spec((SMALL,), i32, one_chip),
        _spec((), i32, one_chip),
        _spec((SMALL, D), jnp.float32, one_chip),
        _spec((SMALL,), i32, one_chip),
        _spec((), i32, one_chip),
        out_cap=2 * SMALL,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("mp", [False, True])
def test_meshed_sfs_round_four_chips(topo, mp):
    """The meshed lazy flush: shard_map of the SFS round over a 4-chip mesh,
    two partitions per chip."""
    from jax.sharding import Mesh
    import numpy as np

    from skyline_tpu.stream.window import meshed_sfs_round

    mesh = Mesh(np.asarray(topo.devices[:4]), ("part",))
    part = NamedSharding(mesh, PartitionSpec("part"))
    P = 8
    fn = meshed_sfs_round.__wrapped__(mesh, "part", True, CAP, mp)
    compiled = fn.lower(
        _spec((P, CAP, D), jnp.float32, part),
        _spec((P,), jnp.int32, part),
        _spec((P, BLOCK, D), jnp.float32, part),
        _spec((P, BLOCK), jnp.bool_, part),
    ).compile()
    _assert_kernel(compiled)
