"""Sharded two-phase skyline over a ``jax.sharding.Mesh``.

This is the TPU-native replacement for the reference's distributed topology
(SURVEY.md §2.5-2.6): Flink's ``keyBy`` hash shuffle becomes host-side
partition-id computation + a sharded ``device_put`` onto the mesh; the
per-subtask ``SkylineLocalProcessor`` becomes a per-device blocked skyline
kernel; and the single-reducer ``GlobalSkylineAggregator`` bottleneck
(FlinkSkyline.java:460-660, pdf §5.5 "global merge time >> local CPU time")
becomes an ``all_gather`` of per-device local skylines over ICI followed by a
distributed masked cross-prune — every device finalizes its own rows, so the
merge itself is parallel instead of funneling into one JVM subtask.

All shapes are static: the window arrives padded to ``P * rows_per_shard`` and
results are (local_keep, global_keep) boolean masks from which the engine
derives skyline sizes and per-partition optimality (survivors_i / local_i,
FlinkSkyline.java:592-608).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skyline_tpu.ops.block_skyline import (
    dominated_by_blocked,
    skyline_mask_blocked,
)

AXIS = "p"


def make_mesh(n_devices: int | None = None, axis: str = AXIS) -> Mesh:
    """1-D device mesh over the first ``n_devices`` local devices (on an
    accelerator, fewer than ``n_devices`` raises; the CPU backend takes what
    it has).

    The reference's analogue is Flink ``env.setParallelism(p)``
    (FlinkSkyline.java:80); here parallel workers are mesh devices and the
    ``2 x parallelism`` logical partitions round-robin onto them (see
    ``skyline_tpu.stream.engine``).
    """
    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices and devices[0].platform != "cpu":
            raise ValueError(
                f"a {n_devices}-device mesh needs {n_devices} devices, but "
                f"only {len(devices)} {devices[0].platform} devices exist"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def build_two_phase(
    mesh: Mesh,
    *,
    axis: str = AXIS,
    local_block: int = 2048,
    cross_block: int = 8192,
):
    """Build a jitted sharded two-phase skyline step for ``mesh``.

    Returns ``step(x, valid) -> (local_keep, global_keep)`` where
    ``x: (N, d)`` and ``valid: (N,)`` are sharded along rows across the mesh
    (N divisible by mesh size). ``local_keep[j]`` marks survivors of the
    per-device local phase; ``global_keep[j]`` marks rows in the global
    skyline. ``global_keep`` is exact and identical to an unsharded
    ``skyline_mask`` (partitioner- and device-count-invariant — the invariant
    the reference only checks by eyeballing CSVs, SURVEY.md §4).
    """
    n_dev = mesh.devices.size

    def per_device(x_shard, valid_shard):
        # Phase 1: local skyline on this device's rows.
        local_keep = skyline_mask_blocked(x_shard, valid_shard, block=local_block)
        # Phase 2: gather every device's local survivors over ICI and prune
        # this device's survivors against them. Local non-survivors need no
        # check (dominance is transitive), and gathered non-survivors are
        # masked out as dominators.
        all_x = lax.all_gather(x_shard, axis, tiled=True)
        all_keep = lax.all_gather(local_keep, axis, tiled=True)
        dominated = dominated_by_blocked(
            x_shard, all_x, x_valid=all_keep, block=cross_block
        )
        global_keep = local_keep & ~dominated
        return local_keep, global_keep

    if n_dev == 1:
        # Degenerate mesh: skip shard_map so single-chip benches avoid any
        # collective overhead.
        @jax.jit
        def step(x, valid):
            local_keep = skyline_mask_blocked(x, valid, block=local_block)
            return local_keep, local_keep

        return step

    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        # scan carries inside the blocked kernels start from replicated
        # constants; skip the varying-manual-axes type check rather than
        # pvary-ing every carry init.
        check_vma=False,
    )
    return jax.jit(sharded)


def shard_rows(mesh: Mesh, x: np.ndarray, valid: np.ndarray, axis: str = AXIS):
    """Place (N, d) rows row-sharded across the mesh (N % mesh size == 0)."""
    sh = NamedSharding(mesh, P(axis))
    return jax.device_put(x, sh), jax.device_put(valid, sh)


# Mesh is hashable by devices + axis names, so equal-but-distinct meshes
# share one compiled step.
_cached_two_phase = functools.lru_cache(maxsize=32)(
    lambda mesh, axis, local_block, cross_block: build_two_phase(
        mesh, axis=axis, local_block=local_block, cross_block=cross_block
    )
)


def sharded_two_phase_skyline(
    mesh: Mesh,
    x,
    valid,
    *,
    axis: str = AXIS,
    local_block: int = 2048,
    cross_block: int = 8192,
):
    """Convenience wrapper: build (cached) + run the two-phase step."""
    step = _cached_two_phase(mesh, axis, local_block, cross_block)
    return step(x, valid)


def skyline_keep_np_sharded(
    mesh: Mesh,
    x: np.ndarray,
    *,
    axis: str | None = None,
    local_block: int = 2048,
    cross_block: int = 8192,
) -> np.ndarray:
    """Survivor mask of a host (n, d) array via the sharded two-phase step —
    the mesh counterpart of ``ops.dispatch.skyline_keep_np``. Pads rows to a
    power-of-two capacity (rounded to a mesh-size multiple), shards them
    across the mesh, and slices the exact mask back. This is the engine's
    global merge when it owns a mesh: the reference's single-reducer
    bottleneck (pdf §5.5) as a parallel collective.

    ``axis`` defaults to the mesh's first axis name, matching how
    ``stream.batched.PartitionSet`` shards partition state."""
    from skyline_tpu.utils.buckets import next_pow2

    n, d = x.shape
    if n == 0:
        return np.zeros((0,), dtype=bool)
    if axis is None:
        axis = mesh.axis_names[0]
    n_dev = int(mesh.shape[axis])
    cap = next_pow2(n, min_cap=1024)
    cap = -(-cap // n_dev) * n_dev  # no-op for power-of-two mesh sizes
    pad = np.full((cap, d), np.inf, dtype=np.float32)
    pad[:n] = x
    valid = np.arange(cap) < n
    xs, vs = shard_rows(mesh, pad, valid, axis=axis)
    _, global_keep = sharded_two_phase_skyline(
        mesh, xs, vs, axis=axis, local_block=local_block,
        cross_block=cross_block,
    )
    return np.asarray(global_keep)[:n]
