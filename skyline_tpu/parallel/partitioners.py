"""Vectorized spatial partitioners: MR-Dim, MR-Grid, MR-Angle.

Each maps a whole window ``(N, d) -> (N,) int32`` of partition ids in one
fused op — the reference computes the same keys tuple-at-a-time inside
Flink's ``keyBy`` (PartitioningLogic, FlinkSkyline.java:669-877). The key
formulas are preserved exactly, with one deliberate fix noted on MR-Grid.

Partition count convention follows the reference: ``numPartitions = 2 *
parallelism`` logical partitions over-partitioned onto workers for skew
tolerance (FlinkSkyline.java:74-76); here logical partitions round-robin onto
mesh devices (see ``skyline_tpu.parallel.mesh``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mr_dim(x: jax.Array, num_partitions: int, domain_max: float) -> jax.Array:
    """1-D range partitioning on dimension 0.

    Mirrors DimPartitioner.getKey (FlinkSkyline.java:686-713):
    ``p = floor(v0 / (domain_max / num_partitions))`` clamped to
    ``[0, num_partitions - 1]``.
    """
    width = domain_max / num_partitions
    p = jnp.floor(x[:, 0] / width).astype(jnp.int32)
    return jnp.clip(p, 0, num_partitions - 1)


def mr_grid(x: jax.Array, num_partitions: int, domain_max: float) -> jax.Array:
    """Hypercube-cell partitioning via the midpoint bitmask.

    Mirrors GridPartitioner.getKey (FlinkSkyline.java:746-790): bit ``i`` of
    the cell id is set iff ``values[i] >= domain_max / 2``, giving ``2^d``
    cells.

    Deliberate fix vs the reference: the reference uses the raw cell id as the
    partition key without reducing modulo ``num_partitions``
    (FlinkSkyline.java:786-788), so with ``d > log2(num_partitions)`` tuples
    land on partition ids that never receive a query trigger and are silently
    dropped from results (SURVEY.md §2.1 note on J4). Here the cell id is
    folded onto partitions with a modulo so every tuple reaches a queried
    partition. At ``d > log2(num_partitions)`` the fold makes cells ``c``
    and ``c + num_partitions`` (and so on) share a partition: the cells that
    differ only in the high dims. On 4-D anti-correlated data over 8
    partitions, partition 0 takes cells 0 and 8, about 41% of the rows and
    the whole skyline (the origin's copies), a skew the
    ``anticorr4d-grid`` benchmark cell measures.
    """
    return (mr_grid_cell(x, domain_max) % num_partitions).astype(jnp.int32)


def mr_grid_cell(x: jax.Array, domain_max: float) -> jax.Array:
    """Raw 2^d grid-cell ids (pre-modulo), exposed for parity tests vs the
    reference formula."""
    mid = domain_max / 2.0
    d = x.shape[1]
    bits = (x >= mid).astype(jnp.int32)
    weights = (1 << jnp.arange(d, dtype=jnp.int32))
    return jnp.sum(bits * weights, axis=1)


def mr_angle(x: jax.Array, num_partitions: int, domain_max: float) -> jax.Array:
    """Hyperspherical (angle-based) partitioning.

    Mirrors AnglePartitioner.getKey (FlinkSkyline.java:803-876): the d-1
    angles are ``phi_i = atan2(norm(v[i+1:]), v[i])`` (:839-851), each
    normalized by pi/2, averaged, scaled by the partition count, and clamped
    (:856-874). Angle partitioning is the documented best strategy for
    anti-correlated data — the north-star workload.

    The atan2 cascade vectorizes as a reversed cumulative sum of squares:
    ``tail_norm_i = sqrt(sum_{k>i} v_k^2)``.
    """
    d = x.shape[1]
    if d < 2:
        return jnp.zeros((x.shape[0],), dtype=jnp.int32)
    sq = x * x
    # tail_sq[:, i] = sum_{k > i} x[:, k]^2  for i in [0, d-2]
    rev_cumsum = jnp.cumsum(sq[:, ::-1], axis=1)[:, ::-1]
    tail_sq = rev_cumsum[:, 1:]  # (N, d-1)
    tail_norm = jnp.sqrt(tail_sq)
    phi = jnp.arctan2(tail_norm, x[:, : d - 1])  # (N, d-1), each in [0, pi/2]
    norm_phi = phi / (jnp.pi / 2.0)
    avg = jnp.mean(norm_phi, axis=1)
    p = jnp.floor(avg * num_partitions).astype(jnp.int32)
    return jnp.clip(p, 0, num_partitions - 1)


def partition_ids_np(
    x, algo: str, num_partitions: int, domain_max: float
):
    """Numpy twin of ``partition_ids`` for host-side stream routing (the
    engine assigns partitions while batches are still host buffers, avoiding
    a device round-trip per micro-batch). Kept formula-identical to the jnp
    versions; equivalence is asserted by tests."""
    import numpy as np

    x = np.asarray(x, dtype=np.float32)
    n, d = x.shape
    if algo == "mr-dim":
        width = domain_max / num_partitions
        p = np.floor(x[:, 0] / width).astype(np.int64)
        return np.clip(p, 0, num_partitions - 1).astype(np.int32)
    if algo == "mr-grid":
        bits = (x >= domain_max / 2.0).astype(np.int64)
        cell = bits @ (1 << np.arange(d, dtype=np.int64))
        return (cell % num_partitions).astype(np.int32)
    if algo == "mr-angle":
        if d < 2:
            return np.zeros((n,), dtype=np.int32)
        sq = (x * x).astype(np.float32)
        rev_cumsum = np.cumsum(sq[:, ::-1], axis=1)[:, ::-1]
        tail_norm = np.sqrt(rev_cumsum[:, 1:])
        phi = np.arctan2(tail_norm, x[:, : d - 1])
        avg = np.mean(phi / (np.pi / 2.0), axis=1, dtype=np.float32)
        p = np.floor(avg * np.float32(num_partitions)).astype(np.int64)
        return np.clip(p, 0, num_partitions - 1).astype(np.int32)
    raise ValueError(
        f"unknown partitioner {algo!r}; expected one of {sorted(PARTITIONERS)}"
    )


PARTITIONERS = {
    "mr-dim": mr_dim,
    "mr-grid": mr_grid,
    "mr-angle": mr_angle,
}

# Reference algo-id mapping (query_trigger.py:58-62): 1=mr-dim, 2=mr-grid, 3=mr-angle.
ALGO_IDS = {1: "mr-dim", 2: "mr-grid", 3: "mr-angle"}


def partition_ids(
    x: jax.Array, algo: str, num_partitions: int, domain_max: float
) -> jax.Array:
    """Dispatch to a partitioner by name ('mr-dim' | 'mr-grid' | 'mr-angle')."""
    try:
        fn = PARTITIONERS[algo]
    except KeyError:
        raise ValueError(
            f"unknown partitioner {algo!r}; expected one of {sorted(PARTITIONERS)}"
        ) from None
    return fn(x, num_partitions, domain_max)
