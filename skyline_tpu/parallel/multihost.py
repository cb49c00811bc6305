"""Multi-host (DCN) scale-out: hierarchical two-phase skyline over a 2-D mesh.

The reference scales out by adding Flink TaskManagers connected over the LAN
(docker-setup/docker-compose.yml:34-44; its shuffle and single-reducer merge
then cross machines, SURVEY.md §2.6). The TPU-native equivalent is a 2-D
``(host, chip)`` mesh: chips within a host merge over ICI (fast), hosts merge
over DCN (slow) — and the DCN stage moves only *compacted per-host survivor
buffers*, not raw windows, because on most distributions local+host pruning
removes the vast majority of points before they would cross the slow link.

Exactness: pruning against a host's *survivors* is exact by dominance
transitivity (a pruned point's dominator is itself in the survivor set). The
one approximation knob is ``host_cap`` — the static size of the per-host
survivor buffer shipped over DCN. Overflow drops *dominators*, which can only
make the result a SUPERSET of the true skyline (no true skyline point is ever
lost); the step reports an overflow flag so callers can detect and re-run
with a larger cap (or ``host_cap=rows_per_host``, which is always exact).

Single-process testing: with ``--xla_force_host_platform_device_count=8`` the
same code runs on a virtual 2x4 or 4x2 CPU mesh (SURVEY.md §4 item 5's
mini-cluster analogue); on a real pod slice, ``init_multihost`` wires
``jax.distributed`` and the host axis maps onto process boundaries so the
stage-2 all_gather rides DCN.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skyline_tpu.ops.block_skyline import dominated_by_blocked, skyline_mask_blocked
from skyline_tpu.ops.dominance import compact

HOST_AXIS = "host"
CHIP_AXIS = "chip"


def init_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Initialize ``jax.distributed`` for a multi-host run (no-op when
    single-process). Arguments default to the ``SKYLINE_COORDINATOR``,
    ``SKYLINE_NUM_PROCESSES``, ``SKYLINE_PROCESS_ID`` env vars; on cloud TPU
    pods all three may be None (auto-detected by JAX)."""
    from skyline_tpu.analysis.registry import env_int, env_str

    coordinator_address = coordinator_address or env_str("SKYLINE_COORDINATOR")
    if num_processes is None:
        num_processes = env_int("SKYLINE_NUM_PROCESSES", None)
    if process_id is None:
        process_id = env_int("SKYLINE_PROCESS_ID", None)
    if num_processes is not None and num_processes <= 1:
        return
    if coordinator_address is None and num_processes is None and process_id is None:
        # nothing configured: single-process run (jax.distributed.initialize
        # with all-None args only works under managed cloud autodetection;
        # on a dev box it raises instead of no-opping)
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_host_chip_mesh(
    n_hosts: int | None = None, chips_per_host: int | None = None
) -> Mesh:
    """2-D ``(host, chip)`` mesh over all devices.

    On a real multi-process run the host axis follows ``process_index`` (so
    the chip-axis collectives stay intra-host on ICI and only the host axis
    crosses DCN). Single-process (virtual CPU devices, or one host's chips)
    falls back to an even reshape into the requested shape.
    """
    devices = jax.devices()
    n_proc = max(d.process_index for d in devices) + 1
    if n_proc > 1:
        by_proc: dict[int, list] = {}
        for d in devices:
            by_proc.setdefault(d.process_index, []).append(d)
        per = {p: sorted(ds, key=lambda d: d.id) for p, ds in by_proc.items()}
        counts = {len(ds) for ds in per.values()}
        if len(counts) != 1:
            raise ValueError(f"uneven devices per process: {per}")
        grid = np.array(
            [per[p] for p in sorted(per)], dtype=object
        )  # (n_hosts, chips_per_host)
    else:
        if n_hosts is None:
            n_hosts = 1
        if chips_per_host is None:
            if len(devices) % n_hosts:
                raise ValueError(
                    f"{len(devices)} devices not divisible into {n_hosts} hosts"
                )
            chips_per_host = len(devices) // n_hosts
        if n_hosts * chips_per_host > len(devices):
            raise ValueError(
                f"need {n_hosts}x{chips_per_host} devices, have {len(devices)}"
            )
        grid = np.asarray(devices[: n_hosts * chips_per_host]).reshape(
            n_hosts, chips_per_host
        )
    return Mesh(grid, (HOST_AXIS, CHIP_AXIS))


def build_hierarchical_two_phase(
    mesh: Mesh,
    *,
    rows_per_shard: int,
    host_cap: int | None = None,
    local_block: int = 2048,
    cross_block: int = 8192,
):
    """Jitted hierarchical two-phase skyline step for a ``(host, chip)`` mesh.

    Returns ``step(x, valid) -> (host_keep, global_keep, overflowed)`` for
    ``x: (N, d)`` row-sharded over both mesh axes (N = shards * rows_per_shard).

    - ``host_keep[j]``: row j survives its host's ICI-merged skyline.
    - ``global_keep[j]``: row j is in the global skyline (exact iff
      ``overflowed == 0``; otherwise a superset — see module docstring).
    - ``overflowed``: number of mesh participants whose host survivor count
      exceeded ``host_cap`` (0 on exact results).

    ``host_cap`` bounds the per-host survivor buffer all_gathered across the
    DCN host axis; default ``rows_per_host`` (always exact, full-size
    exchange). Set lower (e.g. ``rows_per_host // 8``) when local pruning is
    expected to be strong — the overflow flag guards correctness.
    """
    n_hosts, chips = (int(s) for s in mesh.devices.shape)
    rows_per_host = rows_per_shard * chips
    if host_cap is None:
        host_cap = rows_per_host
    if host_cap % 1024 and host_cap != rows_per_host:
        raise ValueError(f"host_cap {host_cap} must be a multiple of 1024")

    def per_device(x_shard, valid_shard):
        # Stage 0: per-chip local skyline.
        local_keep = skyline_mask_blocked(x_shard, valid_shard, block=local_block)
        # Stage 1 (ICI): host-level merge. Gather every chip-in-host's rows
        # and local survivor masks; prune own rows against them. Local
        # non-survivors are transitively covered as dominators.
        hx = lax.all_gather(x_shard, CHIP_AXIS, tiled=True)
        hlk = lax.all_gather(local_keep, CHIP_AXIS, tiled=True)
        dom_host = dominated_by_blocked(x_shard, hx, x_valid=hlk, block=cross_block)
        host_keep = local_keep & ~dom_host
        # Stage 2 (DCN): every chip of a host deterministically compacts the
        # SAME host-survivor set (hx is host-replicated after the gather; the
        # host_keep gather below makes the mask host-replicated too), so the
        # host buffer is identical host-wide and one all_gather over the host
        # axis exchanges exactly (n_hosts * host_cap) rows over DCN.
        hhk = lax.all_gather(host_keep, CHIP_AXIS, tiled=True)
        host_count = jnp.sum(hhk)
        buf, buf_valid, _ = compact(hx, hhk, host_cap)
        all_buf = lax.all_gather(buf, HOST_AXIS, tiled=True)
        all_valid = lax.all_gather(buf_valid, HOST_AXIS, tiled=True)
        dom_global = dominated_by_blocked(
            x_shard, all_buf, x_valid=all_valid, block=cross_block
        )
        global_keep = host_keep & ~dom_global
        overflow = (host_count > host_cap).astype(jnp.int32)
        overflowed = lax.psum(lax.psum(overflow, CHIP_AXIS), HOST_AXIS)
        return host_keep, global_keep, overflowed

    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P((HOST_AXIS, CHIP_AXIS)), P((HOST_AXIS, CHIP_AXIS))),
        out_specs=(
            P((HOST_AXIS, CHIP_AXIS)),
            P((HOST_AXIS, CHIP_AXIS)),
            P(),
        ),
        check_vma=False,
    )
    return jax.jit(sharded)


def shard_rows_2d(mesh: Mesh, x: np.ndarray, valid: np.ndarray):
    """Place (N, d) rows sharded over both mesh axes (N % mesh size == 0)."""
    sh = NamedSharding(mesh, P((HOST_AXIS, CHIP_AXIS)))
    return jax.device_put(x, sh), jax.device_put(valid, sh)
