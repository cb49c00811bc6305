"""Chip-group placement for the sharded streaming engine.

The ``distributed.ShardedEngine`` splits the partition set into
``chips`` contiguous groups and pins each group's device state to one
chip. This module owns the placement decision — which physical device
backs which chip index — and the one cross-chip "collective" the
two-level tournament needs: gathering the surviving chip-local skyline
buffers onto a single root device for the pairwise merge.

Everything here works identically on a CPU host forced to expose N
virtual devices (``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
— that is how tier-1 exercises the real merge topology without a TPU.
"""

from __future__ import annotations

import jax


def chip_devices(chips: int) -> list:
    """The device backing each chip index.

    On an accelerator each group needs its own chip: asking for more chips
    than the host has raises. On the CPU backend (tier-1's virtual devices,
    or a plain 1-CPU run) the groups round-robin over the local devices —
    correctness never depends on the placement, only locality does.
    """
    if chips < 1:
        raise ValueError(f"chips must be >= 1, got {chips}")
    devs = jax.devices()
    if len(devs) < chips and devs[0].platform != "cpu":
        raise ValueError(
            f"{chips} chips asked for, but only {len(devs)} "
            f"{devs[0].platform} devices exist"
        )
    return [devs[c % len(devs)] for c in range(chips)]


def chip_of(pid: int, group_size: int) -> int:
    """The chip owning global partition ``pid`` (contiguous blocks of
    ``group_size`` partitions per chip)."""
    return pid // group_size


def gather_to(device, arrays):
    """Move every array in ``arrays`` onto ``device`` — the cross-chip
    collective feeding the tournament root. On a forced-host-platform CPU
    mesh this is a (virtual) cross-device copy; on a real mesh it is the
    ICI transfer the chip-level witness prune exists to skip."""
    return [jax.device_put(a, device) for a in arrays]
