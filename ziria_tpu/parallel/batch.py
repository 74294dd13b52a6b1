"""Frame batching across chips — the framework's data-parallel axis.

The reference has no batch axis at all (streams are sequential,
SURVEY.md §2.4); independent frames across a TPU mesh is the new
capability that buys the headline throughput: `pjit` shards the frame
axis over 'dp', every chip decodes its shard, no collectives needed in
steady state (only at host gather). `phy/link.sweep_ber_sharded`
rides exactly this pattern for the serving workload: the BER sweep's
frame-lane axis placed with :func:`shard_batch`, every chip sweeping
its shard of lanes, ONE integer all-reduce per sweep for the counts.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def frame_mesh(n_devices: Optional[int] = None, axis: str = "dp") -> Mesh:
    """A 1-D device mesh over the first `n_devices` devices."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, only {len(devs)} visible")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def largest_divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is <= ``cap`` — the mesh
    width an S-lane fleet can actually use (the stream axis must
    shard EVENLY, `shard_batch`'s rule). >= 1 always (every fleet
    runs on one device)."""
    if n < 1 or cap < 1:
        raise ValueError(f"need n >= 1 and cap >= 1, got ({n}, {cap})")
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


def elastic_mesh(n_streams: int, n_devices: Optional[int] = None,
                 axis: str = "dp", min_lanes: int = 1) -> Optional[Mesh]:
    """The ELASTIC placement rule (ISSUE 14 failover): build the
    widest dp mesh the surviving device fleet supports for an
    ``n_streams``-lane receiver — the largest divisor of S that fits
    the visible (or capped) device count and leaves every device at
    least ``min_lanes`` lanes (the floor the served runtime places
    by: under the tuned fleet width a chip's decode tile is mostly
    padding, and the chip is wasted). Returns None when that is
    one device (an unsharded receiver is the correct degenerate
    mesh), so recovery onto a shrunken ``--devices`` — or a machine
    that lost a chip — rebuilds the fleet on whatever is left instead
    of refusing to start."""
    if min_lanes < 1:
        raise ValueError(f"need min_lanes >= 1, got {min_lanes}")
    cap = n_streams // min_lanes
    if cap <= 1:                # one device, and no backend is asked
        return None
    avail = len(jax.devices()) if n_devices is None \
        else min(n_devices, len(jax.devices()))
    m = largest_divisor(n_streams, max(1, min(avail, cap)))
    return None if m <= 1 else frame_mesh(m, axis)


def lane_sharding(mesh: Mesh, ndim: int, axis: str = "dp") -> NamedSharding:
    """The ONE placement rule of every dp surface: leading (frame/lane)
    axis sharded over `axis`, everything else replicated."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def shard_batch(mesh: Mesh, x, axis: str = "dp"):
    """Place `x` with its leading (frame) axis sharded over `axis`."""
    return jax.device_put(x, lane_sharding(mesh, np.ndim(x), axis))


def stream_specs(ndims, axis: str = "dp"):
    """`shard_map` PartitionSpecs for leading-axis sharding: one spec
    per rank in `ndims`, each sharding axis 0 over `axis` and
    replicating the rest — the shard_map twin of :func:`lane_sharding`
    (the multi-stream receiver's chunk and decode programs pass their
    argument/result ranks through this so the stream axis always
    lands on dp, never hand-written per program)."""
    from jax.sharding import PartitionSpec as P
    return tuple(P(axis, *([None] * (int(n) - 1))) for n in ndims)


def data_parallel(fn: Callable, mesh: Mesh, axis: str = "dp") -> Callable:
    """jit `fn` (batched: leading axis = frames) with the frame axis
    sharded over `axis` on `mesh` for both inputs and outputs.

    `fn` must be shardable along its leading axis (vmap-style); XLA then
    runs each chip's shard independently — the |>>>|-free scale-out path.
    """

    def run(*args):
        shardings = jax.tree.map(
            lambda a: lane_sharding(mesh, np.ndim(a), axis), args)
        return jax.jit(fn, in_shardings=shardings)(*args)

    return run
