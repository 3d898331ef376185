"""Device placement for fragment shards (``repro.core.shard``).

Shards are host-emulated processes by default: each ``FragmentShard`` is an
in-process object with its own table, catalog, and maintainers.  When the
runtime exposes more than one accelerator (a ``jax`` device mesh), each
shard's columns are pinned to a device round-robin so per-shard partial
aggregation runs on the shard's own accelerator — the same fragment-routing
logic, different executor placement.  On a single-device host everything
lands on the default device and placement is a no-op.
"""
from __future__ import annotations

from typing import List, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.table import ColumnTable


def serving_mesh(use_devices: bool = True):
    """The 1-D SPMD serving mesh, or ``None`` (single device / disabled).

    Thin indirection over ``repro.launch.mesh.make_serving_mesh`` so the
    sharded engine's placement decisions all route through this module.
    """
    if not use_devices:
        return None
    from repro.launch.mesh import make_serving_mesh

    return make_serving_mesh()


def place_stacked(arr: jax.Array, mesh, shard_axis: int = 1) -> jax.Array:
    """Pin a stacked shard-major array's shard axis across the mesh.

    ``arr``'s ``shard_axis`` is laid out over the mesh's ``"shards"`` axis
    (every device owns a contiguous run of shard slices) so the fused
    shard_map launch reads its shard's rows locally.  Identity when there is
    no mesh.  The caller pads the axis to a multiple of the mesh's device
    count; an axis that does not divide is an error, not a quiet fallback
    to one device.
    """
    if mesh is None:
        return arr
    spec = [None] * arr.ndim
    spec[shard_axis] = "shards"
    return jax.device_put(arr, NamedSharding(mesh, PartitionSpec(*spec)))


def shard_devices(n_shards: int, use_devices: bool = True) -> List[Optional[jax.Device]]:
    """One device per shard, round-robin over the local devices.

    Returns ``None`` entries (no pinning) when placement is disabled or only
    one device exists — ``jax.device_put`` to the sole default device would
    just add transfer bookkeeping for nothing.
    """
    devices = jax.local_devices()
    if not use_devices or len(devices) <= 1:
        return [None] * n_shards
    return [devices[i % len(devices)] for i in range(n_shards)]


def failover_device(
    devices: List[Optional[jax.Device]],
    sid: int,
    dead: List[int],
) -> Optional[jax.Device]:
    """Placement for shard ``sid``'s post-failover rebuild.

    Keeps the shard's own pin in the common case.  When the same physical
    device also backs *another* dead shard, the fault likely sits with the
    device rather than the shard process, so the rebuild lands on the
    least-loaded device backing no dead shard (falling back to its own pin
    when every device is implicated).  ``None`` pins (single-device hosts)
    stay ``None`` — placement is a no-op there.
    """
    own = devices[sid]
    if own is None:
        return None
    dead_devs = {str(devices[d]) for d in dead
                 if d != sid and devices[d] is not None}
    if str(own) not in dead_devs:
        return own
    alive = [d for d in devices if d is not None and str(d) not in dead_devs]
    if not alive:
        return own
    load: dict = {}
    for d in alive:
        load[str(d)] = load.get(str(d), 0) + 1
    return min(alive, key=lambda d: (load[str(d)], str(d)))


def place_table(table: ColumnTable, device: Optional[jax.Device]) -> ColumnTable:
    """Pin every column of ``table`` to ``device`` (identity when None)."""
    if device is None:
        return table
    cols = {k: jax.device_put(v, device) for k, v in table.columns.items()}
    return ColumnTable(table.name, cols, table.primary_key, table.layout,
                       version=table.version, uid=table.uid)
