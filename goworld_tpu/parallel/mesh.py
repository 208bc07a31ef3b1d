"""Mesh construction and sharded state layout.

One mesh axis, ``"space"``: device d hosts space shard d. Entity placement
across shards is the host's job (the reference's dispatcher ``chooseGame``
min-CPU heap, ``DispatcherService.go:523-536``, becomes the host scheduler in
:mod:`goworld_tpu.entity`); the device layer only requires that every leaf of
the stacked state carries a leading ``[n_dev, ...]`` axis sharded over
``"space"``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from goworld_tpu.core.state import SpaceState, WorldConfig, create_state

SPACE_AXIS = "space"

shard_map = jax.shard_map


def shard_map_norep(fn, **kw):
    """shard_map with the varying-manual-axes check OFF — required
    wherever the shard body contains a ``pallas_call`` (no replication
    rule, e.g. the async halo)."""
    return jax.shard_map(fn, check_vma=False, **kw)


def make_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            # A short mesh would make shard_map hand each device a
            # [k>1, ...] block whose shard_fn only ticks row 0 — spaces
            # silently dropped. Fail loudly instead.
            raise ValueError(
                f"make_mesh({n_devices}) but only {len(devs)} device(s) "
                "available; set XLA_FLAGS=--xla_force_host_platform_device_"
                "count=N JAX_PLATFORMS=cpu for simulated meshes"
            )
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (SPACE_AXIS,))


def create_multi_state(cfg: WorldConfig, n_dev: int, seed: int = 0) -> SpaceState:
    """Stacked state: every leaf gains a leading [n_dev] axis."""
    shards = [create_state(cfg, seed=seed * n_dev + d) for d in range(n_dev)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *shards)


def shard_state(state: SpaceState, mesh: Mesh) -> SpaceState:
    """Place a stacked state on the mesh (leading axis over "space")."""
    sharding = NamedSharding(mesh, P(SPACE_AXIS))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), state)
