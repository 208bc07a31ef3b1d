"""The multi-space tick: shard_map over the "space" mesh axis.

Each device runs the single-space :func:`goworld_tpu.core.step.tick_body` on
its own shard, then all shards exchange migrating entities with one
``all_to_all`` and reduce global stats with ``psum`` — the compiled
equivalent of the reference's game-process loops plus the dispatcher hop
between them (``SURVEY.md#2.3``: "dispatcher/star-TCP is replaced within a
mesh by compiled collectives").

Host contract per tick:
  inputs: per-shard TickInputs (client pos syncs routed by the host to the
  owning shard) + per-slot migration requests (target shard, host tag) —
  the staged form of ``EnterSpace`` (``Entity.go:956-973``).
  outputs: per-shard TickOutputs + arrival records (tag -> new slot) the
  host uses to re-point EntityID -> (space, slot), exactly where the
  reference's dispatcher rewrites its entityDispatchInfos table
  (``DispatcherService.go:877-891``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import Mesh, PartitionSpec as P

from goworld_tpu.core.state import SpaceState, WorldConfig
from goworld_tpu.core.step import TickInputs, TickOutputs, tick_body
from goworld_tpu.parallel import migrate as mig
from goworld_tpu.parallel.mesh import SPACE_AXIS, shard_map


@struct.dataclass
class MultiTickInputs:
    base: TickInputs          # leaves [n_dev, ...]
    migrate_target: jax.Array  # i32[n_dev, N]: dest shard or -1
    migrate_tag: jax.Array     # i32[n_dev, N]: host tag for remapping

    @staticmethod
    def empty(cfg: WorldConfig, n_dev: int) -> "MultiTickInputs":
        base = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_dev,) + x.shape),
            TickInputs.empty(cfg),
        )
        return MultiTickInputs(
            base=base,
            migrate_target=jnp.full((n_dev, cfg.capacity), -1, jnp.int32),
            migrate_tag=jnp.full((n_dev, cfg.capacity), -1, jnp.int32),
        )


@struct.dataclass
class MultiTickOutputs:
    base: TickOutputs          # leaves [n_dev, ...]
    arr_tag: jax.Array         # i32[n_dev, n_dev*cap]
    arr_slot: jax.Array        # i32[n_dev, n_dev*cap]
    arr_n: jax.Array           # i32[n_dev]
    migrate_dropped: jax.Array  # i32[n_dev] arrivals lost to full shards
    migrate_demand: jax.Array  # i32[n_dev, n_dev] true per-dest emigrants
    global_alive: jax.Array    # i32[n_dev] (identical on every shard; psum)


def make_multi_tick(cfg: WorldConfig, mesh: Mesh, migrate_cap: int = 256,
                    donate: bool = False):
    """Build the jitted multi-space step over ``mesh``.

    Returns ``step(states, inputs, policy) -> (states, outputs)`` where
    every array carries a leading [n_dev] axis sharded over "space".
    donate=True donates the state carry (arg 0) so XLA aliases the
    output shards in place — the caller's old carry is deleted after
    dispatch (resident-world contract, see entity/manager.py).
    """
    n_dev = mesh.devices.size

    def shard_fn(
        state: SpaceState, inputs: MultiTickInputs, policy
    ) -> tuple[SpaceState, MultiTickOutputs]:
        state = jax.tree.map(lambda x: x[0], state)
        inputs = jax.tree.map(lambda x: x[0], inputs)

        state, outs = tick_body(cfg, state, inputs.base, policy)

        # --- migration: pack -> all_to_all over ICI -> insert ------------
        with jax.named_scope("gw.migrate"):
            fbuf, ibuf, departed, demand = mig.pack_emigrants(
                state, inputs.migrate_target, inputs.migrate_tag,
                n_dev, migrate_cap,
            )
            state = mig.despawn_departed(state, departed)
            fbuf = jax.lax.all_to_all(
                fbuf, SPACE_AXIS, split_axis=0, concat_axis=0,
                tiled=True,
            )
            ibuf = jax.lax.all_to_all(
                ibuf, SPACE_AXIS, split_axis=0, concat_axis=0,
                tiled=True,
            )
            state, arr_tag, arr_slot, arr_n, dropped = \
                mig.insert_arrivals(
                    state, fbuf, ibuf, nbr_sentinel=cfg.capacity,
                    quarantine=departed,
                )

        # --- global stats over the mesh (one psum) -----------------------
        global_alive = jax.lax.psum(
            state.alive.sum().astype(jnp.int32), SPACE_AXIS
        )

        outputs = MultiTickOutputs(
            base=outs,
            arr_tag=arr_tag,
            arr_slot=arr_slot,
            arr_n=arr_n,
            migrate_dropped=dropped,
            migrate_demand=demand,
            global_alive=global_alive,
        )
        state = jax.tree.map(lambda x: x[None], state)
        outputs = jax.tree.map(lambda x: x[None], outputs)
        return state, outputs

    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(SPACE_AXIS), P(SPACE_AXIS), P()),
        out_specs=(P(SPACE_AXIS), P(SPACE_AXIS)),
    )
    # keep_unused: behavior-dead carry lanes must stay parameters or
    # they lose their donation source (see _make_local_tick)
    return jax.jit(mapped, donate_argnums=(0,) if donate else (),
                   keep_unused=donate)
