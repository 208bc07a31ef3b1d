"""One giant Space sharded across the mesh as spatial tiles (megaspace).

The reference's scaling unit is the Space pinned to one process; population
per space is capped in user code (``SpaceService.go:14``). A megaspace
removes that ceiling: entities live in x-interval tiles (device d owns
``x in [d*tile_w, (d+1)*tile_w)``), AOI sees across tile borders via the
ring/halo ghost exchange (:mod:`goworld_tpu.parallel.halo`), and entities
crossing a border migrate automatically through the all_to_all row exchange
(:mod:`goworld_tpu.parallel.migrate`) — no EnterSpace call, no dispatcher.

Identity across the megaspace is the global id ``gid = shard * N + slot``.
Neighbor lists in state hold gids (sentinel ``n_dev * N``), so interest
deltas stay stable while ghost buffer order changes tick to tick, and
enter/leave/sync records emitted to the host reference gids directly.

BASELINE config 4 (64 spaces / 1M entities over ICI) is this module at
n_dev=64; config 2 is :mod:`goworld_tpu.core.step` at n_dev=1.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import Mesh, PartitionSpec as P

from goworld_tpu.core.state import SpaceState, WorldConfig
from goworld_tpu.core.step import TickOutputs, compute_velocity
from goworld_tpu.models.npc_policy import neighbor_mean_offset
from goworld_tpu.ops.aoi import grid_neighbors_flags
from goworld_tpu.ops.delta import interest_pairs
from goworld_tpu.ops.integrate import apply_pos_inputs, integrate
from goworld_tpu.ops.sync import collect_attr_deltas, collect_sync
from goworld_tpu.parallel import migrate as mig
from goworld_tpu.parallel.halo import (
    HALO_IMPLS,
    exchange_halo,
    exchange_halo_2d,
    meta_gid_bound,
)
from goworld_tpu.parallel.mesh import SPACE_AXIS, shard_map_norep
from goworld_tpu.parallel.step import MultiTickInputs
from goworld_tpu.scenarios.behaviors import scenario_velocity


@dataclasses.dataclass(frozen=True)
class MegaConfig:
    """Static megaspace configuration.

    ``cfg.grid`` describes the TILE-LOCAL grid in shifted coordinates:
    origin 0, ``extent_x = tile_w + 2 * radius`` (one halo margin on each
    side). 1D mode (``mesh_shape=None``): devices tile the x axis as
    strips and ``extent_z`` is the world's z extent. 2D mode
    (``mesh_shape=(tx, tz)``): devices tile the XZ plane, device ``d``
    owns tile ``(d // tz, d % tz)`` of size ``tile_w x tile_d``, and
    ``extent_z = tile_d + 2 * radius`` — the realistic layout for square
    worlds at high device counts, where 1D strips become thinner than
    the AOI radius (BASELINE config 4 at 64 devices).
    """

    cfg: WorldConfig
    n_dev: int
    tile_w: float
    halo_cap: int = 1024
    migrate_cap: int = 256
    mesh_shape: tuple[int, int] | None = None  # (tx, tz); None = (n_dev, 1)
    tile_d: float = 0.0                        # z tile depth (2D only)
    # halo shipping impl (parallel/halo.py): "ppermute" (barriered
    # collective, the default) or "async" (Pallas make_async_remote_copy
    # per edge with a dirty-only packed payload — overlap-capable;
    # interpret mode + one-time warning off-TPU, never a CPU default)
    halo_impl: str = "ppermute"

    def __post_init__(self):
        g = self.cfg.grid
        if self.cfg.scenario is not None \
                and "btree" in self.cfg.scenario.behavior_names:
            # the tile step feeds the switch from summary feature lanes
            # (mean offset / client count); the btree chase branch also
            # needs the NEAREST-CLIENT offset, which those lanes don't
            # carry — monsters would silently freeze instead of chasing.
            # Refuse at build time rather than diverge from the
            # single-chip semantics.
            raise ValueError(
                "megaspace scenarios cannot include the 'btree' mix "
                "member: the tile step's summary features carry no "
                "nearest-client offset (pick a non-btree mix, or run "
                "cfg.behavior='btree' homogeneous)"
            )
        if self.halo_impl not in HALO_IMPLS:
            raise ValueError(
                f"halo_impl {self.halo_impl!r} not in {HALO_IMPLS}"
            )
        if self.halo_impl == "async" \
                and self.n_dev * self.cfg.capacity > meta_gid_bound():
            raise ValueError(
                "halo_impl='async' packs gids into a 29-bit meta lane; "
                f"n_dev * capacity = {self.n_dev * self.cfg.capacity} "
                f"exceeds {meta_gid_bound()} — use halo_impl='ppermute'"
            )
        expected = self.tile_w + 2.0 * g.radius
        if abs(g.extent_x - expected) > 1e-6:
            raise ValueError(
                f"grid.extent_x must be tile_w + 2*radius = {expected}, "
                f"got {g.extent_x}"
            )
        if g.origin_x != 0.0 or g.origin_z != 0.0:
            raise ValueError(
                "megaspace grids use tile-shifted coordinates; "
                "grid.origin_x/origin_z must be 0"
            )
        if g.radius > self.tile_w:
            # The halo exchange is one hop each way: an AOI radius wider
            # than a tile would need neighbors-of-neighbors, which never
            # arrive — interest events silently missing.
            raise ValueError(
                f"grid.radius ({g.radius}) must be <= tile_w "
                f"({self.tile_w}) for adjacent-tile halo exchange"
            )
        if self.mesh_shape is not None:
            tx, tz = self.mesh_shape
            if tx * tz != self.n_dev:
                raise ValueError(
                    f"mesh_shape {self.mesh_shape} != n_dev {self.n_dev}"
                )
            if tz > 1:
                if self.tile_d <= 0:
                    raise ValueError("2D megaspace requires tile_d > 0")
                if g.radius > self.tile_d:
                    raise ValueError(
                        f"grid.radius ({g.radius}) must be <= tile_d "
                        f"({self.tile_d})"
                    )
                expected_z = self.tile_d + 2.0 * g.radius
                if abs(g.extent_z - expected_z) > 1e-6:
                    raise ValueError(
                        "2D megaspace: grid.extent_z must be "
                        f"tile_d + 2*radius = {expected_z}, got "
                        f"{g.extent_z}"
                    )

    @property
    def shape(self) -> tuple[int, int]:
        return self.mesh_shape or (self.n_dev, 1)

    @property
    def is_2d(self) -> bool:
        return self.shape[1] > 1

    @property
    def world_x(self) -> float:
        return self.tile_w * self.shape[0]

    @property
    def world_z(self) -> float:
        if self.is_2d:
            return self.tile_d * self.shape[1]
        return self.cfg.grid.extent_z

    @property
    def ghost_rows(self) -> int:
        return (4 if self.is_2d else 2) * self.halo_cap

    @property
    def gid_sentinel(self) -> int:
        return self.n_dev * self.cfg.capacity

    def tile_of(self, x: float, z: float) -> int:
        """Owning device of a world coordinate (host-side placement)."""
        tx, tz = self.shape
        ix = max(0, min(tx - 1, int(x // self.tile_w)))
        if not self.is_2d:
            return ix
        iz = max(0, min(tz - 1, int(z // self.tile_d)))
        return ix * tz + iz


@struct.dataclass
class MegaTickOutputs:
    base: TickOutputs          # j ids are GLOBAL gids; w are local slots
    arr_tag: jax.Array         # i32[n_dev, n_dev*mcap]: old gid of arrival
    arr_slot: jax.Array        # i32[n_dev, n_dev*mcap]: new local slot
    arr_n: jax.Array           # i32[n_dev]
    migrate_dropped: jax.Array  # i32[n_dev]
    migrate_demand: jax.Array  # i32[n_dev, n_dev] true per-dest emigrants
                               # (alarm when > migrate_cap: surplus entities
                               # linger on the wrong tile with degraded AOI)
    halo_demand: jax.Array     # i32[n_dev] boundary strip occupancy (alarm
                               # when > halo_cap)
    global_alive: jax.Array    # i32[n_dev]


def create_mega_state(mc: MegaConfig, seed: int = 0) -> SpaceState:
    """Stacked per-tile state with GLOBAL-id neighbor lists."""
    from goworld_tpu.parallel.mesh import create_multi_state

    st = create_multi_state(mc.cfg, mc.n_dev, seed)
    return st.replace(
        nbr=jnp.full_like(st.nbr, mc.gid_sentinel),
        nbr_cnt=jnp.zeros_like(st.nbr_cnt),
    )


def make_mega_tick(mc: MegaConfig, mesh: Mesh, donate: bool = False):
    """Build the jitted megaspace step. Signature matches make_multi_tick:
    ``step(states, inputs, policy) -> (states, MegaTickOutputs)`` with
    leading [n_dev] axes; ``inputs.migrate_target`` is ignored (tile
    migration is automatic from position). donate=True donates the state
    carry (arg 0): XLA aliases output shards in place and deletes the
    caller's old carry (resident-world contract, entity/manager.py)."""
    cfg = mc.cfg
    n = cfg.capacity
    n_dev = mc.n_dev
    if mesh.devices.size != n_dev:
        raise ValueError(
            f"MegaConfig.n_dev={n_dev} but mesh has {mesh.devices.size} "
            "devices; tile ownership and ring neighbors would disagree"
        )
    radius = cfg.grid.radius
    gsent = mc.gid_sentinel
    tx, tz = mc.shape
    ghost_rows = mc.ghost_rows

    def shard_fn(state, inputs: MultiTickInputs, policy):
        state = jax.tree.map(lambda x: x[0], state)
        inputs = jax.tree.map(lambda x: x[0], inputs)
        d = jax.lax.axis_index(SPACE_AXIS)
        d_ix = d // tz
        d_iz = d % tz
        tile_min = d_ix.astype(jnp.float32) * mc.tile_w
        tile_min_z = d_iz.astype(jnp.float32) * mc.tile_d

        # 1. client inputs (global coords), behaviors, integrate over the
        #    WHOLE world extent (not the tile: movers cross borders freely).
        with jax.named_scope("gw.inputs"):
            pos, yaw, touched = apply_pos_inputs(
                state.pos, state.yaw,
                inputs.base.pos_sync_idx, inputs.base.pos_sync_vals,
                inputs.base.pos_sync_n,
            )
        with jax.named_scope("gw.behave"):
            rng, k_behave = jax.random.split(state.rng)
            # state.nbr holds GLOBAL gids (not local gather indices); the MLP
            # observation instead reads state.nbr_cnt/nbr_mean_off — neighbor
            # features computed over local+ghost positions by the PREVIOUS
            # tick's AOI sweep (step 5 below)
            tele = None
            if cfg.scenario is not None:
                # heterogeneous scenario mix (goworld_tpu/scenarios): the
                # same vmapped lax.switch as tick_body, with the phase
                # schedule anchored to WORLD bounds (the tile grid's
                # extents are tile-local) and the neighbor features read
                # from the summary lanes the previous tick's sweep left
                # behind — gid neighbor lists can't feed the per-slot
                # feature gathers. This is how the multichip bench's
                # border_churn phase drives sustained tile crossings.
                vel, tele_pos, tele = scenario_velocity(
                    cfg, k_behave, pos, yaw, state, policy,
                    bounds=(0.0, 0.0, mc.world_x, mc.world_z),
                    features=(
                        state.nbr_mean_off,
                        state.nbr_client_cnt.astype(jnp.float32),
                        jnp.zeros_like(state.nbr_mean_off),
                    ),
                )
            else:
                vel = compute_velocity(
                    cfg, k_behave, pos, yaw, state, policy,
                    (mc.world_x, mc.world_z), nbr=None, nbr_cnt=None,
                )
        with jax.named_scope("gw.integrate"):
            pos, moved = integrate(
                pos, vel, state.npc_moving, cfg.dt,
                (0.0, -1e9, 0.0), (mc.world_x, 1e9, mc.world_z),
            )
            if tele is not None:
                # teleports override the integrated position BEFORE tile
                # targeting, so a cross-tile jump migrates on this tick
                pos = jnp.where(tele[:, None], tele_pos, pos)
                moved = moved | tele
            state = state.replace(pos=pos, yaw=yaw, vel=vel, rng=rng)
            pre_dirty = (moved | touched | state.dirty) & state.alive

        # 2. automatic tile migration from position (x strip in 1D;
        #    (ix, iz) tile in 2D).
        with jax.named_scope("gw.migrate"):
            tgt_ix = jnp.clip(
                jnp.floor(pos[:, 0] / mc.tile_w).astype(jnp.int32), 0, tx - 1
            )
            if mc.is_2d:
                tgt_iz = jnp.clip(
                    jnp.floor(pos[:, 2] / mc.tile_d).astype(jnp.int32),
                    0, tz - 1,
                )
                tgt = tgt_ix * tz + tgt_iz
            else:
                tgt = tgt_ix
            tgt = jnp.where(state.alive & (tgt != d), tgt, -1)
            tag = d * n + jnp.arange(n, dtype=jnp.int32)   # old gid as tag
            fbuf, ibuf, departed, mig_demand = mig.pack_emigrants(
                state, tgt, tag, n_dev, mc.migrate_cap
            )
            state = mig.despawn_departed(state, departed)
            pre_dirty &= ~departed
            fbuf = jax.lax.all_to_all(fbuf, SPACE_AXIS, 0, 0, tiled=True)
            ibuf = jax.lax.all_to_all(ibuf, SPACE_AXIS, 0, 0, tiled=True)
            state, arr_tag, arr_slot, arr_n, dropped = mig.insert_arrivals(
                state, fbuf, ibuf, nbr_sentinel=gsent, quarantine=departed
            )
            dirty = pre_dirty | state.dirty   # arrivals force-sync

        # 3. halo ghost exchange (ring ppermute). AOI-excluded entities
        #    (aoi_radius <= 0, e.g. service types) never ship as ghosts —
        #    they are invisible to every watcher, local or remote.
        visible = state.alive & (state.aoi_radius > 0.0)
        if mc.is_2d:
            gpos, gyaw, gdirty, gvalid, ggid, halo_demand = \
                exchange_halo_2d(
                    SPACE_AXIS, (tx, tz), n, state.pos, state.yaw, dirty,
                    visible, mc.tile_w, mc.tile_d, radius, mc.halo_cap,
                    impl=mc.halo_impl,
                )
        else:
            gpos, gyaw, gdirty, gvalid, ggid, halo_demand = exchange_halo(
                SPACE_AXIS, n_dev, state.pos, state.yaw, dirty, visible,
                mc.tile_w, radius, mc.halo_cap, impl=mc.halo_impl,
            )

        # 4. AOI over the extended local+ghost population, in tile-shifted
        #    coordinates so the static grid covers [0, tile_w + 2R)
        #    (x [0, tile_d + 2R) in z for 2D tiles).
        with jax.named_scope("gw.aoi"):
            pos_ext = jnp.concatenate([state.pos, gpos])
            shift = jnp.array([0.0, 0.0, 0.0], jnp.float32) \
                .at[0].set(tile_min - radius)
            if mc.is_2d:
                shift = shift.at[2].set(tile_min_z - radius)
            alive_ext = jnp.concatenate([state.alive, gvalid])
            # ghosts already passed the source-side visibility filter: give
            # them +inf so only the local per-entity radii gate here
            wr_ext = jnp.concatenate([
                state.aoi_radius,
                jnp.full((ghost_rows,), jnp.inf, jnp.float32),
            ])
            # ghosts are candidates but never watchers: query only local rows.
            # Dirty and has_client bits (local + ghost) ride the sweep so sync
            # collection needs no [N, k] dirty gather and the behavior tree
            # gets its players-in-AOI count for free. Halo records don't carry
            # has_client, so remote-tile clients read as NPCs to the
            # behavior tree (boundary approximation; transport.py-level parity
            # is unaffected — sync/interest never consult bit 1 of ghosts).
            dirty_ext = jnp.concatenate([dirty, gdirty])
            hc_ext = jnp.concatenate([
                state.has_client,
                jnp.zeros((ghost_rows,), bool),
            ])
            nbr_ext, nbr_cnt, nbr_fl, aoi_stats = grid_neighbors_flags(
                cfg.grid, pos_ext - shift, alive_ext, query_rows=n,
                watch_radius=wr_ext,
                flag_bits=dirty_ext.astype(jnp.int32)
                | (hc_ext.astype(jnp.int32) << 1),
                with_stats=True,
            )

        # 5. neighbor features for next tick's MLP observation (computed
        #    HERE because nbr_ext still indexes pos_ext; after the gid
        #    translation below the positions are no longer addressable),
        #    then translate to stable GLOBAL ids and diff.
        with jax.named_scope("gw.delta"):
            p_ext = n + ghost_rows
            wants_features = (
                cfg.behavior in ("mlp", "btree")
                if cfg.scenario is None else cfg.scenario.needs_features
            )
            if wants_features:  # static at trace time
                mean_off = neighbor_mean_offset(
                    pos_ext, state.pos, nbr_ext, nbr_cnt, p_ext
                )
            else:
                # nothing reads the features: skip the [N, k, 3] gather
                # (gathers are the scarce resource on TPU)
                mean_off = state.nbr_mean_off
            gid_ext = jnp.concatenate(
                [d * n + jnp.arange(n, dtype=jnp.int32), ggid]
            )
            nbr_gid = jnp.where(
                nbr_ext == p_ext, gsent,
                gid_ext[jnp.minimum(nbr_ext, p_ext - 1)],
            )
            nbr_gid = jnp.sort(nbr_gid, axis=1)
            (enter_w, enter_j, enter_n, leave_w, leave_j, leave_n,
             delta_rows_n) = interest_pairs(
                state.nbr, nbr_gid, gsent, cfg.enter_cap, cfg.leave_cap,
                min(cfg.delta_rows_cap_eff, n),
            )

        # 6. sync records over the extended population; subjects -> gids.
        with jax.named_scope("gw.sync"):
            yaw_ext = jnp.concatenate([state.yaw, gyaw])
            sync_w, sync_j, sync_vals, sync_n = collect_sync(
                nbr_ext, dirty_ext, state.has_client, pos_ext, yaw_ext,
                cfg.sync_cap, nbr_dirty=(nbr_fl & 1).astype(bool),
            )
            sync_j = jnp.where(
                sync_j >= 0, gid_ext[jnp.clip(sync_j, 0, p_ext - 1)], -1
            )

        # 7. attr deltas (local only; ghosts' attrs sync on their own shard).
        with jax.named_scope("gw.attrs"):
            attr_e, attr_i, attr_v, attr_n = collect_attr_deltas(
                state.hot_attrs, state.attr_dirty, cfg.attr_sync_cap
            )

        global_alive = jax.lax.psum(
            state.alive.sum().astype(jnp.int32), SPACE_AXIS
        )
        state = state.replace(
            nbr=nbr_gid,
            nbr_cnt=nbr_cnt,
            nbr_client_cnt=(
                (nbr_fl >> 1) & 1
            ).sum(axis=1).astype(jnp.int32),
            nbr_mean_off=mean_off,
            dirty=jnp.zeros_like(state.dirty),
            attr_dirty=jnp.zeros_like(state.attr_dirty),
            tick=state.tick + 1,
        )
        outputs = MegaTickOutputs(
            base=TickOutputs(
                enter_w=enter_w, enter_j=enter_j, enter_n=enter_n,
                leave_w=leave_w, leave_j=leave_j, leave_n=leave_n,
                delta_rows_n=delta_rows_n,
                sync_w=sync_w, sync_j=sync_j, sync_vals=sync_vals,
                sync_n=sync_n,
                attr_e=attr_e, attr_i=attr_i, attr_v=attr_v, attr_n=attr_n,
                alive_count=state.alive.sum().astype(jnp.int32),
                aoi_demand_max=aoi_stats[0],
                aoi_over_k_rows=aoi_stats[1],
                aoi_cell_max=aoi_stats[2],
                aoi_over_cap_cells=aoi_stats[3],
            ),
            arr_tag=arr_tag, arr_slot=arr_slot, arr_n=arr_n,
            migrate_dropped=dropped,
            migrate_demand=mig_demand,
            halo_demand=halo_demand,
            global_alive=global_alive,
        )
        state = jax.tree.map(lambda x: x[None], state)
        outputs = jax.tree.map(lambda x: x[None], outputs)
        return state, outputs

    # norep: pallas_call (the async halo) has no replication rule; the
    # static rep check adds nothing here — every output is sharded
    mapped = shard_map_norep(
        shard_fn,
        mesh=mesh,
        in_specs=(P(SPACE_AXIS), P(SPACE_AXIS), P()),
        out_specs=(P(SPACE_AXIS), P(SPACE_AXIS)),
    )
    # keep_unused: behavior-dead carry lanes must stay parameters or
    # they lose their donation source (see _make_local_tick)
    return jax.jit(mapped, donate_argnums=(0,) if donate else (),
                   keep_unused=donate)
