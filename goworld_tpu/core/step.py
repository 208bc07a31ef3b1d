"""The per-Space tick step: one jitted function per tick per Space shard.

This composes the kernels in :mod:`goworld_tpu.ops` into the TPU analog of
the reference game process's serve loop (``components/game/GameService.go:
77-190``): apply client inputs -> run behaviors -> integrate movement ->
AOI sweep -> interest deltas -> sync/attr record collection. All inputs and
outputs are fixed-capacity arrays so the function compiles exactly once per
(WorldConfig) and the host drives it at tick rate.

The reference processes each of these as separate per-entity events spread
over 5 ms timer ticks; here one compiled program advances the entire Space,
and "events" (AOI enter/leave, sync records, attr deltas) come back as
bounded arrays the host/gateway fans out to clients
(:mod:`goworld_tpu.net.gate`).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from flax import struct

from goworld_tpu.core.state import SpaceState, WorldConfig
from goworld_tpu.models.behavior_tree import (
    btree_velocity,
    features_from_neighbors,
    features_from_summary,
)
from goworld_tpu.models.npc_policy import (
    MLPPolicy,
    build_obs,
    build_obs_from_features,
    policy_accel,
)
from goworld_tpu.models.random_walk import random_walk_step
from goworld_tpu.ops.aoi import (
    _ID_BITS,
    grid_neighbors_flags,
    grid_neighbors_verlet,
    quantize_positions,
)
from goworld_tpu.ops.delta import interest_pairs
from goworld_tpu.ops.integrate import apply_pos_inputs, integrate
from goworld_tpu.ops.sync import collect_attr_deltas, collect_sync
from goworld_tpu.scenarios.behaviors import scenario_velocity


@struct.dataclass
class TickInputs:
    """Per-tick host->device batch (client position syncs; fixed capacity).

    The reference batches the same 16-byte records gate->dispatcher->game
    (``GateService.go:402-429``, ``DispatcherService.go:770-808``).
    """

    pos_sync_idx: jax.Array   # i32[IC] target slots
    pos_sync_vals: jax.Array  # f32[IC, 4] x,y,z,yaw
    pos_sync_n: jax.Array     # i32 scalar

    @staticmethod
    def empty(cfg: WorldConfig) -> "TickInputs":
        ic = cfg.input_cap
        return TickInputs(
            pos_sync_idx=jnp.zeros((ic,), jnp.int32),
            pos_sync_vals=jnp.zeros((ic, 4), jnp.float32),
            pos_sync_n=jnp.zeros((), jnp.int32),
        )


@struct.dataclass
class TickOutputs:
    """Per-tick device->host batch (all fixed capacity; counts are true
    demand and may exceed capacity — the host watches for overflow)."""

    enter_w: jax.Array   # i32[EC] watcher slots
    enter_j: jax.Array   # i32[EC] entered-neighbor slots
    enter_n: jax.Array   # i32
    leave_w: jax.Array
    leave_j: jax.Array
    leave_n: jax.Array
    delta_rows_n: jax.Array  # i32 TRUE count of rows whose AOI list
    # changed; > cfg.delta_rows_cap means surplus rows' enter/leave
    # events were dropped (widen delta_rows_cap, not enter/leave caps)
    sync_w: jax.Array    # i32[SC] watcher slots (has_client only)
    sync_j: jax.Array    # i32[SC] subject slots
    sync_vals: jax.Array  # f32[SC, 4]
    sync_n: jax.Array
    attr_e: jax.Array    # i32[AC] entity slots
    attr_i: jax.Array    # i32[AC] attr column
    attr_v: jax.Array    # f32[AC]
    attr_n: jax.Array
    alive_count: jax.Array  # i32
    # AOI-cap overflow gauges (ops.aoi with_stats; all i32 scalars).
    # Both zero <=> this tick's sweep was exact — the go-aoi sweep is
    # exact at any density (Space.go:244-252); capping is the TPU
    # tradeoff and the host alarms when either gauge fires
    # (manager._process_outputs).
    aoi_demand_max: jax.Array     # max true neighbor demand seen
    aoi_over_k_rows: jax.Array    # rows truncated to nearest-k
    aoi_cell_max: jax.Array       # max grid-cell occupancy
    aoi_over_cap_cells: jax.Array  # cells past cell_cap (drop risk)
    # Verlet skin-reuse telemetry (ops.aoi.grid_neighbors_verlet; None
    # from producers predating the skin — manager guards). aoi_rebuilt
    # is i32 0/1 (1 every tick when skin is off: the front half ran);
    # aoi_skin_slack is f32 skin/2 minus the max displacement since the
    # last rebuild (headroom left; meaningless 0.0 when skin is off).
    aoi_rebuilt: jax.Array | None = None
    aoi_skin_slack: jax.Array | None = None


def compute_velocity(
    cfg: WorldConfig,
    key: jax.Array,
    pos: jax.Array,
    yaw: jax.Array,
    state: SpaceState,
    policy: MLPPolicy | None,
    world_extent: tuple[float, float],
    nbr: jax.Array | None = None,
    nbr_cnt: jax.Array | None = None,
) -> jax.Array:
    """Per-entity velocity update for cfg.behavior (shared by the single-
    space tick and the megaspace shard step). ``nbr``/``nbr_cnt`` are the
    LOCAL-slot neighbor lists for the MLP/behavior-tree observation; pass
    None when they are unavailable (megaspace state holds global ids — its
    observation then comes from the precomputed ``state.nbr_mean_off`` /
    ``state.nbr_client_cnt`` features the previous tick's AOI sweep left
    behind)."""
    if cfg.behavior == "btree":
        # fused Monster-AI behavior tree (BASELINE config 5;
        # models.behavior_tree cites Monster.go:32-100)
        if nbr is None:
            feats = features_from_summary(
                state.nbr_cnt, state.nbr_client_cnt, state.nbr_mean_off
            )
        else:
            feats = features_from_neighbors(
                pos, state.has_client, nbr, nbr_cnt
            )
        return btree_velocity(
            key, feats, state.vel, state.npc_moving,
            cfg.npc_speed, cfg.turn_prob,
        )
    if cfg.behavior == "mlp":
        if nbr is None:
            obs = build_obs_from_features(
                pos, state.vel, yaw, state.nbr_cnt, state.nbr_mean_off,
                cfg.grid.k, world_extent,
            )
        else:
            obs = build_obs(pos, state.vel, yaw, nbr, nbr_cnt,
                            world_extent)
        accel = policy_accel(policy, obs)
        vel = state.vel + accel * cfg.dt
        # cap speed by XZ magnitude (not per-axis) so diagonal movers
        # respect cfg.npc_speed like any other heading
        speed = jnp.sqrt(vel[:, 0] ** 2 + vel[:, 2] ** 2 + 1e-12)
        vel = vel * jnp.minimum(1.0, cfg.npc_speed / speed)[:, None]
        return jnp.where(state.npc_moving[:, None], vel, 0.0)
    return random_walk_step(
        key, state.vel, state.npc_moving, cfg.npc_speed, cfg.turn_prob
    )


def tick_body(
    cfg: WorldConfig,
    state: SpaceState,
    inputs: TickInputs,
    policy: MLPPolicy | None,
) -> tuple[SpaceState, TickOutputs]:
    """Un-jitted single-Space tick (reused by the shard_map'd multi-space
    step in :mod:`goworld_tpu.parallel.step`). See :func:`make_tick`."""
    n = cfg.capacity
    # precision=q16 (ISSUE 12): positions integrate in f32 (the master
    # never loses sub-lattice motion) but everything AOI-visible — the
    # sweep, the Verlet cache, sync records — runs on the SNAPPED
    # lattice view, and the carried velocity plane is bf16 (read
    # promoted here, stored rounded below). The dirty bit dead-bands on
    # the lattice: sub-step jitter moves nothing a client could see, so
    # it stops generating sync records at all (the delta-sync byte
    # story's device half).
    prec = cfg.grid.precision != "off"
    vel_dtype = state.vel.dtype
    if prec:
        state = state.replace(vel=state.vel.astype(jnp.float32))

    # 1. client inputs (scatter). Every numbered phase runs under a
    # ``gw.<phase>`` named scope: metadata only (the HLO is otherwise
    # byte-identical, tests/test_trace_scopes.py), it is how a profiler
    # capture names the phase whatever the compiler calls its ops.
    with jax.named_scope("gw.inputs"):
        pos, yaw, touched = apply_pos_inputs(
            state.pos, state.yaw,
            inputs.pos_sync_idx, inputs.pos_sync_vals, inputs.pos_sync_n,
        )

    # 2. behaviors (vectorized; MXU when behavior == 'mlp'). A scenario
    # config dispatches a heterogeneous population through ONE vmapped
    # lax.switch on the per-entity behavior lane instead of the static
    # Python-if below (goworld_tpu/scenarios/behaviors.py) — one trace
    # per WorldConfig either way.
    with jax.named_scope("gw.behave"):
        rng, k_behave = jax.random.split(state.rng)
        tele = None
        if cfg.scenario is not None:
            vel, tele_pos, tele = scenario_velocity(
                cfg, k_behave, pos, yaw, state, policy
            )
        else:
            vel = compute_velocity(
                cfg, k_behave, pos, yaw, state, policy,
                (cfg.grid.extent_x, cfg.grid.extent_z),
                nbr=state.nbr, nbr_cnt=state.nbr_cnt,
            )

    # 3. integrate + world clamp.
    with jax.named_scope("gw.integrate"):
        pos, moved = integrate(
            pos, vel, state.npc_moving, cfg.dt,
            cfg.bounds_min, cfg.bounds_max,
        )
        if tele is not None:
            # scenario teleports override the integrated position
            # BEFORE the sweep, so the Verlet displacement check sees
            # the full jump and trips the in-graph rebuild cond on this
            # exact tick
            pos = jnp.where(tele[:, None], tele_pos, pos)
            moved = moved | tele
        if prec:
            # the AOI-visible view: snapped lattice positions. "moved"
            # is re-derived IN THE LATTICE DOMAIN (y stays a raw
            # compare) — an entity that didn't cross a lattice step is
            # clean for sync/halo purposes, exactly because no consumer
            # can observe the sub-step motion.
            apos = quantize_positions(cfg.grid, pos)
            aprev = quantize_positions(cfg.grid, state.pos)
            moved = jnp.any(apos != aprev, axis=1)
        else:
            apos = pos
        # state.dirty carries host-set pending force-syncs (spawn marks
        # the new entity dirty so watchers get its position, the
        # syncInfoFlag analog — Entity.go:1189-1205); consumed here,
        # cleared below.
        dirty = (moved | touched | state.dirty) & state.alive

    # 4. AOI sweep (the go-aoi XZList replacement). Per-entity aoi_radius
    # honors EntityTypeDesc.aoiDistance (0 = excluded from AOI). The dirty
    # bit rides the sweep's packed candidate words so sync collection
    # never re-gathers it over [N, k] (r02 TPU profile: that gather cost
    # as much as the sweep itself). With a Verlet skin configured the
    # carried cache lets low-displacement ticks skip the front half +
    # window fetch entirely (lax.cond — NOT valid under vmap, where both
    # branches would run; the World manager clears skin for its vmapped
    # multi-space step like adaptive_extract).
    use_verlet = (
        cfg.grid.skin > 0.0
        and state.aoi_cache is not None
        and n < (1 << _ID_BITS)
    )
    with jax.named_scope("gw.aoi"):
        flag_bits = dirty.astype(jnp.int32) \
            | (state.has_client.astype(jnp.int32) << 1)
        if use_verlet:
            (nbr, nbr_cnt, nbr_fl, aoi_stats, aoi_cache, aoi_rebuilt,
             aoi_slack) = grid_neighbors_verlet(
                cfg.grid, apos, state.alive, state.aoi_cache,
                watch_radius=state.aoi_radius, flag_bits=flag_bits,
                with_stats=True,
            )
        else:
            nbr, nbr_cnt, nbr_fl, aoi_stats = grid_neighbors_flags(
                cfg.grid, apos, state.alive,
                watch_radius=state.aoi_radius, flag_bits=flag_bits,
                with_stats=True,
            )
            aoi_cache = state.aoi_cache
            aoi_rebuilt = jnp.ones((), jnp.int32)
            aoi_slack = jnp.zeros((), jnp.float32)

    # 5. interest deltas -> bounded enter/leave pair lists (changed rows
    # only; the k^2 membership compare never touches stable rows).
    with jax.named_scope("gw.delta"):
        (enter_w, enter_j, enter_n, leave_w, leave_j, leave_n,
         delta_rows_n) = interest_pairs(
            state.nbr, nbr, n, cfg.enter_cap, cfg.leave_cap,
            min(cfg.delta_rows_cap_eff, n),
            adaptive=cfg.adaptive_extract,
        )

    # 6. position sync records (CollectEntitySyncInfos analog). Under
    # precision the records carry the SNAPPED positions — the same
    # lattice values the interest sets were computed from, and exactly
    # what the delta-sync codec re-encodes as int16 steps.
    with jax.named_scope("gw.sync"):
        sync_w, sync_j, sync_vals, sync_n = collect_sync(
            nbr, dirty, state.has_client, apos, yaw, cfg.sync_cap,
            nbr_dirty=(nbr_fl & 1).astype(bool),
            adaptive=cfg.adaptive_extract,
        )

    # 7. hot-attr deltas.
    with jax.named_scope("gw.attrs"):
        attr_e, attr_i, attr_v, attr_n = collect_attr_deltas(
            state.hot_attrs, state.attr_dirty, cfg.attr_sync_cap,
            adaptive=cfg.adaptive_extract,
        )

    new_state = state.replace(
        pos=pos,
        yaw=yaw,
        vel=vel.astype(vel_dtype),
        nbr=nbr,
        nbr_cnt=nbr_cnt,
        nbr_client_cnt=((nbr_fl >> 1) & 1).sum(axis=1).astype(jnp.int32),
        dirty=jnp.zeros_like(state.dirty),
        attr_dirty=jnp.zeros_like(state.attr_dirty),
        rng=rng,
        tick=state.tick + 1,
        aoi_cache=aoi_cache,
    )
    outputs = TickOutputs(
        enter_w=enter_w, enter_j=enter_j, enter_n=enter_n,
        leave_w=leave_w, leave_j=leave_j, leave_n=leave_n,
        delta_rows_n=delta_rows_n,
        sync_w=sync_w, sync_j=sync_j, sync_vals=sync_vals, sync_n=sync_n,
        attr_e=attr_e, attr_i=attr_i, attr_v=attr_v, attr_n=attr_n,
        alive_count=state.alive.sum().astype(jnp.int32),
        aoi_demand_max=aoi_stats[0], aoi_over_k_rows=aoi_stats[1],
        aoi_cell_max=aoi_stats[2], aoi_over_cap_cells=aoi_stats[3],
        aoi_rebuilt=aoi_rebuilt, aoi_skin_slack=aoi_slack,
    )
    return new_state, outputs


def make_tick(cfg: WorldConfig):
    """Build the jitted tick function for a WorldConfig.

    Returns ``tick(state, inputs, policy) -> (state, outputs)``; ``policy``
    is an :class:`MLPPolicy` when ``cfg.behavior == 'mlp'`` else ``None``.
    """

    @jax.jit
    def tick(
        state: SpaceState, inputs: TickInputs, policy: MLPPolicy | None
    ) -> tuple[SpaceState, TickOutputs]:
        return tick_body(cfg, state, inputs, policy)

    return tick


