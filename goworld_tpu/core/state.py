"""Fixed-capacity SoA entity state for one Space shard.

Reference being rebuilt: ``engine/entity/EntityManager.go`` keeps
``map[EntityID]*Entity`` with per-entity structs holding position, yaw, attrs,
client binding, AOI sets (``Entity.go:44-70``). Here the whole population is
a structure-of-arrays pytree of JAX arrays with a static capacity; entity
identity on device is (slot, generation), and the host's EntityManager maps
16-char EntityIDs to slots (free-list allocation is host-side — dynamic
create/destroy never changes array shapes, so the step function compiles
once).

Hot attrs (hp, mp, level, ...) live in a dense f32[N, A] block with a dirty
bitmask driving client attr sync; cold/nested attrs stay host-side in the
MapAttr/ListAttr tree (:mod:`goworld_tpu.entity.attrs`) — the dual
representation called out in ``SURVEY.md#7``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import struct

from goworld_tpu.ops.aoi import (
    _ID_BITS,
    GridSpec,
    VerletCache,
    init_verlet_cache,
)
from goworld_tpu.scenarios.spec import (
    ScenarioSpec,
    assign_behavior_ids,
    assign_watch_radii,
)
from goworld_tpu.utils import consts


@dataclasses.dataclass(frozen=True)
class WorldConfig:
    """Static per-Space configuration (hashable; closed over by jit)."""

    capacity: int = consts.DEFAULT_CAPACITY
    attr_width: int = 8                       # hot-attr columns (<= 32)
    grid: GridSpec = GridSpec(radius=50.0)
    dt: float = 1.0 / consts.TICK_HZ
    npc_speed: float = 5.0
    turn_prob: float = 0.05                   # random-walk heading change/tick
    behavior: str = "random_walk"             # "mlp" (models.npc_policy) or
                                              # "btree" (models.behavior_tree)
    enter_cap: int = consts.DEFAULT_EVENT_CAP
    leave_cap: int = consts.DEFAULT_EVENT_CAP
    sync_cap: int = consts.DEFAULT_SYNC_CAP
    attr_sync_cap: int = consts.DEFAULT_EVENT_CAP
    # churn-adaptive two-tier event extraction (ops/extract.two_tier).
    # MUST be False when tick_body runs under vmap (the single-device
    # multi-space path): cond batches to select_n and both tiers would
    # execute. The World manager clears it for its vmapped local step.
    adaptive_extract: bool = True
    input_cap: int = consts.DEFAULT_INPUT_CAP
    # Adversarial scenario matrix (goworld_tpu/scenarios): when set, the
    # tick's behavior phase dispatches a HETEROGENEOUS population — every
    # entity carries a behavior lane (SpaceState.behavior_id indexing the
    # spec's mix order) through ONE vmapped lax.switch, and `behavior`
    # above is ignored for velocity. ScenarioSpec is frozen/hashable so
    # the config still closes over jit exactly like GridSpec.
    scenario: ScenarioSpec | None = None
    delta_rows_cap: int = 0  # max rows whose AOI list may change per tick
    # before enter/leave events overflow (ops.delta.interest_pairs).
    # <= 0 means "capacity": the row pre-filter then never drops events
    # the enter/leave pair caps had headroom for (a mass-spawn/teleport
    # tick changes nearly every row; a sub-capacity default silently lost
    # its surplus rows' events). Set explicitly to trade compare work for
    # drop risk — it is a pure optimization knob, not a correctness one.

    def __post_init__(self):
        if self.behavior not in ("random_walk", "mlp", "btree"):
            # a typo would otherwise silently fall through to random_walk
            # in compute_velocity
            raise ValueError(
                f"behavior must be random_walk|mlp|btree, "
                f"got {self.behavior!r}"
            )
        if self.scenario is not None \
                and not isinstance(self.scenario, ScenarioSpec):
            raise ValueError(
                "scenario must be a ScenarioSpec (see "
                "goworld_tpu.scenarios.spec.get_scenario), "
                f"got {type(self.scenario).__name__}"
            )

    @property
    def delta_rows_cap_eff(self) -> int:
        """``delta_rows_cap`` resolved: <= 0 tracks ``capacity``."""
        return self.delta_rows_cap if self.delta_rows_cap > 0 \
            else self.capacity

    @property
    def bounds_min(self) -> tuple[float, float, float]:
        g = self.grid
        return (g.origin_x, -1e9, g.origin_z)

    @property
    def bounds_max(self) -> tuple[float, float, float]:
        g = self.grid
        return (g.origin_x + g.extent_x, 1e9, g.origin_z + g.extent_z)


@struct.dataclass
class SpaceState:
    """One Space's population as SoA arrays (a pytree; leaves on device)."""

    pos: jax.Array          # f32[N, 3]
    yaw: jax.Array          # f32[N]
    vel: jax.Array          # f32[N, 3]
    alive: jax.Array        # bool[N]
    npc_moving: jax.Array   # bool[N]  entity moves by velocity integration
    has_client: jax.Array   # bool[N]
    client_gate: jax.Array  # i32[N]   owning gate id (-1 none)
    type_id: jax.Array      # i32[N]
    gen: jax.Array          # i32[N]   slot generation (stale-handle guard)
    hot_attrs: jax.Array    # f32[N, A]
    attr_dirty: jax.Array   # u32[N]   bitmask over attr columns
    nbr: jax.Array          # i32[N, k] sorted AOI neighbor list (sentinel N)
    nbr_cnt: jax.Array      # i32[N]
    nbr_client_cnt: jax.Array  # i32[N] client-owning neighbors as of the
                               # last AOI sweep (behavior-tree feature;
                               # rides the sweep's flag bits for free)
    nbr_mean_off: jax.Array  # f32[N, 3] mean neighbor offset, computed at
                             # AOI time (megaspace MLP observations read
                             # this — its gid neighbor lists can't gather
                             # positions locally; one tick stale, like the
                             # single-space path's prev-tick nbr lists)
    aoi_radius: jax.Array   # f32[N] per-entity AOI distance; 0 = excluded
                            # from AOI entirely, +inf = space default radius
                            # (reference EntityTypeDesc.aoiDistance,
                            # EntityManager.go:24-101)
    dirty: jax.Array        # bool[N]  moved this tick (syncInfoFlag analog)
    rng: jax.Array          # PRNG key
    tick: jax.Array         # i32 scalar
    # Verlet AOI cache (ops.aoi.VerletCache): carried front-half
    # products — candidate ids, reference positions/alive/radii, age,
    # rebuild flag state — letting ticks whose max displacement stays
    # under skin/2 skip the sweep's front half entirely. None when
    # cfg.grid.skin == 0 (no memory cost); the skinless tick passes it
    # through untouched.
    aoi_cache: VerletCache | None = None
    # Per-entity scenario behavior lane (i32[N], dense index into
    # cfg.scenario.mix order; scenarios/behaviors.py dispatches the
    # population through one vmapped lax.switch on it). None when
    # cfg.scenario is None — legacy homogeneous worlds carry no lane.
    # The lane belongs to the SLOT: a respawn inherits it, which is
    # exactly what scenario churn wants (the mix fractions hold).
    behavior_id: jax.Array | None = None


def seed_key(seed: int) -> jax.Array:
    """The ONE place a world seed becomes a PRNG key (state rng, the
    config-built MLP policy, the scenario runner). The stream is the
    installed JAX's default: threefry2x32 in its PARTITIONABLE form
    (``jax_threefry_partitionable``, on by default since jax 0.5) — the
    form a mesh can draw shard-locally, and a different stream from the
    one jax 0.4 drew for the same seed. It is backend-independent
    (integer arithmetic), so ``--seed`` means the same world on the CPU
    and on the chip; tests/test_step.py pins its first words so a
    change of default can never pass silently again."""
    return jax.random.PRNGKey(seed)


def create_state(cfg: WorldConfig, seed: int = 0) -> SpaceState:
    n, a, k = cfg.capacity, cfg.attr_width, cfg.grid.k
    scn = cfg.scenario
    if scn is not None:
        # deterministic per-slot scenario lanes: behavior mix + the
        # watch-radius distribution (host spawns through an entity
        # registry overwrite aoi_radius per type — the runner registers
        # one type per radius class, so both paths agree)
        behavior_id = jnp.asarray(assign_behavior_ids(scn, n, seed))
        aoi_radius = jnp.asarray(assign_watch_radii(scn, n, seed))
    else:
        behavior_id = None
        aoi_radius = jnp.full((n,), jnp.inf, jnp.float32)
    # precision=q16 (cfg.grid.precision): the carried velocity plane is
    # bf16 — integration and behaviors read it promoted to f32 and the
    # tick stores back rounded, halving the plane's HBM stream ("where
    # consumers tolerate it": velocity is a behavior-internal quantity,
    # never an oracle input — positions remain the f32 master)
    vel_dtype = jnp.bfloat16 if cfg.grid.precision != "off" \
        else jnp.float32
    return SpaceState(
        pos=jnp.zeros((n, 3), jnp.float32),
        yaw=jnp.zeros((n,), jnp.float32),
        vel=jnp.zeros((n, 3), vel_dtype),
        alive=jnp.zeros((n,), bool),
        npc_moving=jnp.zeros((n,), bool),
        has_client=jnp.zeros((n,), bool),
        client_gate=jnp.full((n,), -1, jnp.int32),
        type_id=jnp.zeros((n,), jnp.int32),
        gen=jnp.zeros((n,), jnp.int32),
        hot_attrs=jnp.zeros((n, a), jnp.float32),
        attr_dirty=jnp.zeros((n,), jnp.uint32),
        nbr=jnp.full((n, k), n, jnp.int32),
        nbr_cnt=jnp.zeros((n,), jnp.int32),
        nbr_client_cnt=jnp.zeros((n,), jnp.int32),
        nbr_mean_off=jnp.zeros((n, 3), jnp.float32),
        aoi_radius=aoi_radius,
        dirty=jnp.zeros((n,), bool),
        rng=seed_key(seed),
        tick=jnp.zeros((), jnp.int32),
        # mirrors tick_body's use_verlet guard: past the packed-id
        # bound the tick statically falls back to the stateless sweep,
        # so allocating the [n, verlet_cap] cache there would be
        # carried dead weight (~400 MB at 2M capacity)
        aoi_cache=(init_verlet_cache(cfg.grid, n)
                   if cfg.grid.skin > 0.0 and n < (1 << _ID_BITS)
                   else None),
        behavior_id=behavior_id,
    )


def spawn(
    state: SpaceState,
    slot: int,
    *,
    pos,
    yaw: float = 0.0,
    type_id: int = 0,
    npc_moving: bool = False,
    has_client: bool = False,
    client_gate: int = -1,
    hot_attrs=None,
    aoi_radius: float = float("inf"),
) -> SpaceState:
    """Host-side spawn into a free slot (infrequent; not on the hot path).

    The reference creates entities via ``createEntity``
    (``EntityManager.go:201``); here a spawn is a handful of .at[] updates —
    the slot choice (free list) lives in the host EntityManager.

    IMPORTANT free-list contract: do not reuse a slot in the same tick it
    was despawned — the slot's stale neighbor list must survive one tick so
    the previous occupant's AOI leave events fire on the next interest diff
    (the host EntityManager quarantines freed slots for one tick; the device
    migration path does the same via ``insert_arrivals(quarantine=...)``).
    """
    if hot_attrs is None:
        hot_attrs = jnp.zeros(
            (state.hot_attrs.shape[1],), jnp.float32
        )  # fresh occupant never inherits the previous entity's attrs
    upd = dict(
        pos=state.pos.at[slot].set(jnp.asarray(pos, jnp.float32)),
        yaw=state.yaw.at[slot].set(yaw),
        vel=state.vel.at[slot].set(0.0),
        alive=state.alive.at[slot].set(True),
        npc_moving=state.npc_moving.at[slot].set(npc_moving),
        has_client=state.has_client.at[slot].set(has_client),
        client_gate=state.client_gate.at[slot].set(client_gate),
        type_id=state.type_id.at[slot].set(type_id),
        aoi_radius=state.aoi_radius.at[slot].set(aoi_radius),
        gen=state.gen.at[slot].add(1),
        dirty=state.dirty.at[slot].set(True),
        hot_attrs=state.hot_attrs.at[slot].set(
            jnp.asarray(hot_attrs, jnp.float32)
        ),
        attr_dirty=state.attr_dirty.at[slot].set(jnp.uint32(0)),
    )
    return state.replace(**upd)


def despawn(state: SpaceState, slot: int) -> SpaceState:
    """Host-side destroy (``destroyEntity``, ``Entity.go:631-651``)."""
    return state.replace(
        alive=state.alive.at[slot].set(False),
        has_client=state.has_client.at[slot].set(False),
        client_gate=state.client_gate.at[slot].set(-1),
        npc_moving=state.npc_moving.at[slot].set(False),
        dirty=state.dirty.at[slot].set(False),
        attr_dirty=state.attr_dirty.at[slot].set(jnp.uint32(0)),
    )
