"""World — the host-side entity manager and tick driver.

Reference being rebuilt: ``engine/entity/EntityManager.go`` (type registry,
id->entity maps, create/load/restore, RPC entry — ``:155-434``) fused with
the game process's serve loop (``components/game/GameService.go:77-190``):
the reference interleaves per-entity work across 5 ms timer ticks; here the
host stages all mutations between ticks, flushes them as vectorized scatters,
runs ONE jitted device step for all spaces, and fans the step's event arrays
back out to Python hooks and client messages.

Slot lifecycle contract (the "dynamic entities on static shapes" hard part,
``SURVEY.md#7``): a slot freed by a host despawn is flushed before the step,
so its watchers' leave events fire in THAT step; the slot returns to the
free set after those events are processed. A slot freed by an in-step
migration departure gets its leave events one step later, so it is released
one tick later (``_release_next``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
import weakref
from collections import defaultdict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from goworld_tpu.core.state import SpaceState, WorldConfig
from goworld_tpu.core.step import TickInputs, tick_body
from goworld_tpu.entity.attrs import (
    AttrDelta,
    ListAttr,
    MapAttr,
    load_into,
    make_root,
    sever_tree,
)
from goworld_tpu.entity.entity import Entity, GameClient
from goworld_tpu.entity.registry import (
    RF_OTHER_CLIENT,
    RF_OWN_CLIENT,
    Registry,
)
from goworld_tpu.entity.space import Space
from goworld_tpu.entity.timer import Crontab, PostQueue, TimerQueue
from goworld_tpu.parallel.mesh import create_multi_state
from goworld_tpu.utils import consts, ids, log, metrics, opmon, tracing

logger = log.get("world")


def _bucket(n: int, lo: int = 8) -> int:
    """Next power-of-two >= n. Host->device scatter batches are padded to
    bucket sizes so XLA compiles one executable per bucket instead of one
    per distinct batch length (unpadded, every tick with a new staging
    count pays a fresh compile — hundreds of ms each)."""
    b = lo
    while b < n:
        b *= 2
    return b


def _pad_scatter(sh: np.ndarray, sl: np.ndarray, capacity: int,
                 *vals: np.ndarray):
    """Pad index/value arrays to the bucket size; padded rows point at
    slot=capacity (out of bounds) and are dropped by ``mode='drop'``."""
    n = sh.shape[0]
    b = _bucket(n)
    if b == n:
        return (sh, sl) + vals
    pad = b - n
    sh = np.concatenate([sh, np.zeros(pad, sh.dtype)])
    sl = np.concatenate([sl, np.full(pad, capacity, sl.dtype)])
    out = [
        np.concatenate([v, np.zeros((pad,) + v.shape[1:], v.dtype)])
        for v in vals
    ]
    return (sh, sl) + tuple(out)


def _type_aoi_radius(desc) -> float:
    """Device aoi_radius for a type (reference EntityTypeDesc.aoiDistance,
    ``EntityManager.go:24-101``): use_aoi=False types are excluded from AOI
    entirely (radius 0 — invisible and blind, the service-entity case); an
    explicit aoi_distance > 0 bounds the type's view; otherwise +inf means
    "the space's uniform radius" (GridSpec.radius caps the reach)."""
    if not desc.use_aoi:
        return 0.0
    if desc.aoi_distance > 0:
        return float(desc.aoi_distance)
    return float("inf")


def _make_local_tick(cfg: WorldConfig, n_spaces: int = 1,
                     donate: bool = False):
    """Stacked-spaces step on ONE device — the single-process analog of
    the mesh's shard_map step. n_spaces == 1 (the common production
    shape) calls tick_body directly on the squeezed state, so runtime
    lax.cond paths stay real branches: the churn-adaptive extraction
    tiers AND the Verlet skin's rebuild-vs-reuse dispatch both work.
    n_spaces > 1 vmaps, where cond batches to select_n (both branches
    execute every tick) — the adaptive tiers and the skin are cleared
    there because each would be a strict pessimization under vmap.

    donate=True marks the SpaceState carry (arg 0) as donated: XLA
    aliases the output carry onto the input buffers (the resident-world
    contract), which DELETES the caller's old carry after dispatch —
    every host-side reader must use the returned state or an explicit
    device copy taken before the call. keep_unused rides donation:
    lanes the behavior doesn't read (e.g. old nbr_cnt under
    random_walk) would otherwise be PRUNED from the computation and
    lose their donation source — fresh buffers every tick for exactly
    those lanes."""
    dn = (0,) if donate else ()
    if n_spaces == 1:
        def step1(state, inputs, policy):
            s1, out = tick_body(
                cfg,
                jax.tree.map(lambda x: x[0], state),
                jax.tree.map(lambda x: x[0], inputs),
                policy,
            )
            return (jax.tree.map(lambda x: x[None], s1),
                    jax.tree.map(lambda x: x[None], out))

        return jax.jit(step1, donate_argnums=dn, keep_unused=donate)

    cfg = dataclasses.replace(
        cfg, adaptive_extract=False,
        grid=dataclasses.replace(cfg.grid, skin=0.0),
    )

    def step(state, inputs, policy):
        return jax.vmap(
            lambda s, i: tick_body(cfg, s, i, policy)
        )(state, inputs)

    return jax.jit(step, donate_argnums=dn, keep_unused=donate)


def _start_host_copy(tree) -> None:
    """Double-buffered output drain (ISSUE 20): kick off the async D2H
    copy of every leaf in ``tree`` NOW, so the transfer of tick T's
    parked output lanes (TickOutputs, telemetry accumulator, sync-age
    anchor rides them) overlaps the device's compute of tick T+1 —
    next tick's blocking fetch then finds the bytes already staged
    host-side. On an accelerator a failure here is a defect and
    surfaces: swallowing it would serve the serial fetch under the
    overlapped path's name.

    Skipped entirely on the CPU backend: the buffers are already
    host-resident there, and copy_to_host_async on a still-executing
    output WAITS for the producing computation — the prefetch would
    serialize the very overlap it exists to buy."""
    if tree is None or jax.default_backend() == "cpu":
        return
    for leaf in jax.tree.leaves(tree):
        leaf.copy_to_host_async()


# what the waiter of a tick in flight blocks in (the interpreter lock is
# released inside), and what says that nothing has to be waited for;
# names of their own so a test can hold the device
_block_until_landed = jax.block_until_ready


def _has_landed(tree) -> bool:
    return all(leaf.is_ready() for leaf in jax.tree.leaves(tree))


class TickInFlight:
    """A tick between its two halves: ``World.tick_dispatch`` has
    flushed the staging and dispatched the device step, and
    ``World.tick_land`` will fetch and decode what ``fetch`` names.
    In between the caller may run handlers: what they stage belongs to
    the next flush. ``landed``: nothing is left to wait for (set by the
    World's waiter where the caller asked for one:
    ``World.watch_landing``)."""

    __slots__ = ("fetch", "outs", "age_mark", "t_start", "t0", "landed")

    def __init__(self, fetch, outs, age_mark, t_start, t0):
        self.fetch = fetch
        self.outs = outs
        self.age_mark = age_mark
        self.t_start = t_start
        self.t0 = t0
        self.landed = not fetch


def _await_landings(flights: queue.SimpleQueue) -> None:
    """A World's waiter: for each tick in flight handed over, start the
    outputs' copy to the host, block until the device is done, mark
    the tick ``landed`` and set the caller's event. Ends with its
    World (a ``None``)."""
    while (job := flights.get()) is not None:
        flight, wake = job
        fetch = flight.fetch    # tick_land may have taken it (a stop)
        try:
            _start_host_copy(fetch)
            _block_until_landed(fetch)
        except Exception:
            # a device error surfaces in tick_land's own fetch, on the
            # caller's thread, as it always has
            logger.exception("waiting for a tick's outputs failed")
        finally:
            flight.landed = True
            wake.set()
        # nothing of a landed tick outlives it here: the device's
        # output planes are freed when tick_land lets go of them, not
        # when the next tick is handed over
        del job, flight, wake, fetch


class AdmissionPausedError(RuntimeError):
    """``create_entity`` into a space whose admission a rebalance
    handoff paused mid-move (goworld_tpu/rebalance/). Callers place
    the entity elsewhere or retry after the move completes — silent
    placement into a draining space would refill the cohort under
    the handoff."""


class World:
    """Hosts every entity of one game process (= one device or one mesh).

    Parameters:
      cfg: per-space device config (shared by all spaces).
      n_spaces: number of AOI shards in the stacked state.
      mesh: optional jax Mesh; when given, spaces shard over its "space"
        axis and cross-space migration rides all_to_all
        (:mod:`goworld_tpu.parallel.step`); when None, everything runs on
        the default device under vmap.
      clock: injectable time source for timers (tests pass virtual time).
    """

    def __init__(
        self,
        cfg: WorldConfig,
        n_spaces: int = 1,
        *,
        mesh=None,
        game_id: int = 1,
        clock: Callable[[], float] = time.monotonic,
        seed: int = 0,
        migrate_cap: int = 256,
        megaspace: bool = False,
        halo_cap: int = 1024,
        halo_impl: str = "ppermute",
        mega_shape: tuple[int, int] | None = None,
        pipeline_decode: bool = False,
        resident: bool = True,
        telemetry_live: bool = True,
        snapshot_keyframe_every: int = 0,
        residency: bool = True,
        residency_sample_every: int = 16,
        audit: bool = True,
        audit_sample_every: int = 64,
        audit_cohort: int = 64,
    ):
        # delta-compressed snapshot cadence (ISSUE 12, freeze.py
        # SnapshotChain): every Nth checkpoint is a full quantized
        # keyframe, the rest ship sparse int16 plane deltas against it;
        # 0 = today's monolithic msgpack snapshots, bit-identically
        self.snapshot_keyframe_every = max(0, int(snapshot_keyframe_every))
        self.cfg = cfg
        self.n_spaces = n_spaces
        self.game_id = game_id
        self.registry = Registry()
        self.mesh = mesh
        self.policy = None  # MLPPolicy when cfg.behavior == 'mlp' (or a
        # scenario mix includes the mlp member)
        if cfg.behavior == "mlp" or (
            cfg.scenario is not None and cfg.scenario.needs_policy
        ):
            # config-built worlds need a live policy; callers may replace
            # it (e.g. with trained weights) before the first tick
            from goworld_tpu.core.state import seed_key
            from goworld_tpu.models.npc_policy import init_policy

            self.policy = init_policy(seed_key(seed))
        self.mega = None    # MegaConfig when megaspace=True
        # pipelined host decode (see tick()): only the single-
        # controller, non-mesh shape qualifies — reject loudly instead
        # of silently decoding a tick late where same-tick couplings
        # (staged-migration tags, mega arrivals, SPMD collectives)
        # would corrupt state
        if pipeline_decode and (mesh is not None or megaspace):
            raise ValueError(
                "pipeline_decode requires a single-device, "
                "non-megaspace World"
            )
        self.pipeline_decode = pipeline_decode
        # resident-world runtime (ISSUE 20): donate the SpaceState carry
        # into the tick so XLA aliases it in place — zero steady-state
        # HBM allocation on the serve loop. The old carry is DELETED
        # after every dispatch; planes that capture a state reference
        # across a tick (freeze/snapshot) fence with an explicit device
        # copy instead (loud one-time copy-mode log). Bit-identical to
        # resident=False by construction: donation is an allocator
        # aliasing hint, never a numerics change.
        self.resident = resident
        self._resident_copy_warned = False
        self._pending_outs = None
        # set by freeze.restore_world: {client owner's eid: the eids its
        # client was told it can see when the world froze}; consumed by
        # the first tick (_reconcile_restored_interest)
        self._restored_interest: dict[str, list[str]] | None = None
        if mesh is not None and mesh.devices.size != n_spaces:
            raise ValueError(
                f"mesh has {mesh.devices.size} devices but "
                f"n_spaces={n_spaces}"
            )
        if megaspace:
            # ONE logical space spans the whole mesh as tiles — x strips,
            # or XZ tiles when mega_shape=(tx, tz) is given (BASELINE
            # config 4; SURVEY.md#5.7). cfg.grid is the TILE grid in
            # tile-shifted coords: extent_x = tile_w + 2*radius (and
            # extent_z = tile_d + 2*radius for 2D tiles).
            from goworld_tpu.parallel.megaspace import (
                MegaConfig, create_mega_state, make_mega_tick,
            )

            if mesh is None:
                raise ValueError("megaspace=True requires a mesh")
            from goworld_tpu.parallel.mesh import shard_state

            tile_w = cfg.grid.extent_x - 2.0 * cfg.grid.radius
            tile_d = 0.0
            if mega_shape is not None and mega_shape[1] > 1:
                tile_d = cfg.grid.extent_z - 2.0 * cfg.grid.radius
            self.mega = MegaConfig(
                cfg=cfg, n_dev=n_spaces, tile_w=tile_w,
                halo_cap=halo_cap, migrate_cap=migrate_cap,
                mesh_shape=mega_shape, tile_d=tile_d,
                halo_impl=halo_impl,
            )
            self.state = shard_state(
                create_mega_state(self.mega, seed=seed), mesh
            )
            self._step = make_mega_tick(self.mega, mesh,
                                        donate=resident)
            # tile-ticks that named their neighbours by the all-rows
            # gather (more ghost-holding rows than the fast path takes)
            self.mega_ghost_full_path = 0
            self._m_mega_ghost_full = metrics.counter(
                "mega_ghost_full_path_total",
                help="tile-ticks whose ghost-holding rows exceeded "
                     "ghost_sort_rows (all-rows gather and sort)",
            )
            self._m_mega_ghost_rows = metrics.gauge("mega_ghost_rows")
        else:
            state_cfg = cfg
            if mesh is None and n_spaces > 1 and cfg.grid.skin > 0:
                # the vmapped local step clears the skin (cond would
                # batch to select_n — see _make_local_tick); don't
                # allocate [capacity, verlet_cap] caches per space that
                # the step statically never touches
                state_cfg = dataclasses.replace(
                    cfg,
                    grid=dataclasses.replace(cfg.grid, skin=0.0),
                )
            self.state: SpaceState = create_multi_state(
                state_cfg, n_spaces, seed=seed
            )
            if mesh is not None:
                from goworld_tpu.parallel.mesh import shard_state
                from goworld_tpu.parallel.step import make_multi_tick

                self.state = shard_state(self.state, mesh)
                self._step = make_multi_tick(
                    cfg, mesh, migrate_cap=migrate_cap,
                    donate=resident,
                )
            else:
                self._step = _make_local_tick(cfg, n_spaces,
                                              donate=resident)

        # device-plane cost observability (utils/devprof, served at
        # debug_http /costs): register the compiled step as a LAZY
        # analyze provider — a lower+compile costs seconds, so it runs
        # only when an operator asks (?analyze=1), never per scrape.
        # Registered through a weakref: the devprof registry is
        # process-global, and a bound method would pin a discarded
        # World's full device-array state (hundreds of MB at bench
        # scale) for the life of the process.
        import weakref

        from goworld_tpu.utils import devprof

        wself = weakref.ref(self)

        def _tick_cost_provider():
            w = wself()
            if w is None:
                return {"name": "world.tick",
                        "error": "world discarded"}
            return w.cost_report()

        devprof.register_provider("world.tick", _tick_cost_provider)

        # live device-telemetry lanes (ISSUE 11; ops/telemetry.py): the
        # bench-only in-graph histograms promoted to the PRODUCTION
        # per-tick step — one small jitted fold per tick accumulates
        # tick signals (rebuilt/skin_slack/over_k/over_cap/sync/enter/
        # leave + per-shard occupancy, + halo/migrate demand on the
        # mega mesh) on device with zero added host syncs; the drain
        # rides the tick's EXISTING fetch-outputs transfer. Feeds the
        # metrics registry on a cadence and the workload-signature
        # reducer (/workload) over a rotating window.
        self.telemetry_live = bool(telemetry_live)
        self._telem_fn = None
        self._telem_acc = None
        self._telem_lanes = None    # latest drained cumulative (host)
        self._telem_win = None      # window-start cumulative (signature)
        self._telem_win_tick = 0
        self._telem_last_window = None  # last COMPLETED window's delta
        self._pending_telem = None  # pipelined drain: last tick's acc
        # sync-age provenance (utils/syncage.py): the device-tick epoch
        # whose outputs the host is currently fanning out — (seq,
        # tick-start wall us, outputs-host-visible wall us), captured at
        # the EXISTING fetch-outputs transfer (two time.time() calls per
        # tick, zero extra device syncs). Under pipeline_decode the mark
        # swaps one tick back alongside the outputs, so the anchor
        # always describes the epoch the staged sync records came from.
        self.sync_age_anchor: tuple[int, int, int] | None = None
        self._age_pending_mark: tuple[int, int] | None = None
        self._telem_feed_mark = None  # last metrics-fed cumulative
        # negative start: the FIRST drain feeds the registry (a fresh
        # process is scrapeable right away), then the cadence holds
        self._telem_feed_tick = -self.TELEM_FEED_TICKS
        if self.telemetry_live:
            try:
                self._init_live_telemetry()
            except Exception:
                # observability must never take serving down: disable
                # the lanes loudly and keep ticking
                logger.exception("live telemetry init failed; disabled")
                self._telem_fn = self._telem_acc = None

        # serve-loop residency plane (utils/residency.py, ISSUE 16):
        # host-sync bubble / alloc-churn / serve-gap verdicts from
        # perf_counter marks riding this tick's existing structure —
        # zero added device syncs. Constructed OUTSIDE a try block: a
        # bad residency_sample_every must fail loudly (the GridSpec
        # convention), only runtime sampling degrades gracefully.
        self.residency = None
        if residency:
            from goworld_tpu.utils import residency as residency_mod

            self.residency = residency_mod.register(
                f"game{game_id}",
                residency_mod.ResidencyTracker(
                    f"game{game_id}",
                    sample_every=residency_sample_every))

        # correctness audit plane (utils/audit.py, ISSUE 17): an
        # INDEPENDENT entity-ownership ledger fed by the create/
        # destroy/migrate hooks below, plus a sampled live AOI oracle —
        # every audit_sample_every ticks one cohort's interest sets are
        # recomputed brute-force on a background worker against planes
        # that rode THIS tick's existing fetch-outputs transfer (zero
        # added device syncs; see the aud_req piggyback in tick()).
        # Constructed OUTSIDE a try block like residency: bad knobs
        # fail loudly, only runtime sampling degrades gracefully.
        self.audit = None
        self._audit_shard = 0
        if audit:
            from goworld_tpu.utils import audit as audit_mod

            self.audit = audit_mod.register(
                f"game{game_id}",
                audit_mod.AuditPlane(
                    f"game{game_id}",
                    sample_every=audit_sample_every,
                    cohort=audit_cohort))

        # host object model
        self.entities: dict[str, Entity] = {}
        self.spaces: dict[str, Space] = {}
        # spaces currently refusing NEW entity admission (a rebalance
        # handoff pauses its donor space mid-move so the cohort it is
        # draining cannot refill under it; goworld_tpu/rebalance/)
        self._admission_paused: set[str] = set()
        self._slot_owner: list[dict[int, str]] = [
            {} for _ in range(n_spaces)
        ]
        # numpy mirrors of slot -> (entity id, client id, gate), kept
        # incrementally in lockstep with _slot_owner / client binding:
        # the sync-record fan-out decodes tens of thousands of records
        # per tick, and per-record dict lookups (the reference's per-
        # entity Go loops, Entity.go:1208-1267) would rival the device
        # tick itself at 1M-entity scale — with the mirrors the decode
        # is pure numpy gather + groupby (see _process_outputs)
        self._mir_eid = np.zeros((n_spaces, cfg.capacity), "S16")
        self._mir_cid = np.zeros((n_spaces, cfg.capacity), "S16")
        self._mir_gate = np.full((n_spaces, cfg.capacity), -1, np.int32)
        self._free: list[set[int]] = [
            set(range(cfg.capacity)) for _ in range(n_spaces)
        ]
        self._shard_space: list[str | None] = [None] * n_spaces
        self.nil_space: Space | None = None

        # runtime utils
        self.timers = TimerQueue(clock)
        self.post_q = PostQueue()
        self.crontab = Crontab()
        self.tick_count = 0
        self.last_outputs = None  # device outputs of the most recent tick

        # staging buffers (flushed as vectorized scatters each tick)
        self._staged_spawn: list[tuple[int, int, dict]] = []
        self._staged_despawn: list[tuple[int, int]] = []
        self._staged_hot: list[tuple[int, int, int, float]] = []
        self._staged_moving: list[tuple[int, int, bool]] = []
        self._staged_client: list[tuple[int, int, bool, int]] = []
        self._staged_pos: dict[tuple[int, int], Entity] = {}
        # upstream (client->server) pos-sync BATCH path: slot-addressed
        # staging arrays + a lazily rebuilt eid->(shard,slot) intern
        # index over the client-bound mirror columns, so a decoded
        # MT_SYNC_POSITION_YAW_FROM_CLIENT batch resolves in one
        # searchsorted instead of a per-record dict walk (the reference
        # decodes per record in Go, GameService.go:395-407; at 10K+
        # clients the Python equivalent becomes the host wall). Lazy
        # allocation: worlds that never see a client batch pay nothing.
        self._batch_pos_mask: np.ndarray | None = None
        self._batch_pos_vals: np.ndarray | None = None
        self._batch_pos_any = False
        self._sync_index: tuple | None = None
        # pinned host staging (ISSUE 20): the flush-staging scatter and
        # the sync-record fan-out reuse these preallocated host buffers
        # instead of fresh numpy allocations per tick — together with
        # carry donation this makes the steady-state serve loop
        # allocation-free on the host side too. The input-staging
        # trio is zeroed before reuse (the device consumer reads only
        # rows < counts, but zero-fill keeps the transfer deterministic);
        # the sync scratch is gather-overwritten up to sn each flush and
        # never escapes _process_outputs (boolean-masked COPIES go to
        # the sync sink).
        ic = cfg.input_cap
        self._pin_idx = np.zeros((n_spaces, ic), np.int32)
        self._pin_vals = np.zeros((n_spaces, ic, 4), np.float32)
        self._pin_counts = np.zeros((n_spaces,), np.int32)
        self._scr_cid = np.zeros((cfg.sync_cap,), "S16")
        self._scr_gate = np.zeros((cfg.sync_cap,), np.int32)
        self._scr_eid = np.zeros((cfg.sync_cap,), "S16")
        # (src_shard, src_slot, dst_shard, eid) — device-migration requests
        self._staged_migrate: list[tuple[int, int, int, str]] = []
        self._migrate_tags: dict[int, tuple[str, int, int]] = {}
        # (shard, slot, expected_owner_eid): release only applies if the
        # slot still belongs to that entity — a device arrival may have
        # re-occupied a host-despawned slot within the same step
        self._release_now: list[tuple[int, int, str | None]] = []
        self._release_next: list[tuple[int, int, str | None]] = []

        # attr journaling
        self._dirty_attr_entities: dict[str, list[AttrDelta]] = {}

        # the waiter of ticks in flight (watch_landing), made when needed
        self._landings: queue.SimpleQueue | None = None

        # per-tick device read cache
        self._pos_cache: np.ndarray | None = None
        self._yaw_cache: np.ndarray | None = None

        # multi-controller (multi-host) mode: every process runs this
        # World as the SAME program (identical registrations, spawns and
        # staged mutations each tick — the SPMD contract,
        # goworld_tpu/parallel/multihost.py); device fetches then go
        # through process_allgather, and CLIENT-FACING event decode
        # (enter/leave/sync/attr fan-out) covers only the shards on this
        # process's devices, so each host fans out exactly its own tiles'
        # events. Bookkeeping (slot ownership, arrivals) stays global so
        # every controller stages identical follow-up mutations.
        self._multihost = mesh is not None and jax.process_count() > 1
        if self._multihost:
            from goworld_tpu.parallel.multihost import local_shard_indices

            self.local_shards = local_shard_indices(mesh)
            self.mh_rank = jax.process_index()
        else:
            self.local_shards = list(range(n_spaces))
            self.mh_rank = 0
        # deterministic auto-eid sequence for multihost (see _gen_eid)
        self._mh_eid_seq = 0
        # allgathered "every controller is deployment-ready" fact,
        # published by the GameServer's mutation exchange each tick;
        # standalone multihost worlds (no cluster plane) are always ready
        self.mh_group_ready = True

        # pluggable sinks (the gateway overrides these; defaults capture)
        self.client_messages: list[tuple[int, str, dict]] = []
        self.client_sink: Callable[[int, str, dict], None] | None = None
        # batched downstream sync: sync_sink(gate_id, cids, eids, vals)
        # replaces per-record "sync" dicts when set (the game-server path)
        self.sync_sink: Callable[[int, list, list, np.ndarray], None] | None \
            = None
        self.filtered_sink = None  # set by the gateway (stage 3)
        self.remote_router = None  # cross-process RPC hook
        # cross-process EnterSpace: called when the target space is not
        # hosted here (reference requestMigrateTo, Entity.go:1006-1012)
        self.remote_space_router: Callable[[Entity, str, tuple], None] | None \
            = None
        self.storage = None        # persistence backend (stage 6)
        # periodic per-entity persistence (reference Entity.go:164-177
        # setupSaveTimer + config save_interval, default 5 min): every
        # persistent entity saves on this cadence, not only on destroy.
        # Raw timers — never dumped into migrate/freeze data, exactly like
        # the reference's addRawTimer.
        self.save_interval: float = 300.0
        self._save_timers: dict[str, int] = {}
        self.service_mgr = None    # sharded services (stage 5)
        # cluster notifications (the game server wires these)
        self.on_entity_created: Callable[[Entity], None] | None = None
        self.on_entity_destroyed: Callable[[Entity], None] | None = None
        self.op_stats: dict[str, float] = defaultdict(float)
        # overload degradation (utils/overload.py): when > 1 the
        # position-sync fan-out serves each entity cohort every Nth
        # tick (cohort = subject slot % stride) — the GameServer's
        # governor sets it in DEGRADED and restores 1 on recovery
        self.sync_stride = 1
        self._aoi_alarm_tick = -(1 << 30)  # last AOI-overflow alarm tick
        # scrapeable AOI saturation series (debug_http /metrics): the
        # counter accumulates truncated rows/cells; the gauges mirror
        # the per-tick op_stats so a scraper never needs /vars
        self._m_aoi_overflow = metrics.counter(
            "aoi_overflow_total",
            help="AOI rows truncated to nearest-k + cells past cell_cap",
        )
        self._m_aoi_demand = metrics.gauge("aoi_demand_max")
        self._m_aoi_cell = metrics.gauge("aoi_cell_max")
        # enter/leave pairs past enter_cap/leave_cap (and changed rows
        # past delta_rows_cap): the host's interest sets never learn
        # them, so every one is counted, not only warned about
        self._m_aoi_dropped = {
            kind: metrics.counter(
                "aoi_events_dropped_total",
                help="interest events the tick produced and the host "
                     "never decoded (past enter_cap / leave_cap / "
                     "delta_rows_cap)",
                kind=kind,
            ) for kind in ("enter", "leave", "rows")
        }
        self.aoi_dropped = dict.fromkeys(self._m_aoi_dropped, 0)
        # Verlet skin-reuse cadence (ops.aoi.grid_neighbors_verlet):
        # rebuild_total counts front-half rebuilds (== tick count when
        # the skin is off), skin_slack mirrors the headroom left before
        # the next displacement-triggered rebuild
        self._m_aoi_rebuild = metrics.counter(
            "aoi_rebuild_total",
            help="AOI front-half rebuilds (every tick when skin = 0)",
        )
        self._m_aoi_slack = metrics.gauge("aoi_skin_slack")

    # ==================================================================
    # registration / creation
    # ==================================================================
    def register_entity(self, name: str, cls, **kw):
        return self.registry.register(name, cls, **kw)

    def register_space(self, name: str, cls, **kw):
        if not issubclass(cls, Space):
            raise TypeError(f"{cls} must subclass Space")
        return self.registry.register(name, cls, is_space=True, **kw)

    def _attach(self, e: Entity, eid: str) -> None:
        e.id = eid
        e.world = self
        e.attrs = make_root(lambda d, _e=e: self._on_attr_delta(_e, d))
        self._setup_save_timer(e)

    def _gen_eid(self) -> str:
        """Auto-generated entity id. Multi-controller worlds draw from a
        DETERMINISTIC per-world sequence: SPMD-replicated host code (e.g.
        a replayed client RPC spawning an Avatar) must mint the SAME id
        on every controller or host/device state forks. Random
        time+machine+pid ids remain for single-controller worlds
        (reference ``uuid.go:27-60`` semantics)."""
        if not self._multihost:
            return ids.gen_entity_id()
        self._mh_eid_seq += 1
        return ids.gen_fixed_id(
            f"goworld_tpu.mh.{self.game_id}.{self._mh_eid_seq}"
        )

    def _setup_save_timer(self, e: Entity) -> None:
        """Schedule the periodic save for a persistent entity (reference
        ``setupSaveTimer``, ``Entity.go:214-217``). Fires regardless of a
        storage backend being configured yet — save_entity no-ops without
        one, and picks it up once attached."""
        if not e._type_desc.is_persistent or self.save_interval <= 0:
            return
        if e.id in self._save_timers:
            return
        self._save_timers[e.id] = self.timers.add(
            self.save_interval,
            lambda _e=e: None if _e.destroyed else self.save_entity(_e),
            interval=self.save_interval,
        )

    def create_nil_space(self) -> Space:
        """The per-game anchor space (reference ``space_ops.go:33-47``)."""
        if "NilSpace" not in self.registry:
            self.registry.register("NilSpace", Space, is_space=True,
                                   use_aoi=False)
        sp = Space()
        sp._type_desc = self.registry.get("NilSpace")
        self._attach(sp, ids.nil_space_id(self.game_id))
        sp.is_nil_space = True
        self.entities[sp.id] = sp
        if self.audit is not None:
            self.audit.ledger.on_create(sp.id, "NilSpace",
                                        self.tick_count)
        self.spaces[sp.id] = sp
        self.nil_space = sp
        if self.on_entity_created is not None:
            # nil-space ids are opaque hashes (ids.nil_space_id): without a
            # dispatcher route, cross-game enter_space targeting another
            # game's nil space could never resolve (the handshake census
            # covers nil spaces created before the cluster connects; this
            # covers ones created after, e.g. on restore)
            self.on_entity_created(sp)
        return sp

    def create_space(
        self, type_name: str, *, use_aoi: bool | None = None,
        attrs: dict | None = None, eid: str | None = None, **kw_attrs,
    ) -> Space:
        desc = self.registry.get(type_name)
        if not desc.is_space:
            raise TypeError(f"{type_name} is not a space type")
        if eid is not None and eid in self.entities:
            # same guard as create_entity: a replayed CreateSpaceAnywhere
            # must not silently replace a live space under its id
            raise ValueError(f"entity id collision: {eid}")
        sp: Space = desc.cls()
        sp._type_desc = desc
        # honor a caller-supplied id (CreateSpaceAnywhere pre-generates one
        # and routes by it — the space must be findable under that id,
        # goworld.go CreateSpaceAnywhere / space_ops.go)
        self._attach(sp, eid or self._gen_eid())
        aoi = desc.use_aoi if use_aoi is None else use_aoi
        if desc.megaspace:
            if self.mega is None:
                raise RuntimeError(
                    f"space type {type_name!r} declares megaspace=True but "
                    "the World was not built with megaspace=True"
                )
            if any(s is not None for s in self._shard_space):
                raise RuntimeError(
                    "megaspace claims every shard: destroy other AOI "
                    "spaces (or the previous megaspace) first"
                )
            for i in range(self.n_spaces):
                self._shard_space[i] = sp.id
            sp.is_mega = True
        elif aoi:
            if self.mega is not None:
                raise RuntimeError(
                    "a megaspace World hosts exactly one AOI space (the "
                    "megaspace); register the space type with "
                    "megaspace=True or use host-only spaces"
                )
            try:
                shard = self._shard_space.index(None)
            except ValueError:
                raise RuntimeError(
                    f"no free shard for AOI space ({self.n_spaces} in use); "
                    "raise n_spaces"
                ) from None
            self._shard_space[shard] = sp.id
            sp.shard = shard
        self.entities[sp.id] = sp
        self.spaces[sp.id] = sp
        if self.audit is not None:
            self.audit.ledger.on_create(sp.id, type_name,
                                        self.tick_count)
        # explicit attrs dict first (wire path — attr names there may
        # collide with parameter names), then kwarg sugar
        for k, v in {**(attrs or {}), **kw_attrs}.items():
            sp.attrs[k] = v
        sp.OnInit()
        sp.OnSpaceInit()
        sp.OnAttrsReady()
        sp.OnCreated()
        sp.OnSpaceCreated()
        if self.on_entity_created is not None:
            # spaces are entities: the dispatcher must learn the route so
            # MT_QUERY_SPACE_GAMEID_FOR_MIGRATE from other games resolves
            # (reference SpaceService/EnterSpace, DispatcherService.go:834)
            self.on_entity_created(sp)
        return sp

    def create_entity(
        self,
        type_name: str,
        *,
        space: Space | None = None,
        pos=(0.0, 0.0, 0.0),
        eid: str | None = None,
        client: GameClient | None = None,
        attrs: dict | None = None,
        moving: bool = False,
    ) -> Entity:
        """Reference ``createEntity`` (``EntityManager.go:201``)."""
        if space is not None and space.id in self._admission_paused:
            raise AdmissionPausedError(
                f"space {space.id} is draining a rebalance handoff; "
                f"admission paused")
        desc = self.registry.get(type_name)
        if desc.is_space:
            raise TypeError(f"use create_space for space type {type_name}")
        e: Entity = desc.cls()
        e._type_desc = desc
        new_id = eid or self._gen_eid()
        if new_id in self.entities:
            raise ValueError(f"entity id collision: {new_id}")
        self._attach(e, new_id)
        self.entities[e.id] = e
        if self.audit is not None:
            self.audit.ledger.on_create(e.id, type_name,
                                        self.tick_count)
        if attrs:
            load_into(e.attrs, attrs)
        e.OnInit()
        e.OnAttrsReady()
        space = space or self.nil_space
        if space is not None:
            self._enter_space_local(e, space, pos, moving=moving)
        if client is not None:
            self.set_entity_client(e, client)
        e.OnCreated()
        if self.on_entity_created is not None:
            self.on_entity_created(e)
        return e

    def load_entity(self, type_name: str, eid: str,
                    cb: Callable[[Entity | None], None] | None = None) -> None:
        """Async load from storage (reference ``loadEntityLocally``,
        ``EntityManager.go:307``). Requires a storage backend."""
        if self.storage is None:
            raise RuntimeError("no storage backend configured")
        if eid in self.entities:
            if cb:
                # .get at drain time: the entity may be destroyed between
                # this call and the post-queue drain
                self.post_q.post(lambda: cb(self.entities.get(eid)))
            return

        def _loaded(data: dict | None) -> None:
            if data is None:
                logger.warning("load_entity %s %s: not found", type_name, eid)
                if cb:
                    cb(None)
                return
            if eid in self.entities:  # raced a concurrent load/create
                if cb:
                    cb(self.entities[eid])
                return
            e = self.create_entity(type_name, eid=eid, attrs=data)
            e.OnRestored()
            if cb:
                cb(e)

        self.storage.load(type_name, eid, _loaded)

    # ==================================================================
    # slot management
    # ==================================================================
    def _alloc_slot(self, shard: int, eid: str) -> int:
        try:
            slot = self._free[shard].pop()
        except KeyError:
            raise RuntimeError(
                f"space shard {shard} is full ({self.cfg.capacity} slots)"
            ) from None
        self._slot_set(shard, slot, eid)
        return slot

    def _owner_entity(self, shard: int, slot: int) -> Entity | None:
        eid = self._slot_owner[shard].get(slot)
        return self.entities.get(eid) if eid is not None else None

    # -- slot/client numpy mirrors (all _slot_owner writes route here) --
    def _write_client_cols(self, shard: int, slot: int,
                           c: GameClient | None) -> None:
        if c is not None:
            self._mir_cid[shard, slot] = c.client_id.encode("ascii")
            self._mir_gate[shard, slot] = c.gate_id
        else:
            self._mir_cid[shard, slot] = b""
            self._mir_gate[shard, slot] = -1
        # every slot/client mirror write funnels through here (_slot_set,
        # _slot_clear, _mirror_client): the eid->(shard,slot) intern
        # index over these columns is now stale
        self._sync_index = None

    def _slot_set(self, shard: int, slot: int, eid: str) -> None:
        self._slot_owner[shard][slot] = eid
        self._mir_eid[shard, slot] = eid.encode("ascii")
        e = self.entities.get(eid)
        self._write_client_cols(shard, slot,
                                e.client if e is not None else None)

    def _slot_clear(self, shard: int, slot: int) -> None:
        self._slot_owner[shard].pop(slot, None)
        self._slot_abandon(shard, slot)

    def _slot_abandon(self, shard: int, slot: int) -> None:
        """The owner has left this row and its despawn (or its move) is
        staged. The owner mapping stays for the row's leave events; the
        mirror columns go now, so that a decode which runs before that
        flush (the serve loop handles packets while the device
        computes) fans out no sync record to the row or about it."""
        self._mir_eid[shard, slot] = b""
        self._write_client_cols(shard, slot, None)

    def _mirror_client(self, e: Entity) -> None:
        """Refresh the client columns for an entity's current slot (call
        after any (re)bind/unbind; no-op for slotless or stale rows)."""
        if e.shard is None or e.slot is None:
            return
        if self._slot_owner[e.shard].get(e.slot) != e.id:
            return
        self._write_client_cols(e.shard, e.slot, e.client)

    def _drop_staged_for(self, shard: int, slot: int) -> None:
        """Forget pending writes aimed at a row being despawned."""
        self._staged_hot = [
            x for x in self._staged_hot if (x[0], x[1]) != (shard, slot)
        ]
        self._staged_moving = [
            x for x in self._staged_moving if (x[0], x[1]) != (shard, slot)
        ]
        self._staged_client = [
            x for x in self._staged_client if (x[0], x[1]) != (shard, slot)
        ]
        self._staged_pos.pop((shard, slot), None)
        if self._batch_pos_mask is not None:
            self._batch_pos_mask[shard, slot] = False

    # ==================================================================
    # space enter / leave / migration
    # ==================================================================
    def enter_space(self, e: Entity, space_id: str, pos) -> None:
        """Reference ``EnterSpace`` (``Entity.go:956-973``): local fast
        path, or a staged device migration when both spaces are AOI shards
        (replacing the dispatcher block-and-queue protocol,
        ``DispatcherService.go:850-891``)."""
        target = self.spaces.get(space_id)
        if target is None:
            if self.remote_space_router is not None:
                # the space lives on another game process: hand off to the
                # cross-process migration protocol (SURVEY.md#3.5)
                self.remote_space_router(e, space_id, tuple(map(float, pos)))
                return
            raise KeyError(f"space {space_id} not found in this world")
        if e.space is target:
            e.set_position(pos)
            return
        src = e.space
        if (
            src is not None and e.shard is not None
            and target.shard is not None and e.slot is not None
        ):
            e.OnMigrateOut()
            self._staged_migrate.append(
                (e.shard, e.slot, target.shard, e.id)
            )
            self._drop_staged_for(e.shard, e.slot)
            self._slot_abandon(e.shard, e.slot)
            src.members.discard(e.id)
            e.OnLeaveSpace(src)
            src.OnEntityLeaveSpace(e)
            # during the migration window the entity has NO device row it
            # may address: slot ownership of the source row is kept (for
            # its leave events) in _staged_migrate/_migrate_tags, and
            # e.slot is re-pointed from the arrival records
            e._migrating = (e.shard, e.slot, target.shard)
            e.slot = None
            e.shard = None
            e.space = target
            target.members.add(e.id)
            e._pending_pos = tuple(map(float, pos))
        else:
            self.post_q.post(
                lambda: self._move_space_host(e, target, pos)
            )

    def _move_space_host(self, e: Entity, target: Space, pos) -> None:
        if e.destroyed:
            return
        self._leave_space_host(e)
        self._enter_space_local(e, target, pos)

    def _leave_space_host(self, e: Entity) -> None:
        src = e.space
        if src is None:
            self._cancel_migration(e)
            return
        src.members.discard(e.id)
        if e.slot is not None:
            self._drop_staged_for(e.shard, e.slot)
            self._staged_despawn.append((e.shard, e.slot))
            self._slot_abandon(e.shard, e.slot)
            e.slot = None
            e.shard = None
        self._cancel_migration(e)
        e.space = None
        e.OnLeaveSpace(src)
        src.OnEntityLeaveSpace(e)

    def _cancel_migration(self, e: Entity) -> None:
        """Abort an in-window migration (reference ``cancelEnterSpace``,
        ``Entity.go:1014-1023``): despawn the still-live source row.

        Only valid while the request is still staged host-side; once the
        row is in flight on device (``_migrate_tags``), the source row has
        already departed in-step and ``_process_arrivals`` reconciles via
        ``e.destroyed`` instead."""
        mig = getattr(e, "_migrating", None)
        if mig is None:
            return
        if not any(m[3] == e.id for m in self._staged_migrate):
            return  # in flight on device; arrivals reconciliation owns it
        src_sh, src_sl, _dst = mig
        e._migrating = None
        self._staged_migrate = [
            m for m in self._staged_migrate if m[3] != e.id
        ]
        self._staged_despawn.append((src_sh, src_sl))

    def _tile_of(self, pos) -> int:
        """Owning tile (= shard) of a world position in megaspace mode
        (1D x-strips or 2D XZ tiles; MegaConfig.tile_of)."""
        return self.mega.tile_of(float(pos[0]), float(pos[2]))

    def _enter_space_or_park(
        self, e: Entity, space: Space, pos, moving: bool = False
    ) -> bool:
        """Enter ``space``; if its shard has no free slot, park the
        entity in the nil space instead of crashing the world loop.
        Capacity is checked up front — catching _alloc_slot's error
        after the fact would have to unwind membership and user hooks
        that already ran. Returns True on a real entry."""
        if space.is_mega:
            shard = self._tile_of(pos)
        else:
            shard = space.shard
        if shard is not None and not self._free[shard]:
            logger.error(
                "respawn of %s failed (%s full); parked in nil space",
                e.id, space.id,
            )
            if self.nil_space is not None:
                self._enter_space_local(e, self.nil_space, pos)
            return False
        self._enter_space_local(e, space, pos, moving=moving)
        return True

    def _enter_space_local(
        self, e: Entity, space: Space, pos, moving: bool = False,
        yaw: float = 0.0,
    ) -> None:
        e.space = space
        space.members.add(e.id)
        if space.is_mega:
            shard = self._tile_of(pos)
        else:
            shard = space.shard
        if shard is not None:
            slot = self._alloc_slot(shard, e.id)
            e.slot = slot
            e.shard = shard
            hot = [0.0] * self.cfg.attr_width
            for name, col in e._type_desc.hot_attrs.items():
                v = e.attrs.get(name)
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    hot[col] = float(v)
            self._staged_spawn.append((shard, slot, dict(
                pos=tuple(map(float, pos)),
                yaw=float(yaw),
                type_id=e._type_desc.type_id,
                npc_moving=moving,
                has_client=e.client is not None,
                client_gate=e.client.gate_id if e.client else -1,
                hot=hot,
                aoi_radius=_type_aoi_radius(e._type_desc),
            )))
        e._pending_pos = tuple(map(float, pos))
        e.OnEnterSpace()
        space.OnEntityEnterSpace(e)

    def destroy_entity(self, e: Entity) -> None:
        """Reference ``destroyEntity`` (``Entity.go:631-651``)."""
        if e.destroyed:
            return
        e.destroyed = True
        if self.audit is not None:
            # the ledger tracks LIVE entities; the host object may
            # linger in self.entities until its leave events drain
            self.audit.ledger.on_destroy(e.id, self.tick_count)
        try:
            e.OnDestroy()
        except Exception:
            logger.exception("OnDestroy failed for %s", e)
        if e._type_desc.is_persistent and self.storage is not None:
            self.save_entity(e)
        if e.client is not None:
            self.set_entity_client(e, None)
        for tid in list(e.timer_ids):
            self.timers.cancel(tid)
        e.timer_ids.clear()
        save_tid = self._save_timers.pop(e.id, None)
        if save_tid is not None:
            self.timers.cancel(save_tid)
        if isinstance(e, Space):
            # evict members into the nil space (despawns their rows) so a
            # new space claiming this shard never sees ghost entities
            for mid in list(e.members):
                m = self.entities.get(mid)
                if m is None or m is e:
                    continue
                if self.nil_space is not None:
                    self._move_space_host(m, self.nil_space, m.position)
                else:
                    self._leave_space_host(m)
            if e.is_mega:
                self._shard_space = [
                    None if s == e.id else s for s in self._shard_space
                ]
            elif e.shard is not None:
                self._shard_space[e.shard] = None
            e.OnSpaceDestroy()
            self.spaces.pop(e.id, None)
        had_slot = e.slot is not None
        self._leave_space_host(e)
        if not had_slot and e._migrating is None:
            # never on device (and no row in flight): nothing will
            # reference it again
            self.entities.pop(e.id, None)
        # else: the host object stays mapped until the leave events
        # referencing its slot have been processed (_process_outputs), or
        # until _process_arrivals drops its in-flight row (destroyed
        # mid-migration)
        #
        # Break the entity's reference cycles (e -> attrs ->
        # _root_cb-closure -> e, and every attr child's parent
        # pointer): with the logic loop's default gc.freeze-on-boot
        # (net/game.py), boot-time entities live in the GC's permanent
        # generation and ONLY plain refcounting can reclaim them — a
        # destroyed entity left cyclic would leak for the process
        # lifetime. Post-destroy attr mutations no longer journal,
        # which is correct: the entity is gone to every client.
        if e.attrs is not None:
            sever_tree(e.attrs)
        if self.on_entity_destroyed is not None:
            self.on_entity_destroyed(e)

    # ==================================================================
    # staging entry points (called by Entity)
    # ==================================================================
    def stage_pos_set(self, e: Entity) -> None:
        if e.slot is not None and e.shard is not None:
            self._staged_pos[(e.shard, e.slot)] = e

    def stage_pose(self, e: Entity, pos, yaw: float,
                   moving: bool | None = None) -> None:
        """Overwrite an entity's authoritative pose from a snapshot or
        replication record and stage the device-row write (the restore
        / standby-apply path; flushed with the vectorized pos-set
        scatter on the next tick). ``moving=None`` leaves the moving
        flag unstaged — callers pass it only on change, because
        ``_staged_moving`` is an append-only per-tick list and a
        standby applies many frames between ticks."""
        e._pending_pos = tuple(map(float, pos))
        e._pending_yaw = float(yaw)
        self.stage_pos_set(e)
        if moving is not None:
            self.set_moving(e, bool(moving))

    def _sync_pos_index(self) -> tuple:
        """eid -> (shard, slot) intern index over client-bound live
        slots, rebuilt lazily after any client (re)bind/unbind or slot
        change (all of which funnel through ``_write_client_cols``).
        Built/probed via :func:`ids.build_eid_index` (u64 hash keys with
        byte-exact verification, raw-S16 fallback on collision). The
        rebuild is a vectorized argsort over the mirror columns — no
        per-entity Python even at 1M rows (a few ms, paid only on ticks
        with client churn)."""
        if self._sync_index is None:
            sh, sl = np.nonzero(self._mir_gate >= 0)
            hashed, keys, sorted_eids, order = ids.build_eid_index(
                self._mir_eid[sh, sl]
            )
            self._sync_index = (
                hashed,
                keys,
                sorted_eids,
                sh[order].astype(np.int32),
                sl[order].astype(np.int32),
            )
        return self._sync_index

    def stage_pos_sync_batch(self, eids, vals) -> int:
        """Stage a decoded upstream sync batch (S16 eids[N], f32[N,4]
        x/y/z/yaw) without touching per-entity Python objects: one
        searchsorted against the intern index resolves every record to
        its (shard, slot); records for unknown, client-less or slotless
        entities are dropped (the reference's ``e == nil || e.client ==
        nil`` skip, ``GameService.go:395-407`` — a record aimed at an
        entity mid-migration is likewise dropped; the client re-syncs
        within 100 ms). Last write wins per slot, both within a batch
        and across batches in the same tick. Host reads
        (``Entity.position``/``yaw``) see staged values immediately via
        ``_peek_batch_pos``; host-side ``set_position`` writes staged
        the same tick take precedence at flush. Returns #staged."""
        hashed, keys, sorted_eids, ish, isl = self._sync_pos_index()
        eids = np.ascontiguousarray(np.asarray(eids, "S16"))
        if eids.shape[0] == 0 or keys.size == 0:
            return 0
        p, ok = ids.probe_eid_index(hashed, keys, sorted_eids, eids)
        if not ok.any():
            return 0
        sh = ish[p[ok]]
        sl = isl[p[ok]]
        v = np.asarray(vals, np.float32).reshape(-1, 4)[ok]
        if self._batch_pos_mask is None:
            self._batch_pos_mask = np.zeros(
                (self.n_spaces, self.cfg.capacity), bool
            )
            self._batch_pos_vals = np.zeros(
                (self.n_spaces, self.cfg.capacity, 4), np.float32
            )
        # in-batch duplicates: keep the LAST record per slot (wire
        # arrival order), selected via unique on the reversed keys
        lin = sh.astype(np.int64) * self.cfg.capacity + sl
        _, first_of_rev = np.unique(lin[::-1], return_index=True)
        sel = lin.size - 1 - first_of_rev
        self._batch_pos_mask[sh[sel], sl[sel]] = True
        self._batch_pos_vals[sh[sel], sl[sel]] = v[sel]
        self._batch_pos_any = True
        return int(sel.size)

    def _peek_batch_pos(self, shard: int, slot: int):
        """Staged-but-unflushed client sync for a slot (or None)."""
        if self._batch_pos_any and self._batch_pos_mask is not None \
                and self._batch_pos_mask[shard, slot]:
            return self._batch_pos_vals[shard, slot]
        return None

    def set_moving(self, e: Entity, moving: bool) -> None:
        if e.slot is not None and e.shard is not None:
            self._staged_moving.append((e.shard, e.slot, moving))

    def stage_hot(self, e: Entity, col: int, val: float) -> None:
        if e.slot is not None and e.shard is not None:
            self._staged_hot.append((e.shard, e.slot, col, val))

    def set_entity_client(self, e: Entity, client: GameClient | None) -> None:
        """Reference ``SetClient`` (``Entity.go:678-720``): bind/unbind and
        send the client its own entity + currently visible neighbors
        (``GameClient.go:37-53``: player gets Client attrs, neighbors get
        AllClients attrs)."""
        old = e.client
        e.client = client
        if client is not None:
            client.owner = e  # multihost send-dedup needs the backref
        self._mirror_client(e)
        if e.slot is not None and e.shard is not None:
            self._staged_client.append((
                e.shard, e.slot,
                client is not None,
                client.gate_id if client is not None else -1,
            ))
        if old is not None and client is None:
            old.send({"type": "destroy_entity", "eid": e.id,
                      "is_player": True})
            e.OnClientDisconnected()
        elif client is not None:
            client.send({
                "type": "create_entity", "eid": e.id,
                "etype": e.type_name, "is_player": True,
                "attrs": e.get_client_data(),
                "pos": list(e.position), "yaw": e.yaw,
            })
            for nid in e.interested_in:
                n = self.entities.get(nid)
                if n is not None:
                    client.send({
                        "type": "create_entity", "eid": n.id,
                        "etype": n.type_name, "is_player": False,
                        "attrs": n.get_all_clients_data(),
                        "pos": list(n.position), "yaw": n.yaw,
                    })
            e.OnClientConnected()

    # ==================================================================
    # attr deltas
    # ==================================================================
    def _on_attr_delta(self, e: Entity, d: AttrDelta) -> None:
        self._dirty_attr_entities.setdefault(e.id, []).append(d)
        root_key = d.path[0] if d.path else None
        col = e._type_desc.hot_attrs.get(root_key)
        if col is not None and isinstance(d.value, (int, float)) \
                and not isinstance(d.value, bool):
            self.stage_hot(e, col, float(d.value))

    def _journal_wanted(self, e: Entity, aud: str | None) -> bool:
        """Whether a device-attr delta has any recipient: the drain
        fans out to the own client ("client" audience) and/or watching
        clients ("all_clients"); journaling anything else is per-record
        work thrown away at drain (the dominant host cost at
        attr_sync_cap volume — tools/probe_fanout.py)."""
        return aud is not None and (
            e.client is not None
            or (aud == "all_clients" and bool(e.interested_by))
        )

    def _apply_device_attr(self, e: Entity, name: str, v: float,
                           aud: str | None) -> None:
        """Write a kernel-mutated hot attr into the host tree WITHOUT
        echoing it back to the device (it already holds the value),
        journaling the change for client fan-out when ``aud`` (the
        attr's audience, see ``_journal_wanted``) gives it a recipient.

        Runs per record at attr_sync_cap volumes on the per-tick host
        path (profiled: the full MapAttr.set machinery was ~45% of the
        attr decode at cap volume), so plain-scalar overwrites — the
        only shape a hot attr ever has — take a direct dict write:
        orphan/adopt are no-ops for non-node values and the suppressed
        root callback means set() would emit nothing anyway."""
        attrs = e.attrs
        old = attrs._d.get(name)
        if isinstance(old, (MapAttr, ListAttr)):
            cb = attrs._root_cb
            attrs._root_cb = None
            try:
                attrs[name] = v
            finally:
                attrs._root_cb = cb
        else:
            attrs._d[name] = v
        if self._journal_wanted(e, aud):
            self._dirty_attr_entities.setdefault(e.id, []).append(
                AttrDelta((name,), "set", v)
            )

    def _drain_attr_journals(self) -> None:
        for eid, deltas in self._dirty_attr_entities.items():
            e = self.entities.get(eid)
            if e is None or e.destroyed:
                continue
            has_own = e.client is not None
            has_watchers = bool(e.interested_by)
            if not has_own and not has_watchers:
                # nobody to tell — don't build recs that are dropped
                # (this loop runs at attr_sync_cap volumes per tick)
                continue
            desc = e._type_desc
            own: list = []
            others: list = []
            for d in deltas:
                aud = desc.audience_of(d.path[0]) if d.path else None
                if aud is None:
                    continue
                rec = {"path": list(d.path), "op": d.op, "value": d.value}
                if aud == "all_clients":
                    own.append(rec)
                    others.append(rec)
                else:
                    own.append(rec)
            if own and has_own:
                e.client.send({"type": "attrs", "eid": eid, "deltas": own})
            if others and has_watchers:
                for wid in e.interested_by:
                    w = self.entities.get(wid)
                    if w is not None and w.client is not None:
                        w.client.send(
                            {"type": "attrs", "eid": eid, "deltas": others}
                        )
        self._dirty_attr_entities.clear()

    # ==================================================================
    # RPC
    # ==================================================================
    def call(self, eid: str, method: str, *args,
             from_client: str | None = None) -> None:
        """Reference ``entity.Call`` (``EntityManager.go:399-412``):
        local-optimized post, else the remote router (the dispatcher-hop
        analog, provided by the deployment layer)."""
        e = self.entities.get(eid)
        if e is not None and consts.OPTIMIZE_LOCAL_ENTITY_CALL:
            self.post_q.post(
                lambda: self._invoke(e, method, args, from_client)
            )
        elif self.remote_router is not None:
            self.remote_router(eid, method, args, from_client)
        elif e is not None:  # local, but forced through the routed path
            self.post_q.post(
                lambda: self._invoke(e, method, args, from_client)
            )
        else:
            logger.warning("call %s.%s: entity not found", eid, method)

    def _invoke(self, e: Entity, method: str, args: tuple,
                from_client: str | None) -> None:
        if tracing.active:
            ctx = tracing.current()
            if ctx is not None and ctx.sampled:
                # traced RPC: the method execution gets its own span
                # under the transport handle span, so the merged trace
                # separates routing time from entity-logic time
                with tracing.hop("invoke", f"game{self.game_id}", ctx,
                                 method=method, eid=e.id):
                    return self._invoke_body(e, method, args,
                                             from_client)
        return self._invoke_body(e, method, args, from_client)

    def _invoke_body(self, e: Entity, method: str, args: tuple,
                     from_client: str | None) -> None:
        if e.destroyed:
            return
        desc = e._type_desc.rpc_descs.get(method)
        if desc is None:
            logger.warning("%s has no RPC method %s", e, method)
            return
        if from_client is not None:
            own = e.client is not None and e.client.client_id == from_client
            need = RF_OWN_CLIENT if own else RF_OTHER_CLIENT
            if not desc.flags & need:
                logger.warning(
                    "client %s not allowed to call %s.%s",
                    from_client, e, method,
                )
                return
        try:
            getattr(e, method)(*args)
        except Exception:
            logger.exception("RPC %s.%s failed", e, method)

    def call_service(self, name: str, method: str, *args,
                     shard_key: str | None = None,
                     shard_index: int | None = None,
                     all_shards: bool = False) -> None:
        """CallServiceAny/ShardKey/ShardIndex/All (goworld.go:157-172)."""
        if self.service_mgr is None:
            raise RuntimeError("service manager not configured")
        if all_shards:
            self.service_mgr.call_all(name, method, *args)
            return
        self.service_mgr.call(name, method, args, shard_key=shard_key,
                              shard_index=shard_index)

    def call_filtered_clients(self, key, op, val, method, args) -> None:
        if self.filtered_sink is None:
            logger.warning("call_filtered_clients: no gateway attached")
            return
        self.filtered_sink(key, op, val, method, args)

    # ==================================================================
    # timers
    # ==================================================================
    def add_entity_timer(self, e: Entity, delay: float, interval: float,
                         cb_or_method, args: tuple) -> int:
        if isinstance(cb_or_method, str):
            # method-name timers are migration/freeze-safe (Entity.go:271)
            return self.timers.add(
                delay, interval=interval, method=cb_or_method,
                args=(e.id,) + args,
            )
        box: dict[str, int] = {}

        def _cb() -> None:
            if interval <= 0:  # one-shot: forget the tid (no leak)
                e.timer_ids.discard(box.get("tid", -1))
            if not e.destroyed:
                cb_or_method(*args)

        box["tid"] = tid = self.timers.add(
            delay, interval=interval, cb=_cb
        )
        return tid

    def _fire_timer(self, t) -> None:
        if t.method is not None:
            eid = t.args[0]
            e = self.entities.get(eid)
            if e is None or e.destroyed:
                return
            if t.interval <= 0:
                e.timer_ids.discard(t.tid)
            fn = getattr(e, t.method, None)
            if fn is None:
                logger.warning("timer method %s missing on %s", t.method, e)
                return
            fn(*t.args[1:])
        elif t.cb is not None:
            t.cb()

    # ==================================================================
    # client message sink
    # ==================================================================
    def client_emit_ok(self, e: Entity | None) -> bool:
        """Multi-controller send dedup: SPMD host logic (attr journals,
        call_client, bind-time create_entity) runs on EVERY controller, so
        exactly one may emit each client-bound message. Rule: the
        controller owning the entity's shard emits; slotless entities
        (nil-space boot entities, mid-migration rows) belong to the
        leader. Single-controller worlds always emit. The owner-local
        event decode in :meth:`_process_outputs` satisfies this rule by
        construction (a watcher's events decode on its shard's owner)."""
        if not self._multihost:
            return True
        if e is None or e.shard is None:
            return self.mh_rank == 0
        return e.shard in self.local_shards

    def send_to_client(self, gate_id: int, client_id: str, msg: dict) -> None:
        if self.client_sink is not None:
            self.client_sink(gate_id, client_id, msg)
        else:
            self.client_messages.append((gate_id, client_id, msg))

    # ==================================================================
    # cross-process migration (reference Entity.go:1060-1115,
    # EntityManager.go:246-305 — GetMigrateData / restoreEntity)
    # ==================================================================
    def get_migrate_data(self, e: Entity) -> dict:
        """Everything needed to recreate the entity on another game: all
        attrs, client binding, pos/yaw, migration-safe timers — plus the
        audit ownership seq (ISSUE 17) the target's ledger validates
        against re-delivered or stale ghosts. ``remove_for_migration``
        commits the matching ledger move; the seqs agree because the
        two calls run back-to-back on the logic thread."""
        data = {
            "type": e.type_name,
            "id": e.id,
            "attrs": e.attrs.to_dict(),
            "client": (
                [e.client.gate_id, e.client.client_id]
                if e.client is not None else None
            ),
            "pos": list(e.position),
            "yaw": e.yaw,
            "timers": self.timers.dump(list(e.timer_ids)),
        }
        if self.audit is not None:
            data["own_seq"] = self.audit.ledger.next_seq(e.id)
        return data

    def pause_admission(self, space_id: str, paused: bool = True
                        ) -> None:
        """Pause (or resume) NEW-entity admission into a space — the
        rebalance handoff's mid-move guard. ``create_entity`` into a
        paused space raises :class:`AdmissionPausedError`; existing
        entities and migration restores are unaffected (an abort must
        be able to put the cohort back)."""
        if paused:
            self._admission_paused.add(space_id)
        else:
            self._admission_paused.discard(space_id)

    def admission_allowed(self, space_id: str) -> bool:
        return space_id not in self._admission_paused

    def remove_for_migration(self, e: Entity, target: int = 0,
                             out_tick: int | None = None) -> None:
        """Tear down the local copy WITHOUT destroy semantics — no
        OnDestroy, no persistence, no client destroy message (the client
        binding travels in the migrate data; reference
        ``destroyEntity(isMigrate=true)``, ``Entity.go:631-651``).

        ``target`` names the destination game in the ledger's
        in-flight record; ``out_tick`` lets a batched handoff stamp
        each entity at its OWN send tick (default: the current tick) —
        the per-record anchor the burst-aware conservation verdict
        ages from (ISSUE 19)."""
        if self.audit is not None:
            # ledger move-out: opens an in-flight record the target's
            # migrate-in must retire within the conservation grace
            self.audit.ledger.stamp_migrate_out(
                e.id,
                self.tick_count if out_tick is None else int(out_tick),
                target=int(target))
        e.OnMigrateOut()
        for tid in list(e.timer_ids):
            self.timers.cancel(tid)
        e.timer_ids.clear()
        save_tid = self._save_timers.pop(e.id, None)
        if save_tid is not None:
            self.timers.cancel(save_tid)  # target game schedules its own
        e.client = None  # quiet detach; the data carries the binding
        self._mirror_client(e)
        e.destroyed = True
        self._leave_space_host(e)
        if e.slot is None and e._migrating is None:
            self.entities.pop(e.id, None)

    def restore_from_migration(self, data: dict,
                               space: Space | None = None) -> Entity:
        """Recreate a migrated-in entity: rebuild attrs, quietly re-assign
        the client, enter the target space, restore timers, OnMigrateIn."""
        desc = self.registry.get(data["type"])
        e: Entity = desc.cls()
        e._type_desc = desc
        self._attach(e, data["id"])
        self.entities[e.id] = e
        if self.audit is not None:
            self.audit.ledger.on_migrate_in(
                e.id, data["type"], data.get("own_seq", 0),
                self.tick_count)
        load_into(e.attrs, data["attrs"])
        if data.get("client"):
            # direct assignment = the reference's "re-assign client
            # quietly" (no create_entity resend; the client already has
            # the entity)
            e.client = GameClient(
                data["client"][0], data["client"][1], self, owner=e
            )
        sp = space or self.nil_space
        if sp is not None:
            self._enter_space_local(e, sp, tuple(data["pos"]))
        e._pending_yaw = float(data.get("yaw", 0.0))
        self.stage_pos_set(e)
        for tid in self.timers.restore(data.get("timers", [])):
            e.timer_ids.add(tid)
        e.OnMigrateIn()
        if self.on_entity_created is not None:
            self.on_entity_created(e)
        return e

    # ==================================================================
    # persistence
    # ==================================================================
    def save_entity(self, e: Entity) -> None:
        if self.storage is None or not e._type_desc.is_persistent:
            return
        self.storage.save(e.type_name, e.id, e.get_persistent_data())

    # ==================================================================
    # live device telemetry (ISSUE 11)
    # ==================================================================
    # cadence constants (ticks): how often the drained lanes feed the
    # metrics registry, and how often the signature window rotates (the
    # signature reads the delta since the last rotation, so it always
    # covers the most recent 1-2 windows, never process-lifetime
    # averages)
    TELEM_FEED_TICKS = 32
    SIG_WINDOW_TICKS = 256

    def _init_live_telemetry(self) -> None:
        from goworld_tpu.ops import telemetry as telem

        cfg = self.cfg
        mega = self.mega is not None
        # the skin lane exists only where the Verlet cache is LIVE in
        # the compiled step (state carries a cache and capacity is
        # inside the packed-id bound — the tick_body use_verlet
        # predicate; the vmapped S>1 and megaspace shapes cleared it)
        skin_on = (not mega and cfg.grid.skin > 0
                   and getattr(self.state, "aoi_cache", None) is not None
                   and cfg.capacity < (1 << consts.AOI_ID_BITS))
        self._telem_mega = mega
        self._telem_skin_on = skin_on
        self._telem_half_skin = cfg.grid.skin / 2.0 if skin_on else 0.0
        self._telem_acc = telem.telemetry_init(
            skin_on, mega=mega, occupancy=True, n_tiles=self.n_spaces)
        half_skin = self._telem_half_skin

        def _fold(acc, outs):
            return telem.telemetry_update_live(
                acc, outs, mega=mega, half_skin=half_skin)

        # resident worlds donate the accumulator carry too — EXCEPT
        # under pipeline_decode, where the fold of tick N consumes
        # acc_{N-1} while _pending_telem still owes that same buffer to
        # the next tick's host fetch (donating would delete it mid-
        # flight)
        fold_dn = (0,) if (self.resident and not self.pipeline_decode) \
            else ()
        self._telem_fn = jax.jit(_fold, donate_argnums=fold_dn)

    def _ingest_telemetry(self, acc_host) -> None:
        """Host half of the live lanes (called with the accumulator
        copy that rode the tick's fetch-outputs transfer): keep the
        cumulative drain, feed the metrics registry and rotate the
        signature window on their cadences."""
        from goworld_tpu.ops import telemetry as telem

        lanes = telem.telemetry_drain(
            acc_host, self._telem_skin_on, self._telem_half_skin,
            mega=self._telem_mega)
        self._telem_lanes = lanes
        if self.tick_count - self._telem_feed_tick \
                >= self.TELEM_FEED_TICKS:
            self._feed_telemetry_metrics(lanes)
            self._telem_feed_tick = self.tick_count
        if self.tick_count - self._telem_win_tick \
                >= self.SIG_WINDOW_TICKS:
            # stash the just-COMPLETED window's delta before rotating:
            # the governor judges whole windows (reading the running
            # delta right after a rotation would see ~1 tick of
            # samples); the live /workload endpoint keeps serving the
            # running delta below
            self._telem_last_window = telem.lanes_delta(
                lanes, self._telem_win)
            self._telem_win = lanes
            self._telem_win_tick = self.tick_count

    def _feed_telemetry_metrics(self, lanes: dict) -> None:
        """Drained lanes -> metrics registry: one shared-ladder
        histogram per lane (`telemetry_<lane>`; increment = the delta
        since the last feed) plus per-tile occupancy gauges. The
        tick_ms lane is skipped — the live wall latency already has
        its own tick_latency_ms series."""
        from goworld_tpu.ops import telemetry as telem

        delta = telem.lanes_delta(lanes, self._telem_feed_mark)
        for nm, lane in delta.items():
            if nm == "tick_ms" or not isinstance(lane, dict) \
                    or "counts" not in lane:
                continue
            metrics.histogram(
                f"telemetry_{nm}", buckets=tuple(lane["edges"]),
            ).add_counts(lane["counts"])
        per_tile = (lanes.get("occupancy") or {}).get("per_tile")
        if per_tile is not None:
            for i, c in enumerate(per_tile):
                metrics.gauge("telemetry_tile_occupancy",
                              tile=str(i)).set(c)
        self._telem_feed_mark = lanes

    def workload_signature(self) -> dict | None:
        """The live workload signature over the recent window (the
        jax-free reducer in ops/telemetry.py applied to the drained-
        lane delta since the last window rotation), stamped with the
        resolved kernel-config key. None until the first tick has
        drained (or when telemetry_live is off)."""
        if self._telem_lanes is None:
            return None
        from goworld_tpu.ops import telemetry as telem
        from goworld_tpu.utils import devprof

        delta = telem.lanes_delta(self._telem_lanes, self._telem_win)
        sig = telem.workload_signature(
            delta, config=devprof.grid_config_key(self.cfg.grid))
        sig["game_id"] = self.game_id
        sig["tick"] = self.tick_count
        sig["window_ticks"] = self.tick_count - self._telem_win_tick
        return sig

    def window_signature(self) -> dict | None:
        """The signature of the last COMPLETED rotation window (the
        governor's decision input — a whole window every time, never
        the thin running delta right after a rotation). None until the
        first window has rotated."""
        if self._telem_last_window is None:
            return None
        from goworld_tpu.ops import telemetry as telem
        from goworld_tpu.utils import devprof

        sig = telem.workload_signature(
            self._telem_last_window,
            config=devprof.grid_config_key(self.cfg.grid))
        sig["game_id"] = self.game_id
        sig["tick"] = self.tick_count
        sig["window_ticks"] = self.SIG_WINDOW_TICKS
        return sig

    # ==================================================================
    # live tick-config swap (autotune governor, ROADMAP item 2)
    # ==================================================================
    def apply_tick_config(self, cfg2, step, *, telem_fold=None,
                          telem_acc0=None, telem_skin_on: bool = False,
                          telem_half_skin: float = 0.0) -> None:
        """Swap the resolved tick config BETWEEN ticks — the autotune
        governor's commit path (goworld_tpu/autotune). ``step`` is the
        candidate's pre-compiled executable (warmset AOT product; the
        tick signature has fixed shapes, so the compiled object serves
        every subsequent tick with zero retraces), ``cfg2`` its
        resolved WorldConfig. State carries over bit-identically except
        the Verlet cache, which is dropped/reallocated-invalid when the
        skin (or any cache-shaping knob) flips — the next tick rebuilds
        the front half, so the swap is exact from its first tick
        (oracle-asserted in tests/test_governor.py).

        The live telemetry lanes follow the new config's lane set: a
        pre-warmed fold executable + zeroed accumulator swap in when
        provided (the warmset compiles them next to the step), else the
        lanes re-initialize; either way the signature window restarts —
        a window must never straddle two configs."""
        if self.mega is not None or self.mesh is not None \
                or self.n_spaces != 1:
            raise ValueError(
                "apply_tick_config serves single-shard non-mesh worlds"
            )
        from goworld_tpu.autotune.warmset import carry_state

        # a pipelined decode holding last tick's outputs/acc must drain
        # first: their pytree structure belongs to the OLD config
        self.flush_pending_outputs()
        self._pending_telem = None
        self.state = carry_state(self.state, self.cfg, cfg2,
                                 stacked=True)
        self.cfg = cfg2
        self._step = step
        if self._telem_fn is not None or telem_fold is not None:
            if telem_fold is not None and telem_acc0 is not None:
                self._telem_fn = telem_fold
                self._telem_acc = telem_acc0
                self._telem_skin_on = bool(telem_skin_on)
                self._telem_half_skin = float(telem_half_skin)
                self._telem_mega = False
            elif self.telemetry_live:
                try:
                    self._init_live_telemetry()
                except Exception:
                    logger.exception(
                        "live telemetry re-init failed on swap; "
                        "disabled")
                    self._telem_fn = self._telem_acc = None
            # fresh window: drained lanes/marks of the old lane set
            # must never delta against the new accumulator
            self._telem_lanes = None
            self._telem_win = None
            self._telem_win_tick = self.tick_count
            self._telem_last_window = None
            self._telem_feed_mark = None

    # ==================================================================
    # the tick
    # ==================================================================
    def cost_report(self):
        """XLA cost/memory analysis of this World's compiled step — the
        live-process devprof provider (``/costs?analyze=1``). Lowers the
        step at the current state/empty-inputs shapes (mesh + megaspace
        steps take MultiTickInputs — make_mega_tick matches
        make_multi_tick's signature); analysis errors are folded into
        the report, never raised (devprof contract)."""
        from goworld_tpu.utils import devprof

        if self.mesh is not None:
            from goworld_tpu.parallel.step import MultiTickInputs

            inputs = MultiTickInputs.empty(self.cfg, self.n_spaces)
        else:
            inputs = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x, (self.n_spaces,) + x.shape),
                TickInputs.empty(self.cfg),
            )
        return devprof.cost_report(
            self._step, self.state, inputs, self.policy,
            name="world.tick",
            config=devprof.grid_config_key(self.cfg.grid),
            n=self.cfg.capacity * self.n_spaces,
        )

    def tick(self) -> None:
        """One tick with a blocking fetch: a standalone World (tests,
        ``chip_smoke.py``, embedded loops) has no queue to serve while
        the device computes. The GameServer calls the two halves itself
        and pumps its queue between them."""
        with self.tick_record():
            self.tick_land(self.tick_dispatch())

    @contextlib.contextmanager
    def tick_record(self):
        # per-tick phase timeline (debug_http /trace): the GameServer's
        # serve loop opens the tick record (so pump/fan-out spans land in
        # it too); a standalone World opens its own and must close it
        # even when a phase raises, or the process-global recorder wedges
        tl = metrics.timeline
        self_opened = not tl.is_open
        if self_opened:
            tl.begin_tick(self.tick_count)
        try:
            yield
        finally:
            if self_opened:
                tl.end_tick()

    def watch_landing(self, flight: TickInFlight,
                      wake: threading.Event) -> None:
        """Have something other than the caller wait for the outputs
        of ``flight``: this World's waiter blocks until the device is
        done, marks the tick ``landed`` and sets ``wake``. Nothing is
        handed over where the device is done already (a pipelined
        decode fetches the previous tick's outputs). One waiter a
        World, started with the first tick that needs it and fed
        through a queue: starting a thread per tick held the caller 3
        ms a tick in a served process (CPU rehearsal, PR 34)."""
        if flight.landed or _has_landed(flight.fetch):
            flight.landed = True
            return
        flights = self._landings
        if flights is None:
            flights = self._landings = queue.SimpleQueue()
            threading.Thread(target=_await_landings, args=(flights,),
                             name="tick-landing", daemon=True).start()
            weakref.finalize(self, flights.put, None)
        flights.put((flight, wake))

    def tick_dispatch(self) -> TickInFlight:
        """First half of a tick: flush what was staged, dispatch the
        device step, name what the second half fetches. Returns at once
        (the step runs asynchronously)."""
        tl = metrics.timeline
        t_start = time.perf_counter()
        # serve-loop residency marks (utils/residency.py): perf_counter
        # instants at the phase boundaries this method already has —
        # nothing here touches the device
        rt = self.residency
        if rt is not None:
            rt.tick_begin()
        # sync-age epoch: this tick's state is decided by the inputs
        # flushed below, so the age of everything it produces is
        # measured from HERE (utils/syncage.py lane table)
        age_mark = (self.tick_count, int(time.time() * 1e6))
        with tl.span("flush_staging"):
            if self._multihost and self.service_mgr is not None \
                    and self.mh_group_ready \
                    and self.tick_count % self.service_mgr.MH_CHECK_TICKS \
                    == 0:
                # tick-cadence service reconcile (wall timers would fire
                # at different instants per controller and desync the
                # deterministic eid sequence; tick_count is
                # SPMD-consistent, and mh_group_ready comes from the
                # GameServer's per-tick allgather — True by construction
                # for standalone worlds)
                self.service_mgr.check_services()
            self.timers.tick(self._fire_timer)
            self.crontab.tick()
            self.post_q.tick()
            inputs = self._flush_staging()
        self._pos_cache = self._yaw_cache = None
        t0 = time.perf_counter()
        with tl.span("device_step"):
            self.state, outs = self._step(self.state, inputs, self.policy)
            if self._telem_fn is not None:
                # fold THIS tick's outputs into the device-resident
                # lanes — one async jitted dispatch, no host sync (the
                # pipelined swap below only reorders the HOST decode,
                # so the fold always sees the current tick); inside the
                # span so its dispatch/compile time is attributed.
                # A fold failure disables the lanes, never the tick.
                try:
                    self._telem_acc = self._telem_fn(
                        self._telem_acc, outs)
                except Exception:
                    logger.exception(
                        "live telemetry fold failed; disabled")
                    self._telem_fn = self._telem_acc = None
        if rt is not None:
            # the device has work from HERE: closes the previous
            # inter-dispatch gap, so the bubble verdict lands now
            rt.mark_dispatch()
        if self.pipeline_decode:
            # PIPELINED decode (opt-in; single-controller non-mesh
            # worlds only — mesh/mega decode has same-tick couplings
            # like the staged-migration tag map): tick N is dispatched
            # ASYNC above, then tick N-1's outputs — already
            # materialized on device — are fetched and decoded WHILE
            # the device computes N. The frame pays
            # max(device, host decode) instead of their sum (not
            # measured on the chip since). Costs: host-visible events and
            # client sends lag one tick, and the slot-release
            # quarantine is skewed one call to match (_flush_staging
            # routes despawn releases via _release_next). Freeze /
            # checkpoint paths call flush_pending_outputs() first.
            # outs is None on the first tick (nothing to decode yet).
            outs, self._pending_outs = self._pending_outs, outs
        # which accumulator the fetch below drains: the pipelined path
        # swaps it one tick back like the outputs — fetching THIS
        # tick's acc would depend on the in-flight step and re-
        # serialize exactly the host/device overlap pipeline_decode
        # exists to buy
        if self.pipeline_decode:
            acc_fetch, self._pending_telem = \
                self._pending_telem, self._telem_acc
        else:
            acc_fetch = self._telem_acc
        if self.pipeline_decode:
            # the outputs fetched below are the PREVIOUS tick's: the
            # age anchor follows them (same swap as _pending_outs), so
            # the device_tick lane honestly includes the pipeline skew
            age_mark, self._age_pending_mark = \
                self._age_pending_mark, age_mark
            # double-buffered drain (ISSUE 20): the lanes just parked
            # above (this tick's outs + accumulator) start their D2H
            # immediately so the copy overlaps the NEXT tick's compute
            _start_host_copy(self._pending_outs)
            _start_host_copy(self._pending_telem)
        # audit-oracle cohort planes (ISSUE 17): on a sample tick the
        # judged shard's pos/alive/aoi_radius ride the SAME combined
        # fetch below — the lazy device slices cost nothing to build
        # once compiled (the first sample compiles them: tens of ms,
        # inside the span so the trace accounts for it) and the plane
        # adds zero sync points. Only the single-controller non-mega
        # shape is judged (a mesh slice would gather cross-device; the
        # skip is recorded honestly in _audit_sample).
        fetch = {}
        if outs is not None:
            fetch["outs"] = outs
        if acc_fetch is not None:
            # the telemetry drain rides the EXISTING fetch: one
            # combined transfer, zero added sync points per tick
            fetch["acc"] = acc_fetch
        ap = self.audit
        if (ap is not None and self.mega is None
                and self.mesh is None and not self.pipeline_decode
                and ap.want_sample(self.tick_count)):
            s = self._audit_shard % self.n_spaces
            with tl.span("fetch_outputs"):
                fetch["aud"] = (self.state.pos[s], self.state.alive[s],
                                self.state.aoi_radius[s])
        if rt is not None:
            rt.mark_fetch()
        return TickInFlight(fetch, outs, age_mark, t_start, t0)

    def tick_land(self, flight: TickInFlight) -> None:
        """Second half of a tick: fetch the outputs ``tick_dispatch``
        named (a copy of what is on the host already where the caller
        waited for ``flight.landed``), decode them, fan out."""
        tl = metrics.timeline
        rt = self.residency
        outs, age_mark = flight.outs, flight.age_mark
        # the device's planes are let go once they are on the host, as
        # when fetch and decode were one method's locals
        fetch, flight.fetch, flight.outs = flight.fetch, None, None
        with tl.span("fetch_outputs"):
            acc_host = None
            aud_host = None
            if fetch:
                got = self._dget(fetch)
                del fetch
                if "outs" in got:
                    outs = got["outs"]
                acc_host = got.get("acc")
                aud_host = got.get("aud")
            if rt is not None:
                # outputs are host-visible: the device_wait lane ends
                rt.mark_visible()
            if acc_host is not None:
                try:
                    self._ingest_telemetry(acc_host)
                except Exception:
                    logger.exception(
                        "live telemetry drain failed; disabled")
                    self._telem_fn = self._telem_acc = None
            if outs is not None:
                if self._multihost:
                    # EAGER pos/yaw refresh: every controller executes
                    # these two collectives at the same point every tick.
                    # Lazy fetching would deadlock — read_pos is a
                    # process_allgather under multihost, and the
                    # owner-local decode below reaches it on ONE
                    # controller only (e.g. je.position while building a
                    # client enter message, or a user OnEnterAOI hook)
                    self._pos_cache = self._dget(self.state.pos)
                    self._yaw_cache = self._dget(self.state.yaw)
        if outs is not None and age_mark is not None:
            # outputs are host-visible NOW: close the device_tick lane
            # (the GameServer's fan-out flush consumes this anchor)
            self.sync_age_anchor = (age_mark[0], age_mark[1],
                                    int(time.time() * 1e6))
        # under pipelining this measures dispatch + the blocking fetch
        # of the PREVIOUS tick's outputs — i.e. how long this frame
        # actually waited on the device, the number the 16 ms budget
        # cares about (the true per-step device time is not
        # host-observable without a sync)
        dt = time.perf_counter() - flight.t0
        self.op_stats["device_step_s"] = dt
        if rt is not None:
            rt.observe_device_step(dt)
        tl.set_tick_args(device_step_ms=round(dt * 1e3, 3),
                         tick=self.tick_count)
        with tl.span("decode_fanout"):
            if outs is not None:
                self._decode_outputs(outs)
            self.post_q.tick()
        if self._restored_interest is not None:
            with tl.span("restore_reconcile"):
                self._reconcile_restored_interest()
        ap = self.audit
        if ap is not None and ap.want_sample(self.tick_count):
            # capture the cohort + frozen interest sets HERE (the
            # decode above just made them current for this tick), then
            # hand the oracle math to the audit worker. A capture
            # failure disables the plane, never the tick.
            with tl.span("audit_sample"):
                try:
                    self._audit_sample(aud_host)
                except Exception:
                    logger.exception("audit sampling failed; disabled")
                    self.audit = None
        if rt is not None:
            rt.mark_decode_done()
            if rt.should_sample(self.tick_count):
                # sampled churn probes (census pointer reads + local
                # allocator stats — still no device sync). A probe
                # failure disables the plane, never the tick.
                try:
                    rt.sample_census(self.state)
                    dev = getattr(self.state.pos, "devices", None)
                    if dev is not None:
                        rt.sample_memory(next(iter(dev())),
                                         self.tick_count)
                except Exception:
                    logger.exception(
                        "residency sampling failed; disabled")
                    self.residency = None
        self.tick_count += 1
        opmon.monitor.record("world.tick",
                             time.perf_counter() - flight.t_start)

    def _decode_outputs(self, outs) -> None:
        """The host half of a tick: record + decode fetched outputs.
        Shared by tick() and flush_pending_outputs() so the sequence
        cannot drift between the pipelined and eager paths."""
        self.last_outputs = outs  # observability (tests, opmon, dryrun)
        self._process_outputs(outs)
        self._drain_attr_journals()

    def flush_pending_outputs(self) -> None:
        """Drain the pipelined decode (no-op when pipelining is off or
        nothing is pending). Freeze, checkpoint and shutdown paths must
        not snapshot with a tick's outputs undecoded — client sends and
        interest-set updates would be lost with the process."""
        pending, self._pending_outs = self._pending_outs, None
        if pending is None:
            return
        self._decode_outputs(self._dget(pending))

    def _reconcile_restored_interest(self) -> None:
        """End of the first tick after a reload's restore. A client that
        stayed connected still holds the mirrors it had at the freeze,
        but the restored world starts from empty neighbour lists: its
        first tick reports every neighbour as an enter (most of them
        past ``enter_cap`` in a large world) and no leave at all. So
        hold what each such client was told (carried by the freeze
        record) against the device's list for its owner's row: destroy
        what is out of range by now, create what is new and was not
        decoded, and leave the owner's host sets equal to the device's
        — every enter the client ever got still gets its leave."""
        told_by_owner, self._restored_interest = \
            self._restored_interest, None
        if not told_by_owner:
            return
        # a pipelined decode lags one tick: the host's view must be of
        # the same tick as state.nbr before the two are compared
        self.flush_pending_outputs()
        nbr = self._dget(self.state.nbr)
        for eid, told in told_by_owner.items():
            e = self.entities.get(eid)
            if e is None or e.destroyed or e.client is None \
                    or e.slot is None or e.shard not in self.local_shards:
                continue
            now = {}
            for j in nbr[e.shard, e.slot].tolist():
                je = self._owner_subject(e.shard, j)
                if je is not None and not je.destroyed:
                    now[je.id] = je
            for gone in set(told) - now.keys():
                e.client.send({"type": "destroy_entity", "eid": gone,
                               "is_player": False})
            for jid, je in now.items():
                if jid in e.interested_in:
                    continue        # decoded this tick, create sent
                e.interested_in.add(jid)
                je.interested_by.add(eid)
                try:
                    e.OnEnterAOI(je)
                except Exception:
                    logger.exception("OnEnterAOI failed")
                if jid not in told:
                    e.client.send({
                        "type": "create_entity", "eid": jid,
                        "etype": je.type_name, "is_player": False,
                        "attrs": je.get_all_clients_data(),
                        "pos": list(je.position), "yaw": je.yaw,
                    })

    # -- correctness audit sampling (utils/audit.py, ISSUE 17) ----------
    def _audit_sample(self, aud_host) -> None:
        """Logic-thread half of one audit sample: decide eligibility
        (every skip recorded with its reason — a degraded tick must
        never read as a passed one), run the cheap cohort-bounded
        mirror probes inline, freeze the cohort's interest sets and
        ledger census, and hand the O(cohort x n) oracle math to the
        audit worker. Zero device syncs: ``aud_host`` already rode the
        tick's combined fetch."""
        ap = self.audit
        tick = self.tick_count
        if self.mega is not None:
            ap.skip_sample("megaspace", tick)
            return
        if self.mesh is not None:
            ap.skip_sample("mesh", tick)
            return
        if self.pipeline_decode:
            # the decoded interest sets are tick N-1's while state.pos
            # is tick N's — the oracle would judge mismatched epochs
            ap.skip_sample("pipeline_decode", tick)
            return
        if aud_host is None:
            ap.skip_sample("no_fetch", tick)
            return
        if (self.op_stats.get("aoi_over_k_rows")
                or self.op_stats.get("aoi_over_cap_cells")):
            # the check_oracle exactness precondition: a sweep that
            # overflowed k/cell_cap is only approximate by design —
            # provisioning, not correctness, is the finding there
            ap.skip_sample("overflow", tick)
            return
        s = self._audit_shard % self.n_spaces
        self._audit_shard += 1
        owner = dict(self._slot_owner[s])
        if not owner:
            ap.skip_sample("empty", tick)
            return
        # slots whose device rows lag the host this tick (staged
        # spawns/despawns/moves from decode callbacks, in-flight
        # migrations): judging them would manufacture mismatches
        pending = {sl for sh, sl, _ in self._staged_spawn if sh == s}
        pending |= {sl for sh, sl in self._staged_despawn if sh == s}
        pending |= {sl for sh, sl in self._staged_pos if sh == s}
        eligible = []
        for slot, eid in owner.items():
            if slot in pending:
                continue
            e = self.entities.get(eid)
            if (e is None or e.destroyed or e.slot is None
                    or e._migrating is not None
                    or e._pending_pos is not None):
                continue
            eligible.append(slot)
        cohort = ap.next_cohort(eligible)
        if not cohort:
            ap.skip_sample("empty", tick)
            return
        # mirror consistency probes, inline (cohort-bounded dict/numpy
        # peeks): slot->eid mirror columns, client binding columns,
        # interested_by reverse edges
        probe_bad = 0
        for slot in cohort:
            eid = owner[slot]
            e = self.entities[eid]
            if self._mir_eid[s, slot] != eid.encode("ascii"):
                probe_bad += 1
                ap.ledger.note_violation(
                    "mirror_slot",
                    f"slot mirror [{s},{slot}] holds "
                    f"{self._mir_eid[s, slot]!r}, host says EntityID "
                    f"{eid} (tick {tick})", tick)
            cid = e.client.client_id.encode("ascii") \
                if e.client is not None else b""
            gid = e.client.gate_id if e.client is not None else -1
            if (self._mir_cid[s, slot] != cid
                    or int(self._mir_gate[s, slot]) != gid):
                probe_bad += 1
                ap.ledger.note_violation(
                    "mirror_client",
                    f"client mirror [{s},{slot}] diverges for EntityID "
                    f"{eid}: cols ({self._mir_cid[s, slot]!r}, "
                    f"{int(self._mir_gate[s, slot])}) vs host "
                    f"({cid!r}, {gid}) (tick {tick})", tick)
            for jid in e.interested_in:
                je = self.entities.get(jid)
                if je is None or eid not in je.interested_by:
                    probe_bad += 1
                    ap.ledger.note_violation(
                        "interest_symmetry",
                        f"EntityID {eid} watches {jid} but is not in "
                        f"its interested_by (tick {tick})", tick)
        ap.note_probe(len(cohort), probe_bad)
        # ledger-vs-world census cross-check: both sides frozen NOW on
        # the logic thread (the worker only diffs), so churn between
        # capture and judgment cannot fake a divergence
        world_live = {eid for eid, e in self.entities.items()
                      if not e.destroyed}
        ledger_live = ap.ledger.live_eids()
        # frozen interest sets for the cohort (the worker must not
        # chase live sets the next tick is already mutating)
        interest = {owner[slot]: set(self.entities[owner[slot]]
                                     .interested_in)
                    for slot in cohort}
        pos, alive, wr = aud_host
        quant_step = quant_hi = None
        if self.cfg.grid.precision != "off":
            quant_step = self.cfg.grid.quant_step
            quant_hi = (1 << consts.PRECISION_POS_BITS) - 1
        radius = self.cfg.grid.radius
        from goworld_tpu.utils import audit as audit_mod

        def _job():
            diff = sorted(world_live ^ ledger_live)
            if diff:
                ap.ledger.note_violation(
                    "census_divergence",
                    f"ledger and world census diverge at EntityID "
                    f"{diff[0]} ({len(diff)} differ; tick {tick})",
                    tick)
            ap.judge_sample(
                tick=tick, pos=pos, alive=alive, watch_radius=wr,
                radius=radius, cohort_slots=cohort, owner=owner,
                interest=interest, quant_step=quant_step,
                quant_hi=quant_hi or 0)

        ap.submit(_job)

    # -- staging flush --------------------------------------------------
    def _spmd_guard(self) -> None:
        """Multi-controller divergence tripwire: every controller must
        stage IDENTICAL mutations each tick (the SPMD contract — e.g. a
        user AOI hook that spawns only on the event-owning controller
        violates it and silently forks device state). Compare a cheap
        signature of this tick's staging across processes and log loudly
        on mismatch."""
        import zlib

        from jax.experimental import multihost_utils

        sig = repr((
            sorted(
                (s, sl, sorted((k, str(v)) for k, v in d.items()))
                for s, sl, d in self._staged_spawn
            ),
            sorted(self._staged_despawn),
            sorted(self._staged_hot),
            sorted(self._staged_moving),
            sorted(self._staged_client),
            sorted(
                (k, e._pending_pos, e._pending_yaw)
                for k, e in self._staged_pos.items()
            ),
            sorted(self._staged_migrate),
            self._batch_sig(),
        )).encode()
        h = np.uint32(zlib.crc32(sig))
        hs = multihost_utils.process_allgather(h)
        if (np.asarray(hs) != np.asarray(hs).ravel()[0]).any():
            logger.error(
                "SPMD staging divergence across controllers (hashes %s): "
                "device state is forking — all controllers must perform "
                "identical World mutations each tick "
                "(parallel/multihost.py contract)", np.asarray(hs),
            )

    def _batch_sig(self) -> bytes:
        """Staged-batch-sync content for the SPMD divergence tripwire."""
        if not self._batch_pos_any or self._batch_pos_mask is None:
            return b""
        bsh, bsl = np.nonzero(self._batch_pos_mask)
        return (bsh.tobytes() + bsl.tobytes()
                + self._batch_pos_vals[bsh, bsl].tobytes())

    def _flush_staging(self):
        cfg = self.cfg
        # tick_count is SPMD-consistent, so sampling keeps the collective
        # uniform across controllers while keeping the tripwire off the
        # steady-state hot path (it still catches a fork within 16 ticks)
        if self._multihost and self.tick_count % 16 == 0:
            self._spmd_guard()

        # local-path migrations become a host repack (read row -> respawn
        # at destination) BEFORE the scatter flush below applies them
        if self._staged_migrate and self.mesh is None:
            live = [
                m for m in self._staged_migrate
                if (e := self.entities.get(m[3])) is not None
                and not e.destroyed
            ]
            # ONE batched gather for every migrating row (per-entity
            # device_get would pay the transfer latency N times)
            st = self.state
            msh = np.array([m[0] for m in live], np.int32)
            msl = np.array([m[1] for m in live], np.int32)
            if live:
                msh, msl = _pad_scatter(msh, msl, 0)
            rows = jax.device_get({
                "pos": st.pos[(msh, msl)], "yaw": st.yaw[(msh, msl)],
                "type_id": st.type_id[(msh, msl)],
                "npc_moving": st.npc_moving[(msh, msl)],
                "has_client": st.has_client[(msh, msl)],
                "client_gate": st.client_gate[(msh, msl)],
                "hot": st.hot_attrs[(msh, msl)],
            }) if live else None
            for i, (sh_, sl_, dst, eid) in enumerate(live):
                e = self.entities[eid]
                e._migrating = None
                new_slot = self._alloc_slot(dst, eid)
                pend = e._pending_pos or tuple(
                    np.asarray(rows["pos"][i]).tolist()
                )
                self._staged_spawn.append((dst, new_slot, dict(
                    pos=pend, yaw=float(rows["yaw"][i]),
                    type_id=int(rows["type_id"][i]),
                    npc_moving=bool(rows["npc_moving"][i]),
                    has_client=bool(rows["has_client"][i]),
                    client_gate=int(rows["client_gate"][i]),
                    hot=np.asarray(rows["hot"][i]).tolist(),
                    aoi_radius=_type_aoi_radius(e._type_desc),
                )))
                # old slot: despawn now; owner mapping stays for this
                # step's leave events, slot frees after processing
                self._staged_despawn.append((sh_, sl_))
                e.slot = new_slot
                e.shard = dst
                e._pending_pos = pend
                # attr writes made during the migration window are only in
                # the host tree; overwrite the repacked row's hot columns
                for name, col in e._type_desc.hot_attrs.items():
                    v = e.attrs.get(name)
                    if isinstance(v, (int, float)) \
                            and not isinstance(v, bool):
                        self._staged_hot.append((dst, new_slot, col,
                                                 float(v)))
                e.OnMigrateIn()
                e.OnEnterSpace()
                tgt_id = self._shard_space[dst]
                tgt = self.spaces.get(tgt_id) if tgt_id else None
                if tgt is not None:
                    tgt.OnEntityEnterSpace(e)
            self._staged_migrate.clear()

        st = self.state
        cap = cfg.capacity
        if self._staged_spawn:
            sh = np.array([s for s, _, _ in self._staged_spawn], np.int32)
            sl = np.array([s for _, s, _ in self._staged_spawn], np.int32)
            d = [v for _, _, v in self._staged_spawn]
            sh, sl, p_, y_, mv, hc, cg, ti, ht, ar = _pad_scatter(
                sh, sl, cap,
                np.array([x["pos"] for x in d], np.float32),
                np.array([x["yaw"] for x in d], np.float32),
                np.array([x["npc_moving"] for x in d]),
                np.array([x["has_client"] for x in d]),
                np.array([x["client_gate"] for x in d], np.int32),
                np.array([x["type_id"] for x in d], np.int32),
                np.array([x["hot"] for x in d], np.float32),
                np.array(
                    [x.get("aoi_radius", np.inf) for x in d], np.float32
                ),
            )
            ix = (sh, sl)
            st = st.replace(
                pos=st.pos.at[ix].set(p_, mode="drop"),
                yaw=st.yaw.at[ix].set(y_, mode="drop"),
                vel=st.vel.at[ix].set(0.0, mode="drop"),
                alive=st.alive.at[ix].set(True, mode="drop"),
                npc_moving=st.npc_moving.at[ix].set(mv, mode="drop"),
                has_client=st.has_client.at[ix].set(hc, mode="drop"),
                client_gate=st.client_gate.at[ix].set(cg, mode="drop"),
                type_id=st.type_id.at[ix].set(ti, mode="drop"),
                aoi_radius=st.aoi_radius.at[ix].set(ar, mode="drop"),
                gen=st.gen.at[ix].add(1, mode="drop"),
                dirty=st.dirty.at[ix].set(True, mode="drop"),
                hot_attrs=st.hot_attrs.at[ix].set(ht, mode="drop"),
                attr_dirty=st.attr_dirty.at[ix].set(
                    np.uint32(0), mode="drop"),
            )
            # the device row now holds the spawn position; clear the host
            # mirror so Entity.position tracks the live row (unless a
            # newer set_position is staged — that loop clears its own)
            for shard_, slot_, data in self._staged_spawn:
                if (shard_, slot_) in self._staged_pos:
                    continue
                e_ = self._owner_entity(shard_, slot_)
                if e_ is not None:
                    e_._pending_pos = None
                    e_._pending_yaw = None
            self._staged_spawn.clear()

        if self._staged_despawn:
            sh = np.array([s for s, _ in self._staged_despawn], np.int32)
            sl = np.array([s for _, s in self._staged_despawn], np.int32)
            sh, sl = _pad_scatter(sh, sl, cap)
            ix = (sh, sl)
            st = st.replace(
                alive=st.alive.at[ix].set(False, mode="drop"),
                has_client=st.has_client.at[ix].set(False, mode="drop"),
                client_gate=st.client_gate.at[ix].set(-1, mode="drop"),
                npc_moving=st.npc_moving.at[ix].set(False, mode="drop"),
                dirty=st.dirty.at[ix].set(False, mode="drop"),
            )
            # release AFTER this tick's leave events decode: that is
            # the end of THIS tick's _process_outputs normally, but one
            # call LATER under pipelined decode (this tick's outputs
            # decode next tick — releasing now would free the slot a
            # call early, letting a reused slot capture the old
            # entity's pending leave events)
            rel = (self._release_next if self.pipeline_decode
                   else self._release_now)
            rel.extend(
                (sh_, sl_, self._slot_owner[sh_].get(sl_))
                for sh_, sl_ in self._staged_despawn
            )
            self._staged_despawn.clear()

        if self._staged_hot:
            sh = np.array([x[0] for x in self._staged_hot], np.int32)
            sl = np.array([x[1] for x in self._staged_hot], np.int32)
            co = np.array([x[2] for x in self._staged_hot], np.int32)
            va = np.array([x[3] for x in self._staged_hot], np.float32)
            sh, sl, co, va = _pad_scatter(sh, sl, cap, co, va)
            st = st.replace(
                hot_attrs=st.hot_attrs.at[(sh, sl, co)].set(
                    va, mode="drop")
            )
            self._staged_hot.clear()

        if self._staged_moving:
            sh = np.array([x[0] for x in self._staged_moving], np.int32)
            sl = np.array([x[1] for x in self._staged_moving], np.int32)
            mv = np.array([x[2] for x in self._staged_moving])
            sh, sl, mv = _pad_scatter(sh, sl, cap, mv)
            st = st.replace(
                npc_moving=st.npc_moving.at[(sh, sl)].set(mv, mode="drop")
            )
            self._staged_moving.clear()

        if self._staged_client:
            sh = np.array([x[0] for x in self._staged_client], np.int32)
            sl = np.array([x[1] for x in self._staged_client], np.int32)
            hc = np.array([x[2] for x in self._staged_client])
            cg = np.array([x[3] for x in self._staged_client], np.int32)
            sh, sl, hc, cg = _pad_scatter(sh, sl, cap, hc, cg)
            ix = (sh, sl)
            st = st.replace(
                has_client=st.has_client.at[ix].set(hc, mode="drop"),
                client_gate=st.client_gate.at[ix].set(cg, mode="drop"),
            )
            self._staged_client.clear()

        # position-sync inputs -> TickInputs [S, IC]: pinned host
        # staging (ISSUE 20) — the preallocated trio is zeroed and
        # refilled in place instead of three fresh numpy allocations
        # per tick
        ic = cfg.input_cap
        idx = self._pin_idx
        vals = self._pin_vals
        counts = self._pin_counts
        idx.fill(0)
        vals.fill(0)
        counts.fill(0)
        entries = list(self._staged_pos.items())
        # a set_position without set_yaw must keep the current device yaw
        # (apply_pos_inputs scatters all four lanes); batch-gather the
        # fallback yaws in ONE transfer from the post-scatter state
        need_yaw = [
            (shard, slot) for (shard, slot), e in entries
            if e._pending_yaw is None
        ]
        yaw_fb: dict[tuple[int, int], float] = {}
        if need_yaw:
            ysh = np.array([s for s, _ in need_yaw], np.int32)
            ysl = np.array([s for _, s in need_yaw], np.int32)
            ysh, ysl = _pad_scatter(ysh, ysl, 0)  # pad only (gather clips)
            got = self._dget(st.yaw[(ysh, ysl)])
            yaw_fb = {k: float(v) for k, v in zip(need_yaw, got)}
        overflow: dict[tuple[int, int], Entity] = {}
        for (shard, slot), e in entries:
            c = counts[shard]
            if c >= ic:
                # keep it staged so the write lands next tick instead of
                # silently diverging host (_pending_pos) from device
                overflow[(shard, slot)] = e
                continue
            p = e._pending_pos or e.position
            y = e._pending_yaw if e._pending_yaw is not None \
                else yaw_fb.get((shard, slot), 0.0)
            idx[shard, c] = slot
            vals[shard, c] = (p[0], p[1], p[2], y)
            counts[shard] = c + 1
            e._pending_pos = None
            e._pending_yaw = None
        self._staged_pos = overflow
        if overflow:
            logger.warning(
                "pos-sync input overflow: %d updates deferred a tick",
                len(overflow),
            )

        # batched client syncs (stage_pos_sync_batch) fill the remaining
        # input rows; host-side writes staged this tick shadow a client
        # record for the same slot (idx duplicates would make the device
        # scatter order-undefined), and rows that don't fit stay staged
        # for the next tick
        if self._batch_pos_any:
            bm = self._batch_pos_mask
            if entries:
                hsh = np.array([k[0] for k, _ in entries], np.int32)
                hsl = np.array([k[1] for k, _ in entries], np.int32)
                bm[hsh, hsl] = False
            bsh, bsl = np.nonzero(bm)
            deferred = 0
            if bsh.size:
                bv = self._batch_pos_vals[bsh, bsl]
                for shard in np.unique(bsh):
                    m = np.nonzero(bsh == shard)[0]
                    room = max(ic - int(counts[shard]), 0)
                    take = m[:room]
                    k = take.size
                    if k:
                        c0 = int(counts[shard])
                        idx[shard, c0:c0 + k] = bsl[take]
                        vals[shard, c0:c0 + k] = bv[take]
                        counts[shard] = c0 + k
                        bm[shard, bsl[take]] = False
                    deferred += m.size - k
            if deferred:
                logger.warning(
                    "pos-sync input overflow: %d client sync records "
                    "deferred a tick", deferred,
                )
            self._batch_pos_any = bool(bm.any())

        # jnp.array (NOT asarray): asarray may zero-copy-alias the host
        # buffer on CPU backends, and the pinned trio is overwritten
        # next tick while the device step could still be reading it
        base = TickInputs(
            pos_sync_idx=jnp.array(idx),
            pos_sync_vals=jnp.array(vals),
            pos_sync_n=jnp.array(counts),
        )
        self.state = st

        if self.mesh is None:
            return base

        from goworld_tpu.parallel.step import MultiTickInputs

        mt = np.full((self.n_spaces, cfg.capacity), -1, np.int32)
        tags = np.full((self.n_spaces, cfg.capacity), -1, np.int32)
        self._migrate_tags = {}
        for i, (sh_, sl_, dst, eid) in enumerate(self._staged_migrate):
            mt[sh_, sl_] = dst
            tags[sh_, sl_] = i
            self._migrate_tags[i] = (eid, sh_, sl_)
        self._staged_migrate.clear()
        return MultiTickInputs(
            base=base,
            migrate_target=jnp.asarray(mt),
            migrate_tag=jnp.asarray(tags),
        )

    # -- output processing ----------------------------------------------
    def _owner_subject(self, shard: int, j: int) -> Entity | None:
        """Resolve a subject id from tick outputs: a local slot for normal
        spaces, a GLOBAL gid (= tile * capacity + slot) in megaspace mode
        where neighbors may live on adjacent tiles (ghosts)."""
        if self.mega is not None:
            tile, slot = divmod(j, self.cfg.capacity)
            if tile >= self.n_spaces:
                return None  # gid sentinel
            return self._owner_entity(tile, slot)
        return self._owner_entity(shard, j)

    def _process_outputs(self, outs) -> None:
        if self.mesh is not None:
            base = outs.base
        else:
            base = outs
        cfg = self.cfg
        mega_pending = (
            self._mega_collect_arrivals(outs) if self.mega is not None
            else None
        )
        # Leaves before enters, across all shards: a megaspace border-hop
        # emits leave(old slot, X) on the source tile and enter(new slot,
        # X) on the destination tile for a subject X visible from both —
        # both slots resolve to the same host entity, so enters must be
        # applied last for the final interest set to be correct.
        # The pair-decode loops below run at event-cap volumes every
        # tick (the host half of the 16 ms frame budget — see
        # tools/probe_fanout.py): owner resolution is inlined (two
        # dict gets, no helper-call overhead; dict.get(None) is safely
        # None) and the AOI hook call + its exception containment is
        # skipped for types that don't override the no-op hook. The
        # override test is cached per CLASS per decode (so post-
        # registration class patching is honored) with a per-pair
        # instance-__dict__ check for per-object hook assignment.
        mega = self.mega is not None
        entities = self.entities
        dropped = {"enter": 0, "leave": 0, "rows": 0}
        leave_hooked: dict[type, bool] = {}
        enter_hooked: dict[type, bool] = {}
        for shard in self.local_shards:
            ln = int(base.leave_n[shard])
            if ln > cfg.leave_cap:
                logger.warning(
                    "shard %d leave overflow: %d > %d", shard, ln,
                    cfg.leave_cap,
                )
                dropped["leave"] += ln - cfg.leave_cap
            slot_eid = self._slot_owner[shard].get
            # .tolist() upfront: plain-int pairs beat per-element numpy
            # scalar conversions across tens of thousands of events
            for w, j in zip(
                np.asarray(base.leave_w[shard])[: min(ln, cfg.leave_cap)]
                .tolist(),
                np.asarray(base.leave_j[shard])[: min(ln, cfg.leave_cap)]
                .tolist(),
            ):
                we = entities.get(slot_eid(w))
                je = (self._owner_subject(shard, j) if mega
                      else entities.get(slot_eid(j)))
                if we is None or je is None:
                    continue
                # a client is told of a leaving only for what it was
                # told had entered (an enter the pass below skipped, or
                # one past enter_cap, was never sent)
                told = je.id in we.interested_in
                we.interested_in.discard(je.id)
                je.interested_by.discard(we.id)
                wcls = we.__class__
                hooked = leave_hooked.get(wcls)
                if hooked is None:
                    hooked = leave_hooked[wcls] = (
                        wcls.OnLeaveAOI is not Entity.OnLeaveAOI)
                if hooked or "OnLeaveAOI" in we.__dict__:
                    try:
                        we.OnLeaveAOI(je)
                    except Exception:
                        logger.exception("OnLeaveAOI failed")
                if told and we.client is not None and not we.destroyed:
                    we.client.send({
                        "type": "destroy_entity", "eid": je.id,
                        "is_player": False,
                    })
        if mega_pending is not None:
            # re-point tile-migrated entities AFTER leave decode (their
            # new slots may be rows host-despawned this tick, whose leave
            # events reference the previous owner) but BEFORE enter
            # decode (arrivals' enter events reference their new slots)
            self._mega_apply_arrivals(mega_pending, outs)
        for shard in self.local_shards:
            drn = int(base.delta_rows_n[shard])
            drc = min(cfg.delta_rows_cap_eff, cfg.capacity)
            if drn > drc:
                # the ROW cap overflowed: surplus rows' enter/leave events
                # are gone and widening enter/leave caps won't help
                logger.warning(
                    "shard %d AOI delta rows overflow: %d > %d — widen "
                    "WorldConfig.delta_rows_cap", shard, drn, drc,
                )
                dropped["rows"] += drn - drc
            en = int(base.enter_n[shard])
            if en > cfg.enter_cap:
                logger.warning(
                    "shard %d enter overflow: %d > %d", shard, en,
                    cfg.enter_cap,
                )
                dropped["enter"] += en - cfg.enter_cap
            # per-decode payload cache: one subject typically enters
            # MANY watchers' interest this tick (a mover crossing a
            # crowd), and its AllClients attr snapshot + pos/yaw are
            # identical for each — computing them once per subject cuts
            # the dominant host cost of a churn-heavy tick (profiled:
            # to_dict_with_filter alone was ~45% of enter decode at 10K
            # clients). The attrs dict is shared read-only across the
            # sends; a user OnEnterAOI hook mutating the subject MID-
            # DECODE would journal attr deltas to clients anyway.
            payloads: dict[str, tuple] = {}
            slot_eid = self._slot_owner[shard].get
            for w, j in zip(
                np.asarray(base.enter_w[shard])[: min(en, cfg.enter_cap)]
                .tolist(),
                np.asarray(base.enter_j[shard])[: min(en, cfg.enter_cap)]
                .tolist(),
            ):
                we = entities.get(slot_eid(w))
                je = (self._owner_subject(shard, j) if mega
                      else entities.get(slot_eid(j)))
                if we is None or je is None:
                    continue
                if we.slot is None or je.slot is None:
                    # alive on the device in this tick, left by its
                    # entity since the dispatch (destroyed, or moved out
                    # of the space, by a handler the serve loop ran
                    # while the device computed; the despawn waits for
                    # the next flush): no client hears of it again
                    continue
                we.interested_in.add(je.id)
                je.interested_by.add(we.id)
                wcls = we.__class__
                hooked = enter_hooked.get(wcls)
                if hooked is None:
                    hooked = enter_hooked[wcls] = (
                        wcls.OnEnterAOI is not Entity.OnEnterAOI)
                if hooked or "OnEnterAOI" in we.__dict__:
                    try:
                        we.OnEnterAOI(je)
                    except Exception:
                        logger.exception("OnEnterAOI failed")
                if we.client is not None and not je.destroyed:
                    pc = payloads.get(je.id)
                    if pc is None:
                        pc = payloads[je.id] = (
                            je.type_name,
                            je.get_all_clients_data(),
                            list(je.position),
                            je.yaw,
                        )
                    we.client.send({
                        "type": "create_entity", "eid": je.id,
                        "etype": pc[0], "is_player": False,
                        "attrs": pc[1], "pos": pc[2], "yaw": pc[3],
                    })
        for shard in self.local_shards:
            # position sync records -> watching clients
            sn = min(int(base.sync_n[shard]), cfg.sync_cap)
            if sn:
                ws = np.asarray(base.sync_w[shard])[:sn]
                js = np.asarray(base.sync_j[shard])[:sn]
                vs = np.asarray(base.sync_vals[shard])[:sn]
                if self.sync_stride > 1:
                    # DEGRADED fan-out: serve one entity cohort per
                    # tick (subject slot mod stride) — each entity
                    # still syncs every `stride` ticks with its LATEST
                    # position, so nothing is lost, only thinned.
                    # Vectorized mask; skipped records counted so every
                    # shed record has a name (shed_total{sync,stride}).
                    from goworld_tpu.utils import overload as _ov

                    keep = (js % self.sync_stride) == (
                        self.tick_count % self.sync_stride
                    )
                    # (its own name: `dropped` is this method's dict
                    # of undecoded interest events, walked below)
                    strided = int(sn - int(keep.sum()))
                    if strided:
                        _ov.shed_counter(
                            _ov.CLASS_SYNC, "stride").inc(strided)
                    ws, js, vs = ws[keep], js[keep], vs[keep]
                    sn = len(js)
                if not sn:
                    pass
                elif self.sync_sink is not None:
                    # batched path: one (cids, eids, vals) bundle per
                    # gate per tick, feeding
                    # MT_SYNC_POSITION_YAW_ON_CLIENTS — resolved through
                    # the numpy slot mirrors (one gather + per-gate
                    # groupby) instead of per-record dict lookups, which
                    # at 1M-entity sync volumes would rival the device
                    # tick itself (the reference's per-entity Go loop,
                    # Entity.go:1208-1267, has the same shape)
                    # pinned staging (ISSUE 20): gather into the
                    # preallocated scratch (sn <= sync_cap by
                    # construction) — the boolean-masked selections
                    # below COPY, so the scratch never escapes this
                    # method
                    cids = np.take(self._mir_cid[shard], ws,
                                   out=self._scr_cid[:sn])
                    gates = np.take(self._mir_gate[shard], ws,
                                    out=self._scr_gate[:sn])
                    if self.mega is not None:
                        tiles = js // cfg.capacity
                        ok_sub = tiles < self.n_spaces
                        jeids = self._mir_eid[
                            np.minimum(tiles, self.n_spaces - 1),
                            js % cfg.capacity,
                        ]
                    else:
                        ok_sub = np.ones(len(js), bool)
                        jeids = np.take(self._mir_eid[shard], js,
                                        out=self._scr_eid[:sn])
                    ok = (cids != b"") & (jeids != b"") & ok_sub
                    for gate_id in np.unique(gates[ok]):
                        m = ok & (gates == gate_id)
                        self.sync_sink(
                            int(gate_id), cids[m], jeids[m], vs[m]
                        )
                else:
                    for w, j, v in zip(ws, js, vs):
                        we = self._owner_entity(shard, int(w))
                        je = self._owner_subject(shard, int(j))
                        if we is None or we.client is None or je is None:
                            continue
                        we.client.send({
                            "type": "sync", "eid": je.id,
                            "pos": [float(v[0]), float(v[1]), float(v[2])],
                            "yaw": float(v[3]),
                        })
            # device-side hot-attr deltas (kernel-mutated attrs)
            an = min(int(base.attr_n[shard]), cfg.attr_sync_cap)
            if an:
                es = np.asarray(base.attr_e[shard])[:an]
                cs = np.asarray(base.attr_i[shard])[:an]
                vs = np.asarray(base.attr_v[shard])[:an]
                slot_eid = self._slot_owner[shard].get
                dirty = self._dirty_attr_entities
                for slot, col, v in zip(es.tolist(), cs.tolist(),
                                        vs.tolist()):
                    e = entities.get(slot_eid(slot))
                    if e is None or e.slot is None:
                        continue
                    info = e._type_desc.hot_attr_by_col.get(col)
                    if info is None:
                        continue
                    name, aud = info
                    attrs = e.attrs
                    if isinstance(attrs._d.get(name),
                                  (MapAttr, ListAttr)):
                        # a hot attr shadowed by a tree node — take the
                        # orphaning slow path (same journal policy)
                        self._apply_device_attr(e, name, v, aud)
                        continue
                    attrs._d[name] = v
                    # inline _journal_wanted + _apply_device_attr's
                    # fast path (this loop runs at attr_sync_cap
                    # volumes; the call overhead alone was measured by
                    # tools/probe_fanout.py): journal ONLY deltas
                    # someone will receive
                    if aud is not None and (
                        e.client is not None
                        or (aud == "all_clients" and e.interested_by)
                    ):
                        dirty.setdefault(e.id, []).append(
                            AttrDelta((name,), "set", v))

        if self.mesh is not None and self.mega is None:
            self._process_arrivals(outs)

        # AOI-cap overflow gauges (ops.aoi with_stats): live worlds must
        # never degrade to nearest-k / dropped candidates SILENTLY (the
        # go-aoi sweep is exact at any density, Space.go:244-252). The
        # gauges are exposed every tick; the alarm is rate-limited.
        dem_max = int(np.max(base.aoi_demand_max))
        over_k = int(np.sum(base.aoi_over_k_rows))
        cell_max = int(np.max(base.aoi_cell_max))
        over_cap = int(np.sum(base.aoi_over_cap_cells))
        # interest-migration volume (TRUE demand — may exceed the
        # enter/leave caps, which the overflow warnings above already
        # alarm): the scenario runner reads these as its per-tick
        # migration gauges (battle-royale shrink = sustained churn)
        enters = int(np.sum(base.enter_n))
        leaves = int(np.sum(base.leave_n))
        opmon.expose("aoi_enter_events", enters)
        opmon.expose("aoi_leave_events", leaves)
        self.op_stats["aoi_enter_events"] = enters
        self.op_stats["aoi_leave_events"] = leaves
        # ... and what of it the host never saw: this tick's (op_stats,
        # like every gauge here) and since boot (/vars, /metrics)
        for kind, cnt in dropped.items():
            self.op_stats[f"aoi_{kind}_dropped"] = cnt
            if cnt:
                self.aoi_dropped[kind] += cnt
                self._m_aoi_dropped[kind].inc(cnt)
        opmon.expose("aoi_events_dropped", dict(self.aoi_dropped))
        opmon.expose("aoi_demand_max", dem_max)
        opmon.expose("aoi_over_k_rows", over_k)
        opmon.expose("aoi_cell_max", cell_max)
        opmon.expose("aoi_over_cap_cells", over_cap)
        self.op_stats["aoi_demand_max"] = dem_max
        self.op_stats["aoi_over_k_rows"] = over_k
        self.op_stats["aoi_cell_max"] = cell_max
        self.op_stats["aoi_over_cap_cells"] = over_cap
        self._m_aoi_demand.set(dem_max)
        self._m_aoi_cell.set(cell_max)
        reb = getattr(base, "aoi_rebuilt", None)
        if reb is not None:
            rebuilds = int(np.sum(reb))
            slack = float(np.min(base.aoi_skin_slack))
            if rebuilds:
                self._m_aoi_rebuild.inc(rebuilds)
            self._m_aoi_slack.set(slack)
            opmon.expose("aoi_rebuild_last", rebuilds)
            opmon.expose("aoi_skin_slack", slack)
            self.op_stats["aoi_rebuild_last"] = rebuilds
            self.op_stats["aoi_skin_slack"] = slack
        if over_k or over_cap:
            self._m_aoi_overflow.inc(over_k + over_cap)
        if (over_k or over_cap) and \
                self.tick_count - self._aoi_alarm_tick >= 64:
            self._aoi_alarm_tick = self.tick_count
            logger.warning(
                "AOI cap overflow: %d rows truncated to nearest-%d "
                "(demand max %d), %d cells past cell_cap=%d (occupancy "
                "max %d). Interest sets are degraded this tick. "
                "Re-provision: raise GridSpec.k above the demand max "
                "and/or cell_cap above the occupancy max (ini "
                "[gameN] aoi_k / aoi_cell_cap), or shard the hotspot "
                "(megaspace tiles / more spaces).",
                over_k, self.cfg.grid.k, dem_max,
                over_cap, self.cfg.grid.cell_cap, cell_max,
            )

        # release slots whose leave events have now been processed
        for shard, slot, expect in self._release_now:
            cur = self._slot_owner[shard].get(slot)
            if cur == expect:
                self._slot_clear(shard, slot)
                self._free[shard].add(slot)
            # forget destroyed host objects even when the slot was already
            # re-occupied by an arrival (cur != expect): destroy_entity
            # kept them alive only for this release point
            # ... unless a row of theirs still waits for its despawn
            # (a destroyed entity whose row hopped tiles meanwhile): its
            # watchers' leave events come with that one
            if expect is not None:
                e = self.entities.get(expect)
                if e is not None and e.destroyed and e.slot is None \
                        and not any(
                            self._slot_owner[sh_].get(sl_) == expect
                            for sh_, sl_ in self._staged_despawn):
                    self.entities.pop(expect, None)
        self._release_now = self._release_next
        self._release_next = []

    def _mega_collect_arrivals(self, outs) -> list[tuple]:
        """Megaspace: read the device's autonomous tile-migration records
        (old gid -> new slot). Unlike :meth:`_process_arrivals` there are
        no host-staged tags — the device migrates from position and the
        host follows (the dispatcher-table rewrite of
        ``DispatcherService.go:877-891`` with the device as the source of
        truth). Returns pending (new_shard, new_slot, old_sh, old_sl, eid)
        re-pointings; applied by :meth:`_mega_apply_arrivals` BETWEEN the
        leave and enter passes, because a new slot may be a row another
        entity was host-despawned from this very tick — its leave events
        must decode against the OLD owner, the arrival's enter events
        against the NEW one."""
        cap = self.cfg.capacity
        pending: list[tuple] = []
        for shard in range(self.n_spaces):
            an = int(outs.arr_n[shard])
            for t, s in zip(
                np.asarray(outs.arr_tag[shard])[:an],
                np.asarray(outs.arr_slot[shard])[:an],
            ):
                t, s = int(t), int(s)
                if t < 0 or s < 0:
                    continue
                old_sh, old_sl = divmod(t, cap)
                eid = self._slot_owner[old_sh].get(old_sl)
                if eid is not None:
                    pending.append((shard, s, old_sh, old_sl, eid))
        mdem = np.asarray(outs.migrate_demand)
        if (mdem > self.mega.migrate_cap).any():
            logger.warning(
                "megaspace migrate demand %d exceeds migrate_cap %d; "
                "surplus entities linger on the wrong tile this tick",
                int(mdem.max()), self.mega.migrate_cap,
            )
        hdem = np.asarray(outs.halo_demand)
        if (hdem > self.mega.halo_cap).any():
            logger.warning(
                "megaspace halo demand %d exceeds halo_cap %d; some "
                "cross-border neighbors invisible this tick",
                int(hdem.max()), self.mega.halo_cap,
            )
        # rows holding a ghost: the tick re-sorts only those, unless a
        # tile holds more than ghost_sort_rows and takes the full path
        gdem = np.asarray(outs.ghost_row_demand)
        full = int((gdem > self.mega.ghost_sort_rows).sum())
        if full:
            self.mega_ghost_full_path += full
            self._m_mega_ghost_full.inc(full)
        self._m_mega_ghost_rows.set(int(gdem.max()))
        opmon.expose("mega_ghost_rows", int(gdem.max()))
        opmon.expose("mega_ghost_full_path_total", self.mega_ghost_full_path)
        return pending

    def _claim_arrival_row(self, shard: int, slot: int, eid: str) -> None:
        """The device has put a migrated row into ``slot``: the host
        follows. A row the host handed to a spawn of its own since the
        dispatch (a handler the serve loop ran while the device
        computed; still staged, nothing on the device names it) gives
        way: that spawn moves to another free row."""
        cur = self._slot_owner[shard].get(slot)
        if cur is not None and cur != eid:
            for i, (sh_, sl_, _) in enumerate(self._staged_spawn):
                if (sh_, sl_) == (shard, slot):
                    self._move_staged_spawn(i, cur)
                    break
        self._slot_set(shard, slot, eid)
        self._free[shard].discard(slot)

    def _move_staged_spawn(self, i: int, eid: str) -> None:
        shard, slot, data = self._staged_spawn[i]
        z = self.entities.get(eid)
        holds = z is not None and (z.shard, z.slot) == (shard, slot)
        if self._free[shard]:
            new = self._free[shard].pop()
            self._staged_spawn[i] = (shard, new, data)
            self._restage_row((shard, slot), (shard, new))
            self._slot_set(shard, new, eid)
            if holds:
                z.slot = new
            return
        logger.error(
            "spawn of %s lost its row to an arrival and shard %d is "
            "full; parked in nil space", eid, shard)
        del self._staged_spawn[i]
        self._drop_staged_for(shard, slot)
        self._staged_despawn = [
            x for x in self._staged_despawn if x != (shard, slot)]
        if holds:
            z.slot = z.shard = None
            sp = z.space
            if sp is not None:
                sp.members.discard(eid)
                z.space = None
                self._enter_space_or_park(z, sp, data["pos"])

    def _restage_row(self, old: tuple[int, int],
                     new: tuple[int, int]) -> None:
        """Writes staged for row ``old`` since the dispatch follow the
        row's entity to ``new`` (the device moved it in the tick that
        was in flight, or an arrival took a staged spawn's row)."""
        if old in self._staged_pos:
            self._staged_pos[new] = self._staged_pos.pop(old)
        if self._batch_pos_any and self._batch_pos_mask[old]:
            self._batch_pos_mask[old] = False
            self._batch_pos_mask[new] = True
            self._batch_pos_vals[new] = self._batch_pos_vals[old]
        for name in ("_staged_despawn", "_staged_hot", "_staged_moving",
                     "_staged_client"):
            rows = getattr(self, name)
            if rows:
                setattr(self, name, [
                    new + x[2:] if x[:2] == old else x for x in rows])

    def _mega_apply_arrivals(self, pending: list[tuple], outs) -> None:
        for shard, s, old_sh, old_sl, eid in pending:
            # old slot keeps its owner mapping through THIS step's leave
            # events; released at the end of _process_outputs
            self._release_now.append((old_sh, old_sl, eid))
            self._claim_arrival_row(shard, s, eid)
            self._restage_row((old_sh, old_sl), (shard, s))
            e = self.entities.get(eid)
            if e is not None and (e.shard, e.slot) == (old_sh, old_sl):
                e.shard = shard
                e.slot = s
            else:
                # its entity left the row while it hopped tiles
                # (destroyed, or moved out of the space: by a hook of
                # the leave pass above, or by a handler the serve loop
                # ran while the device computed): drop the arrived row.
                # The despawn staged for the old row names it by now.
                if (shard, s) not in self._staged_despawn:
                    self._staged_despawn.append((shard, s))
                self._slot_abandon(shard, s)
        total_dropped = int(np.asarray(outs.migrate_dropped).sum())
        if total_dropped:
            self._mega_reconcile_dropped(total_dropped)

    def _mega_reconcile_dropped(self, total_dropped: int) -> None:
        """A border-crosser whose destination tile was full departed its
        source row but never arrived (no record). Without reconciliation
        its host object keeps addressing a dead row that a later arrival
        may re-occupy — staged writes would then corrupt another entity.
        Find the orphans by comparing host mappings against device
        liveness (one [n_dev, N] readback, only on this alarmed path) and
        respawn them from host knowledge."""
        logger.error(
            "megaspace dropped %d border-crossing entities (destination "
            "tiles full); respawning from host state — raise capacity",
            total_dropped,
        )
        snap = self._dget({
            "alive": self.state.alive,
            "moving": self.state.npc_moving,
            "yaw": self.state.yaw,
        })
        alive = np.asarray(snap["alive"])
        expected_dead = {
            (sh_, sl_) for sh_, sl_, _ in self._release_now
        } | set(self._staged_despawn)
        orphans: list[tuple[int, int, str]] = []
        for sh_ in range(self.n_spaces):
            for sl_, eid in self._slot_owner[sh_].items():
                if alive[sh_, sl_] or (sh_, sl_) in expected_dead:
                    continue
                e = self.entities.get(eid)
                if e is None or e.shard != sh_ or e.slot != sl_:
                    continue
                orphans.append((sh_, sl_, eid))
        for sh_, sl_, eid in orphans:
            e = self.entities[eid]
            last_pos = tuple(self.read_pos(sh_, sl_).tolist())
            moving = bool(snap["moving"][sh_, sl_])
            self._slot_clear(sh_, sl_)
            self._free[sh_].add(sl_)
            e.slot = None
            e.shard = None
            if e.destroyed:
                self.entities.pop(eid, None)
                continue
            sp = e.space
            if sp is not None:
                sp.members.discard(eid)
                e.space = None
                pos = e._pending_pos or last_pos
                # the dead row's device-only state (heading, mover flag)
                # travels with the respawn; velocity regenerates from the
                # behavior on the next tick
                if self._enter_space_or_park(e, sp, pos, moving=moving):
                    e._pending_yaw = float(snap["yaw"][sh_, sl_])
                    self.stage_pos_set(e)

    def _process_arrivals(self, outs) -> None:
        """Mesh path: re-point migrated entities from the arrival records
        (the analog of the dispatcher rewriting entityDispatchInfos,
        ``DispatcherService.go:877-891``) and reconcile requests that did
        not complete (capacity backpressure)."""
        resolved: set[int] = set()
        for shard in range(self.n_spaces):
            an = int(outs.arr_n[shard])
            for t, s in zip(
                np.asarray(outs.arr_tag[shard])[:an],
                np.asarray(outs.arr_slot[shard])[:an],
            ):
                info = self._migrate_tags.get(int(t))
                if info is None:
                    continue
                resolved.add(int(t))
                eid, src_sh, src_sl = info
                e = self.entities.get(eid)
                # source slot: owner cleared after its leave events fire
                # NEXT step (the departure happened inside this step)
                self._release_next.append((src_sh, src_sl, eid))
                if e is None:
                    continue
                e._migrating = None
                e.slot = int(s)
                e.shard = shard
                self._claim_arrival_row(shard, int(s), eid)
                if e.destroyed:
                    # destroyed mid-flight after the row already moved:
                    # drop the arrived row
                    self._staged_despawn.append((shard, int(s)))
                    e.slot = None
                    e.shard = None
                    continue
                # the arrived row carries source-tick pos/attrs; stage the
                # requested destination position and any attr writes made
                # during the migration window
                if e._pending_pos is not None:
                    self.stage_pos_set(e)
                for name, col in e._type_desc.hot_attrs.items():
                    v = e.attrs.get(name)
                    if isinstance(v, (int, float)) \
                            and not isinstance(v, bool):
                        self.stage_hot(e, col, float(v))
                e.OnMigrateIn()
                e.OnEnterSpace()
                tgt_id = self._shard_space[shard]
                tgt = self.spaces.get(tgt_id) if tgt_id else None
                if tgt is not None:
                    tgt.OnEntityEnterSpace(e)
            dropped = int(np.asarray(outs.migrate_dropped[shard]))
            if dropped:
                logger.warning("shard %d dropped %d migrants", shard, dropped)

        # unresolved requests: either the emigrant stayed behind
        # (pack capacity) or it was dropped at a full destination.
        # ONE batched alive fetch for the whole loop — per-entity reads
        # would pay the transfer (or, under multihost, a DCN allgather)
        # once per migrant
        alive_np = None
        if any(t not in resolved for t in self._migrate_tags):
            alive_np = self._dget(self.state.alive)
        for t, (eid, src_sh, src_sl) in self._migrate_tags.items():
            if t in resolved:
                continue
            e = self.entities.get(eid)
            if e is None:
                continue
            if e.destroyed:
                # destroyed while unresolved: drop whichever row survived
                # and forget the entity
                if bool(alive_np[src_sh, src_sl]):
                    self._staged_despawn.append((src_sh, src_sl))
                else:
                    self._slot_clear(src_sh, src_sl)
                    self._free[src_sh].add(src_sl)
                    self.entities.pop(eid, None)
                e.slot = None
                e.shard = None
                e._migrating = None
                continue
            still_there = bool(alive_np[src_sh, src_sl])
            src_id = self._shard_space[src_sh]
            src = self.spaces.get(src_id) if src_id else None
            if still_there and src is not None:
                # stayed behind (pack capacity): revert the host-side
                # space move and retry next tick
                intended = e.space
                if intended is not None:
                    intended.members.discard(eid)
                e.space = src
                src.members.add(eid)
                e.slot = src_sl
                e.shard = src_sh
                e._migrating = None
                logger.warning("migration of %s deferred (pack cap)", eid)
                if intended is not None and intended.id in self.spaces:
                    pos = e._pending_pos or (0.0, 0.0, 0.0)
                    self.post_q.post(
                        lambda e=e, sid=intended.id, pos=pos: (
                            None if e.destroyed
                            else self.enter_space(e, sid, pos)
                        )
                    )
            else:
                # departed but dropped at destination: respawn from host
                # knowledge (hot attrs re-derived from the attr tree)
                logger.error(
                    "migrant %s dropped at full destination; respawning",
                    eid,
                )
                self._slot_clear(src_sh, src_sl)
                self._free[src_sh].add(src_sl)
                tgt = e.space
                e.slot = None
                e.shard = None
                e._migrating = None
                if tgt is not None:
                    tgt.members.discard(eid)
                    e.space = None
                    self._enter_space_or_park(
                        e, tgt, e._pending_pos or (0.0, 0.0, 0.0)
                    )
        self._migrate_tags = {}

    # ==================================================================
    # device reads
    # ==================================================================
    def _dget(self, x):
        """Device fetch that works in BOTH controller modes: plain
        device_get on a single controller; process_allgather under
        multi-controller (a non-addressable shard's value can only cross
        hosts through a collective, and the SPMD contract guarantees
        every controller reaches this call at the same point)."""
        if self._multihost:
            from jax.experimental import multihost_utils

            # tiled=True: global sharded arrays come back as their
            # assembled global value (no stacked process axis)
            return multihost_utils.process_allgather(x, tiled=True)
        return jax.device_get(x)

    def read_pos(self, shard: int, slot: int) -> np.ndarray:
        if self._pos_cache is None:
            self._pos_cache = self._dget(self.state.pos)
        return self._pos_cache[shard, slot]

    def read_yaw(self, shard: int, slot: int) -> float:
        if self._yaw_cache is None:
            self._yaw_cache = self._dget(self.state.yaw)
        return float(self._yaw_cache[shard, slot])
