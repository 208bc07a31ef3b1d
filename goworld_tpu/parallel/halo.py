"""Ring/halo AOI ghost exchange for Spaces sharded across devices.

The reference cannot shard one Space: a space lives wholly on one game
process and user code caps its population (``doc.go:12-14``,
``SpaceService.go:14`` <=100 avatars/space). The rebuild's flagship upgrade
(``SURVEY.md#5.7``) is a Space whose entity SoA spans the mesh as spatial
tiles along x; AOI then needs each tile to see the ``radius``-wide strips of
its left/right neighbor tiles. Structurally identical to ring attention's
block rotation: bounded ghost buffers rotate over ICI with ``ppermute``
while each shard computes locally.

Ghost buffers are fixed capacity ``halo_cap``; entities in a boundary strip
beyond the cap are dropped from the neighbor's view that tick (the AOI-limit
tradeoff again — size halo_cap for the worst expected strip density).

Two shipping impls (``halo_impl`` knob on :class:`MegaConfig`):

* ``"ppermute"`` (default): one ``lax.ppermute`` per payload lane per
  direction. Collectives are barriered — every device enters the
  exchange together, so the halo serializes against the whole tick.
* ``"async"``: the Pallas ``make_async_remote_copy`` pattern
  (SNIPPETS.md [2] / the jax distributed-Pallas guide). Each device
  DMAs ONE packed i32 strip buffer straight into its neighbor's
  receive buffer — no mesh-wide barrier, only a sender/receiver
  semaphore pair per edge, so the copy can overlap every part of the
  tick that does not consume ghosts (behavior, integrate, the migrate
  pack: the ghost block's only consumer is the AOI window gather).
  The packed payload is dirty-only: pos (12 B) + one meta word
  (gid/dirty/valid bits, 4 B) always ship, and the yaw lane (4 B) is
  zero unless the row is dirty — 16 B + 4 B·dirty versus the 22 B/row
  of the 5-lane ppermute path in the modeled ICI budget
  (``devprof.roofline_model_bytes_multichip``). Off-TPU the kernel
  runs in interpret mode behind
  :func:`goworld_tpu.ops.pallas_compat.resolve_interpret` (loud
  one-time warning, never a CPU default).

Both impls are bit-identical: same ghost blocks, same demand gauges
(tests/test_halo_async.py holds them exact across dirty/visible
permutations and halo_cap overflow).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from goworld_tpu.ops.extract import bounded_extract
from goworld_tpu.ops.scopes import scoped

HALO_IMPLS = ("ppermute", "async")

# packed meta word: (gid + 1) << 2 | dirty << 1 | valid. gid ∈ [-1,
# gid_sentinel], so the +1 shift keeps it non-negative and the pack is
# exact while gid_sentinel + 1 < 2^29 (1M/chip × 64 chips = 2^26 —
# plenty; megaspace.py guards the bound at build time).
_META_GID_BITS = 29


def meta_gid_bound() -> int:
    """Largest gid the async meta word can carry exactly."""
    return (1 << _META_GID_BITS) - 2


def _pack_strip(gpos, gyaw, gdirty, gvalid, ggid) -> jax.Array:
    """One i32[H, 5] buffer per strip: cols 0-2 pos bits, col 3 yaw
    bits, col 4 meta. f32 -> i32 is a bitcast (exact roundtrip); the
    meta word packs gid/dirty/valid."""
    meta = ((ggid + 1) << 2) \
        | (gdirty.astype(jnp.int32) << 1) \
        | gvalid.astype(jnp.int32)
    return jnp.concatenate([
        lax.bitcast_convert_type(gpos, jnp.int32),
        lax.bitcast_convert_type(gyaw, jnp.int32)[:, None],
        meta[:, None],
    ], axis=1)


def _unpack_strip(buf: jax.Array):
    pos = lax.bitcast_convert_type(buf[:, 0:3], jnp.float32)
    yaw = lax.bitcast_convert_type(buf[:, 3], jnp.float32)
    meta = buf[:, 4]
    return (
        pos,
        yaw,
        ((meta >> 1) & 1).astype(bool),
        (meta & 1).astype(bool),
        (meta >> 2) - 1,
    )


def _async_ship(axis: str, n_dev: int, shift: int, buf: jax.Array,
                recv_ok) -> jax.Array:
    """DMA ``buf`` to device ``(d + shift) % n_dev`` with one Pallas
    ``make_async_remote_copy`` per device — the SNIPPETS.md [2] ring
    pattern. The ring wraps so no device conditionally skips its send
    (conditional DMAs deadlock interpret mode); non-participating
    receivers (``recv_ok`` False — world-edge tiles) zero their block
    instead, reproducing ``ppermute``'s fill exactly."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from goworld_tpu.ops.pallas_compat import resolve_interpret

    def kernel(in_ref, out_ref, send_sem, recv_sem):
        my_id = lax.axis_index(axis)
        dst = lax.rem(my_id + shift + n_dev, n_dev)
        op = pltpu.make_async_remote_copy(
            src_ref=in_ref, dst_ref=out_ref,
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        op.start()
        op.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        grid_spec=grid_spec,
        interpret=resolve_interpret("halo_async"),
    )(buf)
    return jnp.where(recv_ok, out, 0)


def _ship(axis: str, n_dev: int, shift: int, perm, pack, recv_ok,
          impl: str):
    """Ship one packed strip tuple ``(pos, yaw, dirty, valid, gid)``
    ``shift`` devices along the flat axis. ``perm`` is the explicit
    non-periodic (src, dst) list the ppermute impl uses; the async impl
    rides the periodic ring (every device sends — conditional DMAs
    would deadlock interpret mode) and non-participating receivers
    (``recv_ok`` False — world-edge tiles) zero their block instead,
    reproducing ``ppermute``'s fill exactly."""
    if impl == "async":
        buf = _pack_strip(*pack)
        return _unpack_strip(_async_ship(axis, n_dev, shift, buf,
                                         recv_ok))
    if impl != "ppermute":
        raise ValueError(
            f"halo_impl {impl!r} not in {HALO_IMPLS}"
        )
    return jax.tree.map(lambda t: lax.ppermute(t, axis, perm), pack)


@scoped("gw.halo")
def exchange_halo(
    axis: str,
    n_dev: int,
    pos: jax.Array,        # f32[N, 3] (global coords)
    yaw: jax.Array,
    dirty: jax.Array,      # bool[N]
    alive: jax.Array,
    tile_w: float,
    radius: float,
    halo_cap: int,
    impl: str = "ppermute",
):
    """Ship boundary strips to lateral neighbor tiles.

    Returns a ghost block of size 2*halo_cap (left-neighbor ghosts then
    right-neighbor ghosts): (gpos f32[2H,3], gyaw f32[2H], gdirty bool[2H],
    gvalid bool[2H], ggid i32[2H] global entity ids = owner_dev * N + slot),
    plus ``strip_demand`` i32: the true occupancy of this shard's fuller
    boundary strip (host alarm when it exceeds halo_cap — ghosts beyond the
    cap were invisible to the neighbor tile this tick).

    The yaw lane ships dirty-gated (zero for clean rows) under BOTH
    impls: sync collection only ever reads the yaw of dirty subjects,
    so the ghost outputs are consumer-invariant and the two impls stay
    bit-identical.
    """
    n = pos.shape[0]
    d = lax.axis_index(axis)
    tile_min = d.astype(jnp.float32) * tile_w
    x = pos[:, 0]

    def pack(mask):
        flat, valid, demand = bounded_extract(mask, halo_cap)
        slots = jnp.where(valid, flat, n - 1)
        sel_dirty = dirty[slots] & valid
        return (
            jnp.where(valid[:, None], pos[slots], 0.0),
            jnp.where(sel_dirty, yaw[slots], 0.0),
            sel_dirty,
            valid,
            jnp.where(valid, d * n + slots, -1),
        ), demand

    left_pack, left_demand = pack(alive & (x < tile_min + radius))
    right_pack, right_demand = pack(alive & (x >= tile_min + tile_w - radius))
    # edge tiles don't ship their outward strip — exclude it from the
    # capacity alarm so a crowd at the world border can't trigger a false
    # "widen halo_cap" recompile
    strip_demand = jnp.maximum(
        jnp.where(d > 0, left_demand, 0),
        jnp.where(d < n_dev - 1, right_demand, 0),
    )

    # my left strip is a ghost for tile d-1; my right strip for tile d+1.
    # Non-periodic: edge tiles receive zeros (gvalid False).
    to_left = [(i, i - 1) for i in range(1, n_dev)]
    to_right = [(i, i + 1) for i in range(n_dev - 1)]
    from_right = _ship(axis, n_dev, -1, to_left, left_pack,
                       d < n_dev - 1, impl)
    from_left = _ship(axis, n_dev, +1, to_right, right_pack, d > 0,
                      impl)

    gpos = jnp.concatenate([from_left[0], from_right[0]])
    gyaw = jnp.concatenate([from_left[1], from_right[1]])
    gdirty = jnp.concatenate([from_left[2], from_right[2]])
    gvalid = jnp.concatenate([from_left[3], from_right[3]])
    ggid = jnp.concatenate([from_left[4], from_right[4]])
    # normalize invalid rows' gid to 0 (ppermute edge fill / async
    # zero block / packed -1 all collapse): consumers gate on gvalid,
    # and one canonical fill keeps the impls bit-identical
    ggid = jnp.where(gvalid, ggid, 0)
    return gpos, gyaw, gdirty, gvalid, ggid, strip_demand


@scoped("gw.halo")
def exchange_halo_2d(
    axis: str,
    shape: tuple[int, int],   # (tx, tz) device grid over the flat axis
    n_per_dev: int,
    pos: jax.Array,           # f32[N, 3] (global coords)
    yaw: jax.Array,
    dirty: jax.Array,
    alive: jax.Array,
    tile_w: float,            # x tile width
    tile_d: float,            # z tile depth
    radius: float,
    halo_cap: int,
    impl: str = "ppermute",
):
    """Two-phase 8-neighbor halo for 2D (XZ) tiling.

    Device ``d`` owns tile ``(ix, iz) = (d // tz, d % tz)``. Phase 1
    ships the west/east boundary strips laterally; phase 2 ships the
    north/south strips of the COMBINED region (local + phase-1 ghosts),
    so corner neighbors arrive transitively — the classic 2-phase halo
    that avoids 4 extra diagonal transfers. Ghost block = 4 * halo_cap
    rows (west, east, north, south — the z-phase buffers carry the
    corners). Per-strip capacity overflow drops entities beyond the cap
    in slot order (not by distance) from the neighbor's view that tick —
    same contract as the 1D exchange; size halo_cap for the worst
    expected strip density.

    Returns (gpos[4H,3], gyaw[4H], gdirty[4H], gvalid[4H], ggid[4H],
    strip_demand) — strip_demand is the max true occupancy over this
    shard's inward-facing strips (alarm when > halo_cap). The yaw lane
    ships dirty-gated like the 1D exchange.
    """
    tx, tz = shape
    n = pos.shape[0]
    d = lax.axis_index(axis)
    ix = d // tz
    iz = d % tz
    tmin_x = ix.astype(jnp.float32) * tile_w
    tmin_z = iz.astype(jnp.float32) * tile_d
    x = pos[:, 0]
    z = pos[:, 2]
    local_gid = d * n_per_dev + jnp.arange(n, dtype=jnp.int32)

    def pack(mask, src_pos, src_yaw, src_dirty, src_gid):
        m = src_pos.shape[0]
        flat, valid, demand = bounded_extract(mask, halo_cap)
        slots = jnp.where(valid, flat, m - 1)
        sel_dirty = src_dirty[slots] & valid
        return (
            jnp.where(valid[:, None], src_pos[slots], 0.0),
            jnp.where(sel_dirty, src_yaw[slots], 0.0),
            sel_dirty,
            valid,
            jnp.where(valid, src_gid[slots], -1),
        ), demand

    # ---- phase 1: x strips over the flat axis (stride tz) -------------
    west_pack, west_dem = pack(
        alive & (x < tmin_x + radius), pos, yaw, dirty, local_gid
    )
    east_pack, east_dem = pack(
        alive & (x >= tmin_x + tile_w - radius), pos, yaw, dirty,
        local_gid,
    )
    n_dev = tx * tz
    to_west = [(i, i - tz) for i in range(n_dev) if i // tz > 0]
    to_east = [(i, i + tz) for i in range(n_dev) if i // tz < tx - 1]
    from_east = _ship(axis, n_dev, -tz, to_west, west_pack,
                      ix < tx - 1, impl)
    from_west = _ship(axis, n_dev, +tz, to_east, east_pack, ix > 0,
                      impl)

    # ---- phase 2: z strips of local + phase-1 ghosts ------------------
    cpos = jnp.concatenate([pos, from_west[0], from_east[0]])
    cyaw = jnp.concatenate([yaw, from_west[1], from_east[1]])
    cdirty = jnp.concatenate([dirty, from_west[2], from_east[2]])
    cvalid = jnp.concatenate([alive, from_west[3], from_east[3]])
    cgid = jnp.concatenate([local_gid, from_west[4], from_east[4]])
    cz = cpos[:, 2]
    north_pack, north_dem = pack(
        cvalid & (cz < tmin_z + radius), cpos, cyaw, cdirty, cgid
    )
    south_pack, south_dem = pack(
        cvalid & (cz >= tmin_z + tile_d - radius), cpos, cyaw, cdirty,
        cgid,
    )
    to_north = [(i, i - 1) for i in range(n_dev) if i % tz > 0]
    to_south = [(i, i + 1) for i in range(n_dev) if i % tz < tz - 1]
    from_south = _ship(axis, n_dev, -1, to_north, north_pack,
                       iz < tz - 1, impl)
    from_north = _ship(axis, n_dev, +1, to_south, south_pack, iz > 0,
                       impl)

    gpos = jnp.concatenate(
        [from_west[0], from_east[0], from_north[0], from_south[0]]
    )
    gyaw = jnp.concatenate(
        [from_west[1], from_east[1], from_north[1], from_south[1]]
    )
    gdirty = jnp.concatenate(
        [from_west[2], from_east[2], from_north[2], from_south[2]]
    )
    gvalid = jnp.concatenate(
        [from_west[3], from_east[3], from_north[3], from_south[3]]
    )
    ggid = jnp.concatenate(
        [from_west[4], from_east[4], from_north[4], from_south[4]]
    )
    ggid = jnp.where(gvalid, ggid, 0)
    # inward-facing strips only: world-edge outward strips never ship
    strip_demand = jnp.max(jnp.stack([
        jnp.where(ix > 0, west_dem, 0),
        jnp.where(ix < tx - 1, east_dem, 0),
        jnp.where(iz > 0, north_dem, 0),
        jnp.where(iz < tz - 1, south_dem, 0),
    ]))
    return gpos, gyaw, gdirty, gvalid, ggid, strip_demand
