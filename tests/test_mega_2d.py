"""2D megaspace tiling (VERDICT #9): the XZ plane tiled over a (4, 2)
device grid with 8-neighbor halo exchange — corners included via the
two-phase x-then-z ghost shipment. At 64 devices over a square world,
1D x-strips get thinner than the AOI radius; 2D tiles are the realistic
BASELINE config-4 layout."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from goworld_tpu.core.state import WorldConfig
from goworld_tpu.entity.entity import Entity
from goworld_tpu.entity.manager import World
from goworld_tpu.entity.space import Space
from goworld_tpu.ops.aoi import GridSpec
from goworld_tpu.parallel.mesh import make_mesh

TX, TZ = 4, 2
TILE_W, TILE_D = 60.0, 60.0
RADIUS = 10.0


class Walker(Entity):
    pass


class MegaArena(Space):
    pass


def _world_2d(capacity=96):
    cfg = WorldConfig(
        capacity=capacity,
        grid=GridSpec(
            radius=RADIUS,
            extent_x=TILE_W + 2 * RADIUS,
            extent_z=TILE_D + 2 * RADIUS,
            k=32, cell_cap=64, row_block=capacity,
        ),
        npc_speed=30.0, turn_prob=0.2,
        enter_cap=8192, leave_cap=8192, sync_cap=8192,
    )
    mesh = make_mesh(TX * TZ)
    w = World(cfg, n_spaces=TX * TZ, mesh=mesh, megaspace=True,
              halo_cap=64, migrate_cap=32, mega_shape=(TX, TZ))
    w.register_space("MegaArena", MegaArena, megaspace=True)
    w.register_entity("Walker", Walker)
    w.create_nil_space()
    return w


def _oracle_check(w: World, arena):
    ents = [
        w.entities[eid] for eid in arena.members
        if w.entities[eid].slot is not None
    ]
    pos = np.asarray(w.state.pos)
    coords = {
        e.id: (float(pos[e.shard, e.slot][0]),
               float(pos[e.shard, e.slot][2]))
        for e in ents
    }
    for e in ents:
        ex, ez = coords[e.id]
        want = {
            o.id for o in ents
            if o.id != e.id
            and max(abs(coords[o.id][0] - ex), abs(coords[o.id][1] - ez))
            <= RADIUS
        }
        assert e.interested_in == want, (
            f"{e.id} tile {e.shard} at ({ex:.1f},{ez:.1f}): "
            f"{len(e.interested_in)} vs {len(want)} expected"
        )


def test_2d_corner_visibility():
    """Four entities around a 4-tile corner point — every pair crosses a
    tile boundary, the diagonal pair ONLY via the corner exchange."""
    w = _world_2d()
    arena = w.create_space("MegaArena")
    cx, cz = TILE_W, TILE_D  # the (0,0)/(1,0)/(0,1)/(1,1) corner point
    quad = [
        w.create_entity("Walker", space=arena, pos=(cx - 3, 0, cz - 3)),
        w.create_entity("Walker", space=arena, pos=(cx + 3, 0, cz - 3)),
        w.create_entity("Walker", space=arena, pos=(cx - 3, 0, cz + 3)),
        w.create_entity("Walker", space=arena, pos=(cx + 3, 0, cz + 3)),
    ]
    for _ in range(2):
        w.tick()
    tiles = {e.shard for e in quad}
    assert len(tiles) == 4, f"quad not spread over 4 tiles: {tiles}"
    ids = {e.id for e in quad}
    for e in quad:
        assert e.interested_in == ids - {e.id}, (
            f"corner entity on tile {e.shard} sees "
            f"{len(e.interested_in)}/3 of its diagonal quad"
        )
    _oracle_check(w, arena)


def test_2d_border_churn_matches_oracle():
    w = _world_2d()
    arena = w.create_space("MegaArena")
    rng = np.random.default_rng(7)
    ents = []
    spawn_tile = {}
    for _ in range(TX * TZ * 30):
        x = float(rng.uniform(0, TILE_W * TX))
        z = float(rng.uniform(0, TILE_D * TZ))
        e = w.create_entity("Walker", space=arena, pos=(x, 0, z),
                            moving=True)
        ents.append(e)
        spawn_tile[e.id] = e.shard
    for _ in range(10):
        w.tick()
        outs = w.last_outputs
        assert int(np.asarray(outs.migrate_dropped).sum()) == 0
        assert (np.asarray(outs.halo_demand) <= 64).all()
        _oracle_check(w, arena)
    # host tiles track device positions in BOTH axes
    pos = np.asarray(w.state.pos)
    for e in ents:
        x, z = float(pos[e.shard, e.slot][0]), float(pos[e.shard, e.slot][2])
        ix = max(0, min(TX - 1, int(x // TILE_W)))
        iz = max(0, min(TZ - 1, int(z // TILE_D)))
        assert e.shard == ix * TZ + iz, \
            f"{e.id}: host tile {e.shard} != ({ix},{iz}) for ({x},{z})"
    crossings = sum(1 for e in ents if e.shard != spawn_tile[e.id])
    assert crossings > 0, "no tile border was ever crossed"
    assert sum(len(o) for o in w._slot_owner) == len(ents)


def test_2d_z_crossing_keeps_identity():
    """Teleport across a Z border (the new axis): identity, attrs and
    interest survive exactly like the 1D x-crossing."""
    w = _world_2d()
    arena = w.create_space("MegaArena")
    a = w.create_entity("Walker", space=arena, pos=(30.0, 0, 57.0))
    b = w.create_entity("Walker", space=arena, pos=(30.0, 0, 55.0))
    a.attrs["hp"] = 5
    w.tick()
    assert a.shard == 0 and b.shard == 0
    assert a.interested_in == {b.id}
    a.set_position((30.0, 0, 63.0))  # z crosses into tile (0,1)
    w.tick()
    assert a.shard == 1, f"z-crossing did not hop tiles (shard={a.shard})"
    assert a.attrs["hp"] == 5
    assert a.interested_in == {b.id}, "interest lost across the z border"
    assert b.interested_in == {a.id}


def test_mega_config_validates_2d():
    from goworld_tpu.parallel.megaspace import MegaConfig

    cfg = WorldConfig(
        capacity=16,
        grid=GridSpec(radius=10.0, extent_x=80.0, extent_z=80.0,
                      k=8, cell_cap=16, row_block=16),
    )
    with pytest.raises(ValueError, match="mesh_shape"):
        MegaConfig(cfg=cfg, n_dev=8, tile_w=60.0, mesh_shape=(3, 2),
                   tile_d=60.0)
    with pytest.raises(ValueError, match="tile_d"):
        MegaConfig(cfg=cfg, n_dev=8, tile_w=60.0, mesh_shape=(4, 2))
    with pytest.raises(ValueError, match="extent_z"):
        MegaConfig(cfg=cfg, n_dev=8, tile_w=60.0, mesh_shape=(4, 2),
                   tile_d=99.0)
    MegaConfig(cfg=cfg, n_dev=8, tile_w=60.0, mesh_shape=(4, 2),
               tile_d=60.0)  # valid


# --- neighbour naming: ghost-holding rows only, or every row -------------

def _churn_world(shape):
    """Four tiles under border churn: 2x2 tiles, or four 1-D x strips
    (``shape`` None). 60 movers a tile at radius 10 on 60-wide tiles,
    so every tile holds rows that see a ghost on every tick."""
    two_d = shape is not None
    extent_z = TILE_D + 2 * RADIUS if two_d else TILE_D
    cfg = WorldConfig(
        capacity=96,
        grid=GridSpec(radius=RADIUS, extent_x=TILE_W + 2 * RADIUS,
                      extent_z=extent_z, k=32, cell_cap=64, row_block=96),
        npc_speed=30.0, turn_prob=0.2,
        enter_cap=8192, leave_cap=8192, sync_cap=8192,
    )
    w = World(cfg, n_spaces=4, mesh=make_mesh(4), megaspace=True,
              halo_cap=64, migrate_cap=32, mega_shape=shape)
    w.register_space("MegaArena", MegaArena, megaspace=True)
    w.register_entity("Walker", Walker)
    w.create_nil_space()
    arena = w.create_space("MegaArena")
    world_x = TILE_W * (2 if two_d else 4)
    world_z = TILE_D * 2 if two_d else TILE_D
    rng = np.random.default_rng(11)
    for _ in range(240):
        w.create_entity("Walker", space=arena, moving=True, pos=(
            float(rng.uniform(0, world_x)), 0.0,
            float(rng.uniform(0, world_z))))
    return w


def _ghost_holding_rows(w) -> np.ndarray:
    """Per tile, rows whose global-id list names a row of another tile:
    read from the device's lists, not from the tick's own count."""
    nbr = np.asarray(w.state.nbr)
    n_dev, cap, _ = nbr.shape
    tile = np.arange(n_dev)[:, None, None]
    other = (nbr != n_dev * cap) & (nbr // cap != tile)
    return other.any(axis=2).sum(axis=1)


def _run_churn(shape, sort_rows=None, ticks=8):
    """Tick the churn world; per tick the state and outputs as numpy
    leaves, the rows holding a ghost per tile, and how many tiles the
    full-path counter took. ``sort_rows`` shrinks the fast path's row
    set for this run only."""
    from goworld_tpu.parallel.megaspace import MegaConfig

    with pytest.MonkeyPatch.context() as mp:
        if sort_rows is not None:
            mp.setattr(MegaConfig, "ghost_sort_rows",
                       property(lambda self: sort_rows))
        w = _churn_world(shape)
        rows_cap = w.mega.ghost_sort_rows
        run = []
        for _ in range(ticks):
            before = w.mega_ghost_full_path
            w.tick()
            ghost = _ghost_holding_rows(w)
            np.testing.assert_array_equal(
                ghost, np.asarray(w.last_outputs.ghost_row_demand))
            run.append((
                [np.asarray(x) for x in jax.tree.leaves(w.state)],
                [np.asarray(x) for x in jax.tree.leaves(w.last_outputs)],
                ghost,
                w.mega_ghost_full_path - before,
            ))
    return rows_cap, run


@functools.cache
def _full_path_run(shape):
    """Today's formulation: every tile of every tick names all its
    rows by the gather and sorts them (a row set of one, and every
    tile holds more ghost rows than that on every tick)."""
    _, run = _run_churn(shape, sort_rows=1)
    for _, _, ghost, full in run:
        assert (ghost > 1).all(), ghost
        assert full == 4
    return run


@pytest.mark.parametrize("shape,rows", [
    ((2, 2), "default"),      # ghost rows only, on every tile
    ((2, 2), "median"),       # some tiles one path, some the other
    (None, "default"),        # 1-D strips: two ghost strips a tile
])
def test_ghost_row_naming_matches_full_path(shape, rows):
    """The fast path (local ids by arithmetic, gather and re-sort only
    on the rows that hold a ghost) gives the state, the enter/leave
    pairs, the sync records and ``delta_rows_n`` bit for bit as the
    all-rows gather and sort, on every tick; the full-path counter
    counts exactly the tiles holding more such rows than the set."""
    ref = _full_path_run(shape)
    sort_rows = None
    if rows == "median":
        sort_rows = int(np.median(np.concatenate([g for *_, g, _ in ref])))
    rows_cap, run = _run_churn(shape, sort_rows)
    fired = 0
    for t, ((st, out, ghost, full), (st0, out0, ghost0, _)) in \
            enumerate(zip(run, ref)):
        assert all(np.array_equal(a, b) for a, b in zip(st, st0)), \
            f"tick {t}: state differs from the full path"
        assert all(np.array_equal(a, b) for a, b in zip(out, out0)), \
            f"tick {t}: outputs differ from the full path"
        np.testing.assert_array_equal(ghost, ghost0)
        assert full == int((ghost > rows_cap).sum()), (t, ghost, rows_cap)
        fired += full
    if rows == "median":
        assert 0 < fired < 4 * len(run), "both paths must run"
    else:
        assert fired == 0
    # the host's gauge reads the last tick's busiest tile
    from goworld_tpu.utils import opmon

    assert opmon.vars()["mega_ghost_rows"] == int(run[-1][2].max())
