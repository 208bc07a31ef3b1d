"""A world tiled over several chips, as the harness reads it from a
configuration: the shape and its row numbers (world.py), the mix that
walks over the borders (generators/orbit.py ``border_share``), and the
checks that judge the whole world under one row number (reduce.py).
No socket, no program.
"""
import hashlib
import json
import os

import numpy as np
import pytest

import reduce as R
import work
from generators import orbit
from reference import neighbourhoods
from world import Shape, tiles

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


# ---- the shape ----------------------------------------------------------
def test_shape_of_both_configurations():
    one = Shape(load("configs", "open-world-tile"))
    assert (one.mega, one.tiles, one.borders) == (False, 1,
                                                  {"x": [], "z": []})
    assert one.tile_of(3.0, 10450.0) == 0
    four = Shape(load("configs", "open-world-2x2"))
    assert (four.mega, four.tx, four.tz, four.tiles) == (True, 2, 2, 4)
    assert four.capacity == one.capacity == 131072
    # two tiles of the tile's side, the tile's density
    assert four.tile_w == four.tile_d == one.extent_x == 10451.0
    assert four.borders == {"x": [10451.0], "z": [10451.0]}
    # device d owns tile (ix, iz) = (d // tz, d % tz); a row ON the
    # border belongs to the higher tile; the world's edge is clipped
    x = np.array([0.0, 10450.9, 10451.0, 20902.0, 30000.0, 5.0])
    z = np.array([0.0, 10451.0, 10450.9, 20902.0, -4.0, 20000.0])
    assert four.tile_of(x, z).tolist() == [0, 1, 2, 3, 2, 1]
    assert four.tile_of(10451.0, 3.0) == 2
    assert four.border_distance(np.array([10401.0, 3.0]),
                                np.array([3.0, 10461.0])).tolist() \
        == [50.0, 10.0]
    assert tiles(load("configs", "open-world-2x2")) == 4
    assert tiles({"world": {}}) == 1


def test_the_programs_rule_is_the_same():
    """The benchmark's copy of the tiling rule against the program's
    own (``MegaConfig.tile_of``): imported here, in a test, only."""
    mega = pytest.importorskip("goworld_tpu.parallel.megaspace")
    four = Shape(load("configs", "open-world-2x2"))
    mc = mega.MegaConfig.__new__(mega.MegaConfig)
    object.__setattr__(mc, "mesh_shape", (2, 2))
    object.__setattr__(mc, "n_dev", 4)
    object.__setattr__(mc, "tile_w", four.tile_w)
    object.__setattr__(mc, "tile_d", four.tile_d)
    rng = np.random.default_rng(3)
    for x, z in rng.uniform(-10.0, 20912.0, (200, 2)):
        assert mc.tile_of(float(x), float(z)) == four.tile_of(x, z)


def test_work_counts_one_tile_against_one_chip():
    tile = work.least_seconds(load("configs", "open-world-tile"),
                              load("traffic", "roam"), "TPU v5 lite")
    four = work.least_seconds(load("configs", "open-world-2x2"),
                              load("traffic", "roam-borders"),
                              "TPU v5 lite")
    assert four == pytest.approx(tile)      # the same tile, the same chip


# ---- the mix ------------------------------------------------------------
def digest(plan, seqs=64) -> str:
    return hashlib.sha256(plan.positions(seqs).tobytes()).hexdigest()


def test_roam_is_unchanged_where_border_share_is_absent():
    """Pinned from the parent's generator (PR 26's tree): the tile's
    plan, and the rehearsal's, bit for bit; so is the schedule."""
    mix = load("traffic", "roam")
    assert "border_share" not in mix
    plan = orbit.Plan(mix, 10451.0, 50.0, 256)
    assert (plan.side, plan.spacing, plan.origin) == (11, 500.0, 2725.5)
    assert digest(plan) == ("7846cbbcca466f55ac68f433e78197a2"
                            "ea40041c39da610b569473d68200d527")
    assert not plan.on_border.any()
    small = orbit.Plan(dict(mix, twin_sites=2), 1400.0, 50.0, 16)
    assert digest(small) == ("879f843fba5a81237e114b2e96ae3062"
                             "38ffa8d2ceab185da8bf7075bd959157")
    # borders given and no share asked for: still the same plan
    same = orbit.Plan(mix, 10451.0, 50.0, 256, borders={"x": [5000.0]})
    assert digest(same) == digest(plan)
    o, w, k = orbit.schedule(mix, 256, 2**31 + 5, 40.0, 0)
    assert hashlib.sha256(o.tobytes() + w.tobytes() + k.tobytes()) \
        .hexdigest() == ("594b7f9e39c0d15292efb01305bd8479"
                         "6fe7d39f40ddee7493813b52e86685ea")


def borders_plan():
    cfg = load("configs", "open-world-2x2")
    mix = load("traffic", "roam-borders")
    shape = Shape(cfg)
    return shape, mix, orbit.Plan(
        mix, shape.extent_x, float(cfg["game"]["aoi_radius"]),
        int(mix["clients"]), borders=shape.borders)


def test_roam_borders_puts_a_quarter_of_its_sites_on_a_border():
    shape, mix, plan = borders_plan()
    sites = plan.sites
    assert len(sites) == 512 - 16 == 496
    on_x = sites[:, 0] == 10451.0
    on_z = sites[:, 1] == 10451.0
    assert ((on_x | on_z) == plan.on_border).all()
    assert plan.on_border.sum() == 124 == round(0.25 * 496)
    assert on_x.sum() == on_z.sum() == 62          # over both borders
    assert not (on_x & on_z).any()                 # none on the centre
    assert plan.on_border[:16].sum() == 8          # 8 of 16 twin sites
    # no two sites inside each other's AOI, twin gap and orbits counted
    d = np.abs(sites[:, None, :] - sites[None, :, :]).max(axis=2)
    np.fill_diagonal(d, np.inf)
    assert d.min() >= 4 * 50.0 + 4 * 8.0 + 60.0
    assert sites.min() >= 292.0 and sites.max() <= 20902.0 - 292.0
    # 256 clients a tile by their anchors, give or take the border's own
    anchors = np.array([plan.anchor(c) for c in range(1024)])
    per_tile = np.bincount(shape.tile_of(anchors[:, 0], anchors[:, 1]),
                           minlength=4)
    assert per_tile.sum() == 1024 and per_tile.min() >= 200


def test_border_pairs_walk_over_the_border_and_stand_apart():
    shape, mix, plan = borders_plan()
    lap = int(round(2 * np.pi / float(mix["orbit_step_rad"])))
    pos = plan.positions(lap + 1)
    tile = shape.tile_of(pos[:, :, 0].astype(np.float64),
                         pos[:, :, 2].astype(np.float64))
    changes = (tile[:, 1:] != tile[:, :-1]).sum(axis=1)
    singles = [c for c in range(4 * 16, 1024)
               if plan.on_border[plan.group_of(c) - 16]]
    assert len(singles) == 2 * (124 - 8)
    # twice a lap (a member that starts ON the line shows one of the
    # two just outside this lap)
    assert set(changes[singles].tolist()) <= {1, 2}
    assert changes[singles].mean() > 1.7
    # the two members of such a pair: in different tiles meanwhile
    a, b = singles[0], plan.observer(singles[0])
    assert (tile[a] != tile[b]).mean() > 0.9
    # off the borders nobody changes tile
    off = [c for c in range(4 * 16, 1024)
           if not plan.on_border[plan.group_of(c) - 16]]
    assert changes[off].sum() == 0
    # twin groups on a border: AOI-edge crossers that stand in
    # different tiles while they cross
    assert len(plan.crossers()) == 16 * 8
    astride = sum((tile[c] != tile[d]).any() for c, d in plan.crossers())
    # 4 twin sites on the border in x (the two groups on either side:
    # all 8 ordered pairs), 4 on the border in z (the pairs on opposite
    # phases, which are the ones whose distance crosses the AOI edge)
    assert astride == 4 * 8 + 4 * 4


def test_border_share_needs_a_border():
    mix = dict(load("traffic", "roam"), border_share=0.25)
    with pytest.raises(ValueError):
        orbit.Plan(mix, 10451.0, 50.0, 256)


# ---- the checks over one world -----------------------------------------
def two_tile_world(cap=8):
    """Two tiles of ``cap`` rows side by side (border at x = 100); rows
    by global number tile * cap + slot. A pair astride the border (rows
    1 and cap + 2), a pair inside tile 1, a loner."""
    pos = np.zeros((2 * cap, 3), np.float32)
    alive = np.zeros(2 * cap, bool)
    place = {1: (90.0, 50.0), cap + 2: (120.0, 60.0),        # astride
             cap + 4: (400.0, 400.0), cap + 5: (410.0, 380.0),
             3: (20.0, 300.0)}
    for row, (x, z) in place.items():
        pos[row, [0, 2]] = (x, z)
        alive[row] = True
    want = {1: {cap + 2}, cap + 2: {1}, cap + 4: {cap + 5},
            cap + 5: {cap + 4}, 3: set()}
    k = 4
    nbr = np.full((2 * cap, k), 2 * cap, np.int32)   # the gid sentinel
    for row, s in want.items():
        nbr[row, :len(s)] = sorted(s)
    return pos, alive, nbr, sorted(place)


def test_rows_check_on_a_two_tile_world_with_a_pair_astride_the_border():
    cap = 8
    pos, alive, nbr, rows = two_tile_world(cap)
    rows = np.array(rows)
    final = np.zeros((1, 4), np.float32)
    final[0, [0, 2]] = pos[1, [0, 2]]
    near = np.abs(pos[rows, 0] - 100.0) <= 50.0
    ok = R.rows_check(pos, alive, rows, nbr[rows], 50.0, [1], final, near)
    assert ok == {"rows_wrong": 0, "avatar_row_off": 0,
                  "rows_wrong_near_border": 0}
    # a tile that does not see across the border: the ghost is missing
    blind = nbr.copy()
    blind[1] = 2 * cap
    got = R.rows_check(pos, alive, rows, blind[rows], 50.0, [1], final,
                       near)
    assert got["rows_wrong"] == 1 and got["rows_wrong_near_border"] == 1
    # a list that names the neighbour by its slot, not its global row
    local = nbr.copy()
    local[1, 0] = 2
    assert R.rows_check(pos, alive, rows, local[rows], 50.0, [1], final,
                        near)["rows_wrong"] == 1
    # a fault far from the border is no border row
    far = nbr.copy()
    far[cap + 4] = 2 * cap
    got = R.rows_check(pos, alive, rows, far[rows], 50.0, [1], final, near)
    assert got["rows_wrong"] == 1 and got["rows_wrong_near_border"] == 0


def test_finals_and_crossings_over_a_border_are_counted():
    shape = Shape({"game": {"megaspace": True, "mesh_devices": 2,
                            "mega_shape": "2x1", "capacity": 8,
                            "extent_x": 200.0, "extent_z": 100.0}})
    xz = np.array([[90.0, 50.0], [120.0, 60.0], [20.0, 10.0]])
    final = np.zeros((3, 4), np.float32)
    final[:, [0, 2]] = xz
    want = neighbourhoods(xz, 50.0)
    mirrors = [{f"c{d}": ("client", d, tuple(final[d])) for d in want[c]}
               for c in range(3)]
    got = R.interest_check(xz, 50.0, 12.0, mirrors, final,
                           shape.tile_of(xz[:, 0], xz[:, 1]))
    assert got["finals_over_border"] == 2 and got["final_missing"] == 0
    assert R.interest_check(xz, 50.0, 12.0, mirrors, final)[
        "finals_over_border"] == 0
    # client 1 stands at x = 90 (tile 0), client 2 walks along x from
    # 120 to 170 and back in tile 1: one leave, one enter, both astride
    table = np.zeros((3, 64, 4), np.float32)
    table[1, :, 0] = 90.0
    xs = np.concatenate([np.linspace(120.0, 170.0, 20),
                         np.linspace(170.0, 120.0, 20)])
    table[2, 1:41, 0] = xs
    table[2, 0, 0] = 120.0
    sends = [(1, 1, 0.0)] + [(2, q, 0.1 * q) for q in range(1, 41)]
    ref = R.cross_check([(1, 2)], sends, table, {}, 50.0, 3.0,
                        shape.tile_of)
    assert ref["crossings"] == 2 and ref["crossings_over_border"] == 2
    assert R.cross_check([(1, 2)], sends, table, {}, 50.0,
                         3.0)["crossings_over_border"] == 0


# ---- the counts only a tiled world can fail ------------------------------
def test_entities_lost_reads_a_dropped_and_a_doubled_row():
    cap = 8
    _pos, alive, _nbr, rows = two_tile_world(cap)
    live = int(alive.sum())
    assert R.entities_lost(alive, live) == 0
    dropped = alive.copy()
    dropped[rows[0]] = False          # left its tile, never arrived
    assert R.entities_lost(dropped, live) == 1
    doubled = alive.copy()
    doubled[np.nonzero(~alive)[0][0]] = True   # arrived, never left
    assert R.entities_lost(doubled, live) == 1


def test_border_untested_wants_a_crossing_and_a_final_over_a_border():
    from world import border_untested

    met = {"crossings_over_border": 46, "finals_over_border": 240,
           "rows_near_border": 1800}
    assert border_untested(met, True) == 0
    assert border_untested(dict(met, crossings_over_border=0), True) == 1
    assert border_untested(dict(met, finals_over_border=0), True) == 1
    assert border_untested({}, True) == 2
    # one space has no border to meet
    assert border_untested({}, False) == 0


def test_mesh_dropped_counts_the_programs_own_three_lines():
    """The program has no counter for a tile's exchange overflowing,
    only log lines (entity/manager.py): the pattern is held to the
    words as the program's source has them today, and to lines as its
    logger prints them."""
    import run

    with open(os.path.join(os.path.dirname(BENCH), "goworld_tpu", "entity",
                           "manager.py")) as f:
        src = f.read()
    for words in ("megaspace migrate demand %d exceeds migrate_cap %d",
                  "megaspace halo demand %d exceeds halo_cap %d",
                  "megaspace dropped %d border-crossing entities"):
        assert words in src
        assert len(run.MESH_DROPPED.findall(
            (words % ((7, 1) if words.count("%d") == 2 else (7,)))
            .encode())) == 1
    log = (b"op world.tick took 180.3 ms\n"
           b"W goworld_tpu.entity megaspace migrate demand 3 exceeds "
           b"migrate_cap 1; surplus entities linger on the wrong tile "
           b"this tick\n"
           b"W goworld_tpu.entity megaspace halo demand 1100 exceeds "
           b"halo_cap 1024; some cross-border neighbors invisible this "
           b"tick\n"
           b"E goworld_tpu.entity megaspace dropped 2 border-crossing "
           b"entities (destination tiles full); respawning from host "
           b"state\n"
           b"shard 3 enter overflow: 915373 > 4096\n")
    assert len(run.MESH_DROPPED.findall(log)) == 3


# ---- set-up: logins and placement in waves --------------------------------
def test_waves_of_one_space_are_what_they_were():
    import bots

    order = bots.round_the_tiles(np.zeros(128, np.int64),
                                 lambda g: range(2 * g, 2 * g + 2))
    assert (order == np.arange(256)).all()
    assert bots.wave_bounds(np.zeros(256, np.int64), 2) \
        == [0, 8, 24, 56, 120, 184, 248, 256]


def test_waves_of_the_tiled_world_bring_no_tile_more_than_one_spaces():
    import bots

    cfg, mix = load("configs", "open-world-2x2"), load("traffic",
                                                       "roam-borders")
    shape, n = Shape(cfg), int(mix["clients"])
    plan = orbit.Plan(mix, shape.extent_x, cfg["game"]["aoi_radius"], n,
                      borders=shape.borders)
    table = plan.positions(4)
    tile = shape.tile_of(table[:, 1, 0], table[:, 1, 2])
    order = bots.round_the_tiles(
        [tile[plan.members(g)[0]] for g in range(n // plan.g)],
        plan.members)
    assert sorted(order.tolist()) == list(range(n))
    # whole groups stay together
    assert (order[0::2] // 2 == order[1::2] // 2).all()
    bounds = bots.wave_bounds(tile[order], plan.g)
    assert bounds[0] == 0 and bounds[-1] == n
    sizes = np.diff(bounds)
    assert len(sizes) <= 10 and sizes.max() > 3 * bots.WAVE_MAX
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        assert np.bincount(tile[order[lo:hi]]).max() <= bots.WAVE_MAX
        assert lo == 0 or hi - lo <= 2 * (lo - bounds[bounds.index(lo) - 1])


# ---- chips that are still being given back ---------------------------------
def test_start_again_while_the_chips_are_busy(tmp_path, monkeypatch):
    """A game that finds its chips busy dies at once and `start` fails:
    the harness runs `start` again after a pause (it opens no device
    node to find out), and gives up on any other failure at once."""
    import run

    os.makedirs(tmp_path / "run")
    cl = run.Cluster(str(tmp_path))
    busy = (b"RuntimeError: Unable to initialize backend 'tpu': UNKNOWN: "
            b"TPU initialization failed: open(/dev/vfio/1): Device or "
            b"resource busy: Device or resource busy; Couldn't open iommu "
            b"group /dev/vfio/1\n")
    calls, naps = [], []

    def fake_gw(script):
        def gw(args, timeout, host_devices=0):
            calls.append(args)
            rc, log = script[len(calls) - 1]
            with open(cl.game_log, "ab") as f:       # `start` appends
                f.write(log)
            return rc, ("dispatcher1: started | game1: FAILED" if rc
                        else "game1: started | gate1: started"), 1.0
        return gw

    monkeypatch.setattr(run.time, "sleep", naps.append)
    monkeypatch.setattr(run, "gw", fake_gw(
        [(1, busy), (1, busy), (0, b"GOWORLD_TPU_PROCESS_STARTED\n")]))
    assert run.start_cluster(cl) == 0
    assert calls == [["start", str(tmp_path)]] * 3
    assert naps == [run.CHIPS_BUSY_PAUSE_S] * 2
    # the dead game's words are moved aside: the log is the live game's
    with open(cl.game_log, "rb") as f:
        assert run.CHIPS_BUSY not in f.read()
    with open(cl.game_log + ".busy", "rb") as f:
        assert run.CHIPS_BUSY in f.read()
    # another failure is no reason to ask again
    calls.clear(), naps.clear()
    os.remove(cl.game_log)
    monkeypatch.setattr(run, "gw", fake_gw([(1, b"ImportError: no\n")]))
    assert run.start_cluster(cl) == 1 and len(calls) == 1 and not naps
    with open(os.path.join(BENCH, "run.py")) as f:
        src = f.read().replace("open(/dev/vfio/1)", "")
    assert "/dev/" not in src and "vfio" not in src
