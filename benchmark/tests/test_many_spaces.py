"""A world of many spaces on one chip, as the harness reads it from a
configuration (``game.n_spaces``): the reference within a space and
across spaces, the mix that hops between spaces and writes attrs
(generators/hop.py), the checks judged space by space (reduce.py), and
— starting clusters on the CPU, half a minute each — the 8-space
rehearsal with each of its own counts read above 0 by a planted fault.
"""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import reduce as R
import work
from generators import hop, orbit
from phase_reduce import scope_of, scopes_of
from reference import chebyshev, neighbourhoods, neighbours_of
from world import Shape

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "soak.spaces"


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def soak_plan(spaces=None):
    cfg, mix = load("configs", "upstream-soak"), load("traffic", "hop")
    shape = Shape(cfg)
    return cfg, mix, shape, hop.Plan(
        mix, shape.extent_x, float(cfg["game"]["aoi_radius"]),
        int(mix["clients"]), spaces=spaces or shape.spaces)


# ---- the shape and the reference -----------------------------------------
def test_shape_of_the_third_kind_of_world():
    cfg = load("configs", "upstream-soak")
    sh = Shape(cfg)
    assert (sh.mega, sh.tiles, sh.capacity) == (False, 1, 256)
    assert sh.spaces == cfg["game"]["n_spaces"] > 1
    assert cfg["world"]["live"] == 128 * sh.spaces + 1024
    rows = np.array([0, 255, 256, 3 * 256 + 7])
    assert sh.space_of(rows).tolist() == [0, 0, 1, 3]
    one = Shape(load("configs", "open-world-tile"))
    assert one.spaces == 1 and one.space_of(rows).tolist() == [0, 0, 0, 0]
    assert Shape(load("configs", "open-world-2x2")).spaces == 1


def test_reference_within_a_space_and_empty_across():
    rng = np.random.default_rng(11)
    xz = rng.uniform(0.0, 280.0, (600, 2)).astype(np.float32)
    space = rng.integers(0, 5, 600)
    want = []
    d = chebyshev(xz, xz)
    for i in range(600):
        want.append({int(j) for j in np.nonzero(
            (d[i] <= 50.0) & (space == space[i]))[0] if j != i})
    got = neighbourhoods(xz, 50.0, space)
    assert got == want
    assert all(space[j] == space[i] for i, s in enumerate(got) for j in s)
    assert sum(map(len, got)) > 1000                    # not vacuous
    rows = rng.choice(600, 80, replace=False)
    assert neighbours_of(xz, rows, 50.0, space) == [want[i] for i in rows]
    # two rows on one spot in different spaces do not see each other
    same = np.array([[10.0, 10.0], [10.0, 10.0], [12.0, 10.0]])
    assert neighbourhoods(same, 50.0, [0, 1, 0]) == [{2}, set(), {0}]
    # absent: one world, as ever
    flat = [{int(j) for j in np.nonzero(d[i] <= 50.0)[0] if j != i}
            for i in range(600)]
    assert neighbourhoods(xz, 50.0) == flat


# ---- the mix -------------------------------------------------------------
def ops_of(kind):
    out = {}
    for k in kind:
        name = k[1] if isinstance(k, tuple) else int(k)
        out[name] = out.get(name, 0) + 1
    return out


def walk(plan, mix, offs, who, kind, start=0.0):
    """Follow a stretch's hops from where the plan's pairs stand: pairs
    hop together, always to another space, never onto a held place or
    one left less than ``hop_free_s`` ago, and no pair again within
    ``hop_min_gap_s`` of its last hop (the one before the stretch too).
    Every call says what it does to its client."""
    at = dict(plan.place)
    left = {}
    last = {g: t - start for g, t in plan.hopped.items()}
    hops = [(t, int(c), k) for t, c, k in zip(offs, who, kind)
            if isinstance(k, tuple) and k[1] == hop.ENTER]
    for (t, c, op), (t2, c2, op2) in zip(hops[0::2], hops[1::2]):
        assert (t, op, c ^ 1) == (t2, op2, c2)       # a pair, together
        assert op[3] == {"at": op[2]}
        grp = c // 2
        space, x, z = op[2]
        to = (space, plan.corners.index((x, z)))
        assert plan.crowds <= space < plan.spaces
        assert space != at[grp][0]
        assert to not in at.values()
        assert t - left.get(to, -1e9) >= mix["hop_free_s"]
        assert t - last.get(grp, -1e9) >= mix["hop_min_gap_s"]
        left[at[grp]] = t
        at[grp] = to
        last[grp] = t
    return len(hops)


@pytest.mark.parametrize("spaces", [None, 256])
def test_hop_gives_every_seed_the_same_work_and_no_site_twice(spaces):
    """The window's work is the same in every seed — every pair outside
    the crowd hops once in every period of 16 s, half of them in the
    window's last, cut half period: 1,280 calls — while the instants
    are drawn: a second holds as many hops as chance brings. No two
    pairs ever stand on one place, a place is reused only
    ``hop_free_s`` after it was left, a hop always changes the space.
    The mix fits every cut of the space count."""
    counts, busiest, calmest = set(), 0, 10**9
    for seed in (3, 2**31 + 5, 2**31 + 77):
        _cfg, mix, shape, plan = soak_plan(spaces)
        offs, who, kind = plan.schedule(seed, 40.0, 0, 1000.0)
        assert (np.diff(offs) >= 0).all() and len(offs) == len(kind)
        counts.add(tuple(sorted(ops_of(kind).items(), key=str)))
        walk(plan, mix, offs, who, kind, 1000.0)
        a_second = np.bincount(np.floor([
            t for t, k in zip(offs, kind)
            if isinstance(k, tuple) and k[1] == hop.ENTER]).astype(int),
            minlength=40)
        busiest = max(busiest, int(a_second.max()))
        calmest = min(calmest, int(a_second.min()))
    assert len(counts) == 1
    n = dict(counts.pop())
    assert n == {0: 204800, 1: 40960, hop.SET_HP: 20480, hop.ENTER: 1280}
    assert calmest < 24 and busiest > 40        # 32 a second on average


def test_hop_schedules_from_where_its_pairs_really_stand():
    """A warm-up stretch is cut when the window opens: the plan is told
    of every call that was sent and starts the next stretch there."""
    _cfg, mix, _shape, plan = soak_plan()
    offs, who, kind = plan.schedule(5, 10.0, 1, 50.0)
    sent = [(float(t), int(c), k) for t, c, k in zip(offs, who, kind)
            if isinstance(k, tuple) and t < 4.5]
    for t, c, op in sent:
        plan.called(c, op, 50.0 + t)
    moved = {c // 2: (t, op[2]) for t, c, op in sent if op[1] == hop.ENTER}
    assert 50 < len(moved) < 100    # 4.5 s of 16: some 72 of 256 pairs
    for grp, (t, (space, x, z)) in moved.items():
        assert plan.place[grp] == (space, plan.corners.index((x, z)))
        assert plan.hopped[grp] == 50.0 + t
    # the next stretch starts from there, 4.5 s on, and keeps every
    # pair's gap over the cut: a pair that has just hopped waits
    offs, who, kind = plan.schedule(5, 40.0, 0, 54.5)
    assert walk(plan, mix, offs, who, kind, 54.5) == 1280
    soon = {int(c) // 2 for t, c, k in zip(offs, who, kind)
            if isinstance(k, tuple) and k[1] == hop.ENTER and t < 1.0}
    assert soon and not soon & {g for g, (t, _p) in moved.items()
                                if t > 1.5}


def test_hop_plan_places_and_crowd_lists_stay_under_k():
    cfg, mix, shape, plan = soak_plan()
    table = plan.positions(256)                          # a whole lap
    space = np.array([plan.login(c)[3]["at"][0] for c in range(plan.n)])
    assert (np.bincount(space)[:8] == 64).all()          # the crowd
    assert set(np.bincount(space)[8:136].tolist()) == {4}   # two pairs
    assert space.max() == 135 < shape.spaces
    assert table[:, :, [0, 2]].min() >= 0.0
    assert table[:, :, [0, 2]].max() <= shape.extent_x
    # the densest point of the plan: the most other avatars any client
    # of a crowded space holds at any step of a lap, with room under
    # k = 64 for the NPCs (16 expected among 128 at this extent)
    crowd = np.nonzero(space == 0)[0]
    most = 0
    for q in range(0, 256, 4):
        d = chebyshev(table[crowd, q][:, [0, 2]], table[crowd, q][:, [0, 2]])
        most = max(most, int((d <= 50.0).sum(axis=1).max()) - 1)
    assert 12 <= most <= 24
    # a pair never loses its partner; two pairs of a space never meet
    a = table[np.arange(0, plan.n, 2)][:, :, [0, 2]].astype(np.float64)
    b = table[np.arange(1, plan.n, 2)][:, :, [0, 2]].astype(np.float64)
    assert np.abs(a - b).max() <= 16.0 + 1e-3
    q = shape.extent_x / 4
    assert 2 * q - 16.0 > 50.0 + 12.0
    # neighbouring sites on opposite phases swing over the AOI edge
    pairs = plan.crossers()
    assert len(pairs) == 8 * 26 * 4
    c, d = pairs[0]
    dist = np.abs(table[c][:, [0, 2]].astype(np.float64)
                  - table[d][:, [0, 2]]).max(axis=1)
    assert dist.min() < 47.0 and dist.max() > 53.0
    assert space[c] == space[d] < 8


def test_no_wave_brings_one_space_more_than_64():
    import bots

    _cfg, _mix, _shape, plan = soak_plan()
    space = np.array([plan.login(c)[3]["at"][0] for c in range(plan.n)])
    order = bots.round_the_tiles(
        [space[plan.members(g)[0]] for g in range(plan.n // plan.g)],
        plan.members)
    assert sorted(order.tolist()) == list(range(plan.n))
    bounds = bots.wave_bounds(space[order], plan.g)
    assert bounds[-1] == plan.n and len(bounds) <= 10
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        assert np.bincount(space[order[lo:hi]]).max() <= bots.WAVE_MAX


def test_roam_and_roam_borders_are_what_they_were():
    """``orbit`` is untouched: both mixes' plans and schedules, pinned
    from the parent's tree (9b969bf)."""
    def digest(*arrays):
        return hashlib.sha256(b"".join(
            np.ascontiguousarray(a).tobytes() for a in arrays)).hexdigest()

    mix = load("traffic", "roam")
    assert digest(orbit.Plan(mix, 10451.0, 50.0, 256).positions(64)) == (
        "7846cbbcca466f55ac68f433e78197a2ea40041c39da610b569473d68200d527")
    assert digest(*orbit.schedule(mix, 256, 2**31 + 5, 40.0, 0)) == (
        "594b7f9e39c0d15292efb01305bd84796fe7d39f40ddee7493813b52e86685ea")
    cfg, mix = load("configs", "open-world-2x2"), load("traffic",
                                                       "roam-borders")
    shape = Shape(cfg)
    plan = orbit.Plan(mix, shape.extent_x, 50.0, 1024,
                      borders=shape.borders)
    assert digest(plan.positions(64)) == ROAM_BORDERS_PLAN
    assert digest(*orbit.schedule(mix, 1024, 2**31 + 5, 40.0, 0)) \
        == ROAM_BORDERS_SCHEDULE


ROAM_BORDERS_PLAN = (
    "5d0a4bc99bf53a4957757443f32acb47"
    "eb899ab4f3d8873a35be5e18201910c2")
ROAM_BORDERS_SCHEDULE = (
    "8712dbbec1458de9b888938035fc3948"
    "475687901dc0e5f65870184569092f33")


# ---- the checks, space by space --------------------------------------------
def two_spaces(cap=8):
    """Two spaces of ``cap`` rows that share their coordinates: a pair in
    space 0 (slots 1, 2), a pair on the same spots in space 1 (slots 1,
    2), a loner in space 1. Lists hold SLOTS of their own space."""
    pos = np.zeros((2 * cap, 3), np.float32)
    alive = np.zeros(2 * cap, bool)
    for row, (x, z) in {1: (90.0, 50.0), 2: (120.0, 60.0),
                        cap + 1: (90.0, 50.0), cap + 2: (120.0, 60.0),
                        cap + 5: (250.0, 250.0)}.items():
        pos[row, [0, 2]] = (x, z)
        alive[row] = True
    nbr = np.full((2 * cap, 4), cap, np.int32)       # sentinel: capacity
    nbr[1, 0], nbr[2, 0] = 2, 1
    nbr[cap + 1, 0], nbr[cap + 2, 0] = 2, 1
    return pos, alive, nbr, np.nonzero(alive)[0]


def test_rows_check_judges_every_space_by_itself():
    cap = 8
    pos, alive, nbr, rows = two_spaces(cap)
    space = np.arange(2 * cap) // cap
    final = np.zeros((1, 4), np.float32)
    final[0, [0, 2]] = pos[1, [0, 2]]
    args = (50.0, [1], final, None, space, cap)
    ok = R.rows_check(pos, alive, rows, nbr[rows], *args)
    assert ok["rows_wrong"] == 0 and ok["avatar_row_off"] == 0
    # judged as ONE world the same rows are wrong: the twins on the same
    # spots would be neighbours
    flat = R.rows_check(pos, alive, rows, nbr[rows], 50.0, [1], final)
    assert flat["rows_wrong"] == 5
    # a list that misses its neighbour; a list that holds a slot whose
    # row in ITS space is dead (the twin's space has one there)
    blind = nbr.copy()
    blind[cap + 1] = cap
    assert R.rows_check(pos, alive, rows, blind[rows], *args)[
        "rows_wrong"] == 1
    stray = nbr.copy()
    stray[cap + 5, 0] = 1
    assert R.rows_check(pos, alive, rows, stray[rows], *args)[
        "rows_wrong"] == 1


def test_interest_check_holds_a_mirror_to_its_space():
    xz = np.array([[90.0, 50.0], [120.0, 60.0], [90.0, 50.0], [120.0, 60.0]])
    final = np.zeros((4, 4), np.float32)
    final[:, [0, 2]] = xz
    space = np.array([0, 0, 1, 1])
    mirrors = [{f"p{c ^ 1}": ("client", c ^ 1, tuple(final[c ^ 1])),
                "n": ("npc", f"npc{space[c]}", (100.0, 0.0, 55.0, 0.0))}
               for c in range(4)]
    ok = R.interest_check(xz, 50.0, 12.0, mirrors, final, None, space)
    assert ok == dict(final_missing=0, interest_extra=0, npc_stray=0,
                      npc_cross_missing=0, finals_over_border=0,
                      space_wrong=0)
    # as one world every client would miss the twins of the other space
    assert R.interest_check(xz, 50.0, 12.0, mirrors, final)[
        "final_missing"] == 8
    # a client of the other space in a mirror: extra, and of a wrong space
    mirrors[0]["p2"] = ("client", 2, tuple(final[2]))
    got = R.interest_check(xz, 50.0, 12.0, mirrors, final, None, space)
    assert got["interest_extra"] == 1 and got["space_wrong"] == 1
    # an NPC that clients of two spaces mirror
    del mirrors[0]["p2"]
    mirrors[3]["n"] = ("npc", "npc0", (100.0, 0.0, 55.0, 0.0))
    got = R.interest_check(xz, 50.0, 12.0, mirrors, final, None, space)
    assert got["space_wrong"] == 1
    # ... is missing from nobody of the other space
    assert got["npc_cross_missing"] == 1      # client 2 lacks npc1 now? no:
    #   client 3 no longer holds npc1, which client 2 sees inside its box


def test_space_wrong_reads_an_avatar_and_an_npc_in_another_space():
    now = np.array([0, 0, 3, 3])
    npc = {"a": 0, "b": 3}
    mirrored = [{"a"}, {"a"}, {"b"}, {"b", "x-unknown"}]
    assert R.space_wrong([0, 0, 3, 3], now, mirrored, npc) == 0
    # an EnterSpace that moved nobody: the avatar's row is where it was
    assert R.space_wrong([0, 0, 0, 3], now, mirrored, npc) == 1
    # a mirror that holds an NPC of another space
    mirrored[1] = {"a", "b"}
    assert R.space_wrong([0, 0, 3, 3], now, mirrored, npc) == 1


def test_hop_check_counts_unanswered_untold_and_untested():
    obs = [1, 0, 3, 2]
    nan = float("nan")
    # client 0 hops at 1.0 and 9.0, client 2 at 2.0; window ends at 20
    hops = [(0, 1.0, 1.6), (0, 9.0, 9.8), (2, 2.0, 2.9)]
    told = {(1, 0): [(1.5, False), (1.7, True), (9.6, False), (9.9, True)],
            (3, 2): [(2.5, False), (2.8, True)]}
    ok = R.hop_check(hops, obs, told, 3, 20.0)
    assert ok == {"hop_unanswered": 0, "hops": 3, "hops_done": 3,
                  "first_bad": [], "hops_untested": 0}
    # never answered
    bad = R.hop_check([(0, 1.0, nan)] + hops[1:], obs, told, 3, 20.0)
    assert bad["hop_unanswered"] == 1 and bad["hops_done"] == 2
    # the partner never told of the leaving; told of the entering twice
    assert R.hop_check(hops, obs, {**told, (3, 2): [(2.8, True)]},
                       3, 20.0)["hop_unanswered"] == 1
    twice = {**told, (3, 2): [(2.5, False), (2.8, True), (3.0, True)]}
    assert R.hop_check(hops, obs, twice, 3, 20.0)["hop_unanswered"] == 1
    gone = {**told, (3, 2): [(2.5, False), (2.8, True), (3.0, False)]}
    assert R.hop_check(hops, obs, gone, 3, 20.0)["hop_unanswered"] == 1
    # out of sight for a frame and back (a position of the old place
    # applied in the new one: the gate batches syncs, not calls): told
    again = {**told, (3, 2): [(2.5, False), (2.8, True), (3.0, False),
                              (3.3, True)]}
    assert R.hop_check(hops, obs, again, 3, 20.0)["hop_unanswered"] == 0
    # fewer than three quarters of the schedule's hops done in the window
    assert R.hop_check(hops, obs, told, 4, 20.0)["hops_untested"] == 0
    assert R.hop_check(hops, obs, told, 5, 20.0)["hops_untested"] == 1
    late = [(0, 1.0, 1.6), (0, 9.0, 29.0), (2, 2.0, 22.0)]
    assert R.hop_check(late, obs, told, 3, 20.0)["hops_untested"] == 1
    # a mix without hops has nothing to test
    assert R.hop_check([], obs, {}, 0, 20.0) == {
        "hop_unanswered": 0, "hops": 0, "hops_done": 0, "first_bad": [],
        "hops_untested": 0}


# ---- the reducers ------------------------------------------------------------
def test_work_counts_all_spaces_and_records_by_kind():
    cfg, mix = load("configs", "upstream-soak"), load("traffic", "hop")
    live = int(cfg["world"]["live"])
    by = cfg["world"]["expected_neighbours_by_kind"]
    records = sum(by[k] * n for k, n in mix["clients_by_kind"].items())
    assert sum(mix["clients_by_kind"].values()) == mix["clients"]
    want = 2 * work.ROW_BYTES * live + 2 * 4 * work.K * live \
        + work.SYNC_RECORD_BYTES * records
    assert work.least_seconds(cfg, mix, "TPU v5 lite") == pytest.approx(
        want / work.peaks("TPU v5 lite")["hbm_bytes_per_s"])
    # the other configurations count as ever
    tile = load("configs", "open-world-tile")
    assert "expected_neighbours_by_kind" not in tile["world"]
    assert work.necessary_bytes(100000, 256, 2, 12) == \
        2 * work.ROW_BYTES * 100000 + 2 * 4 * 64 * 100000 + 24 * 256 * 13


def test_a_scope_under_vmap_is_read_by_its_name():
    assert scope_of("gw.sync") == scope_of("vmap(gw.sync)") == "gw.sync"
    assert scope_of("vmap(jit(step))") is None and scope_of("gather") is None
    assert scopes_of("jit(step)/vmap(gw.sync)/jit(collect_sync)/gather") \
        == ("gw.sync",)
    assert scopes_of("jit(step)/vmap(gw.aoi)/gw.aoi.rank/sort") \
        == ("gw.aoi", "gw.aoi.rank")
    assert scopes_of("jit(step)/gw.delta/cond/branch_1_fun/gather") \
        == ("gw.delta",)


def test_the_other_configurations_no_longer_say_no_mix_sends_attrs():
    for name in ("open-world-tile", "open-world-2x2"):
        text = load("configs", name)["assumed"]["traffic"]
        assert "no mix sends it yet" not in text and "attr" in text


# ---- the rehearsal: 8 spaces of 256 on the CPU --------------------------------
def run(*extra, seed=5, seconds=10, trace=0):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--cell-file", "benchmark/cells/soak.spaces.json",
           "--workload", CELL, "--seed", str(seed), "--seconds",
           str(seconds), "--trace", str(trace), "--rehearsal", *extra]
    r = subprocess.run(cmd, cwd=ROOT, env=dict(os.environ,
                                                JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=900)
    lines = r.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1])
    except (ValueError, IndexError):
        last = None
    return r, last


def bad(last):
    return {k for k, c in last["checks"].items() if c["value"] > c["limit"]}


def test_rehearsal_of_the_many_spaces_cell():
    r, last = run(seed=2**31 + 21)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    assert last["correct"] is True, last["checks"]
    assert {"space_wrong", "attr_wrong", "hop_unanswered",
            "hops_untested", "entities_lost"} <= set(last["checks"])
    assert all(c["value"] == 0 for c in last["checks"].values())
    # 48 clients: 5 sends and 1 Echo a second each, a write in 2 s, and
    # 8 pairs that hop once in every 4 s: twice each in the window's
    # two whole periods, and 4 of them in its last half one
    assert last["attempted"] == 48 * (50 + 10 + 5) + 2 * (2 * 8 + 4)
    assert last["compiles_in_window"] == 0
    with np.load(os.path.join(ROOT, ".bench_work", CELL, "rows.npz")) as z:
        # every space under ONE row number, space * capacity + slot
        assert z["pos"].shape == (8 * 256, 3)
        assert int(z["alive"].sum()) == 8 * 128 + 48
        assert len(z["rows"]) == 48 + 768          # ONE sample over all
        assert len(z["npc_rows"]) == len(z["npc_eids"]) == 8 * 128
        assert (np.bincount(z["npc_rows"] // 256) == 128).all()
        spaces = z["avatar_rows"] // 256
        assert (np.bincount(spaces, minlength=8)[:2] == 16).all()
        assert np.bincount(spaces, minlength=8)[2:].sum() == 16
    with open(os.path.join(ROOT, ".bench_work", CELL, "bots.json")) as f:
        hops = json.load(f)["hops"]
    assert hops["hops"] == 40 and hops["hops_done"] >= 30


@pytest.mark.parametrize("plant,caught", [
    # the fixture answers OnEntered and moves nobody: the avatars' rows
    # are in the spaces they were in, and no partner saw anybody leave
    ("stay", {"space_wrong", "hop_unanswered"}),
    # every second attr write dropped before the attr is set
    ("hp", {"attr_wrong"}),
])
def test_a_many_spaces_worlds_own_counts_read_not_correct(plant, caught):
    r, last = run("--plant", plant)
    assert last is not None, r.stdout[-3000:] + r.stderr[-2000:]
    assert last["correct"] is False
    assert caught <= bad(last), last["checks"]
    if plant == "hp":
        assert bad(last) == caught, last["checks"]


def test_control_reads_not_correct_on_many_spaces():
    r, last = run("--control-faults", "drop:gate->dispatcher:mt=14:0.9")
    assert last is not None, r.stdout[-3000:] + r.stderr[-2000:]
    assert last["correct"] is False, last["checks"]
