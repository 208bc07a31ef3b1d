"""The send-to-receipt matcher, the percentile and the check numbers on
synthetic logs (no socket, no program)."""
import numpy as np
import pytest

import reduce as R
from generators import orbit
from reference import neighbourhoods

MIX = {"group_size": 2, "grid_spacing_max": 500.0,
       "grid_spacing_min": 240.0, "orbit_radius": 8.0,
       "orbit_step_rad": 0.025, "send_interval_ms": 100,
       "send_probability": 0.5, "rpc_per_client_per_s": 1.0}


def test_nearest_rank():
    v = list(range(1, 101))
    assert R.nearest_rank(v, 0.50) == 50
    assert R.nearest_rank(v, 0.95) == 95
    assert R.nearest_rank([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        R.nearest_rank([], 0.5)


def _log(stall_at=None, stall_s=0.0):
    """Two clients; 0 sends seq 1..100 every 0.1 s; client 1 (its
    observer) receives each 0.05 s later — except during a stall, when
    receipts wait and the first one after it carries the newest seq."""
    due = 0.1 * np.arange(1, 101)
    sc, sq = np.zeros(100, int), np.arange(1, 101)
    rt, rq = [], []
    for n, d in zip(sq, due):
        t = d + 0.05
        if stall_at is not None and stall_at <= t < stall_at + stall_s:
            continue                      # coalesced away by the stall
        rt.append(t)
        rq.append(n)
    if stall_at is not None:
        # the stall ends: one receipt with the newest position sent
        newest = int(sq[due + 0.05 < stall_at + stall_s][-1])
        rt.append(stall_at + stall_s)
        rq.append(newest)
        order = np.argsort(rt)
        rt, rq = list(np.array(rt)[order]), list(np.array(rq)[order])
    n = len(rt)
    return (sc, sq, due, np.ones(n, int), np.zeros(n, int),
            np.array(rq), np.array(rt))


def test_match_and_tail_moves_with_a_stall():
    obs = np.array([1, 0])
    sc, sq, due, rr, rs, rq, rt = _log()
    seen = R.match_sends(sc, sq, obs, rr, rs, rq, rt)
    ms, failed = R.latencies(due, seen, close=20.0)
    assert failed == 0
    assert np.allclose(ms, 50.0)
    sc, sq, due, rr, rs, rq, rt = _log(stall_at=4.0, stall_s=2.0)
    seen = R.match_sends(sc, sq, obs, rr, rs, rq, rt)
    ms, failed = R.latencies(due, seen, close=20.0)
    assert failed == 0
    # every send of the stalled two seconds waited for its end
    assert R.nearest_rank(ms, 0.50) == pytest.approx(50.0)
    assert R.nearest_rank(ms, 0.95) > 1000.0
    assert ms.max() == pytest.approx(2000.0 + 50.0 - 100.0, abs=60.0)


def test_later_position_counts_and_never_seen_fails():
    obs = np.array([1, 0])
    # sends 1, 2, 3 due at 1, 2, 3 s; only seq 2 ever arrives (at 2.5)
    seen = R.match_sends([0, 0, 0], [1, 2, 3], obs, [1], [0], [2], [2.5])
    assert seen[0] == 2.5 and seen[1] == 2.5 and np.isnan(seen[2])
    ms, failed = R.latencies([1.0, 2.0, 3.0], seen, close=5.0)
    assert failed == 1
    assert list(ms) == [1500.0, 500.0, 2000.0]
    # a receipt at a client that is not the observer times nothing
    seen = R.match_sends([0], [1], obs, [0], [0], [1], [1.0])
    assert np.isnan(seen[0])


def test_stream_faults_counts_wrong_and_backward_records():
    plan = orbit.Plan(MIX, 3000.0, 50.0, 4)
    table = plan.positions(64)
    recv = np.array([1, 1, 1, 0])
    send = np.array([0, 0, 0, 1])
    seq = np.array([3, 5, 4, 7])            # 4 after 5: one step back
    vals = table[send, seq].copy()
    assert R.stream_faults(recv, send, seq, vals, table, 4) == (0, 1)
    vals[0, 0] += 1.0                       # x altered on the way
    vals[3, 3] += 0.5                       # yaw altered
    assert R.stream_faults(recv, send, seq, vals, table, 4) == (2, 1)


def test_reference_neighbourhoods():
    xz = np.array([[0.0, 0.0], [50.0, 10.0], [50.1, 0.0], [-20.0, -50.0]])
    assert neighbourhoods(xz, 50.0) == [{1, 3}, {0, 2}, {1}, {0}]


def _world():
    plan = orbit.Plan(MIX, 3000.0, 50.0, 4)
    table = plan.positions(8)
    final = table[np.arange(4), 5]
    xz = final[:, [0, 2]]
    mirrors = []
    for c in range(4):
        d = plan.observer(c)
        ax, az = plan.anchor(c)
        mirrors.append({
            f"p{d}": ("client", d, tuple(final[d])),
            "npcA": ("npc", f"npc{c // 2}", (ax + 20.0, 0.0, az, 0.0))})
    return plan, final, xz, mirrors


def test_interest_check_passes_and_catches_each_fault():
    plan, final, xz, mirrors = _world()
    ok = R.interest_check(xz, 50.0, 12.0, mirrors, final)
    assert ok == dict(final_missing=0, interest_extra=0, npc_stray=0,
                      npc_cross_missing=0, finals_over_border=0)
    # the partner mirrored at a stale place
    _p, final, xz, m = _world()
    m[0]["p1"] = ("client", 1, tuple(plan.positions(8)[1, 4]))
    assert R.interest_check(xz, 50.0, 12.0, m, final)["final_missing"] == 1
    # the partner not mirrored at all
    _p, final, xz, m = _world()
    del m[2]["p3"]
    assert R.interest_check(xz, 50.0, 12.0, m, final)["final_missing"] == 1
    # a client of another group mirrored
    _p, final, xz, m = _world()
    m[0]["p3"] = ("client", 3, tuple(final[3]))
    assert R.interest_check(xz, 50.0, 12.0, m, final)["interest_extra"] == 1
    # an NPC far outside the box still mirrored
    _p, final, xz, m = _world()
    ax, az = plan.anchor(0)
    m[0]["far"] = ("npc", "far", (ax + 90.0, 0.0, az, 0.0))
    assert R.interest_check(xz, 50.0, 12.0, m, final)["npc_stray"] == 1
    # an NPC the partner sees well inside this client's box, not held
    _p, final, xz, m = _world()
    del m[1]["npcA"]
    got = R.interest_check(xz, 50.0, 12.0, m, final)
    assert got["npc_cross_missing"] == 1


def test_schedule_same_work_every_seed():
    a = orbit.schedule(MIX, 8, 1, 40.0, 0)
    b = orbit.schedule(MIX, 8, 2**31 + 5, 40.0, 0)
    for offs, who, kind in (a, b):
        assert len(offs) == 8 * (200 + 40)
        assert (np.diff(offs) >= 0).all() and offs.max() < 40.0
        assert (np.bincount(who[kind == orbit.SEND]) == 200).all()
        assert (np.bincount(who[kind == orbit.RPC]) == 40).all()
    assert not np.array_equal(a[0], b[0])
    again = orbit.schedule(MIX, 8, 1, 40.0, 0)
    assert np.array_equal(a[0], again[0])


def test_plan_groups_apart_and_seq_readable():
    plan = orbit.Plan(dict(MIX, group_size=8), 3305.0, 50.0, 512)
    t = plan.positions(4096)
    assert (t[:, :, 1] == np.arange(4096)[None, :]).all()
    xz = t[:, 7, :][:, [0, 2]]
    nb = neighbourhoods(xz, 50.0)
    for c in range(512):
        assert nb[c] == set(plan.members(plan.group_of(c))) - {c}
    assert plan.observer(7) == 0 and plan.observer(8) == 9
    with pytest.raises(ValueError):
        orbit.Plan(dict(MIX, group_size=8), 1000.0, 50.0, 512)


def test_twin_sites_cross_the_edge_and_keep_their_group():
    mix = dict(MIX, twin_sites=2, twin_gap=60.0)
    plan = orbit.Plan(mix, 3000.0, 50.0, 12)
    t = plan.positions(300)
    seen_in = seen_out = 0
    for q in range(0, 300, 5):
        nb = neighbourhoods(t[:, q, :][:, [0, 2]], 50.0)
        for c in range(12):
            own = set(plan.members(plan.group_of(c))) - {c}
            assert own <= nb[c]                 # the observer never loses it
            assert nb[c] - own <= {d for a, d in plan.crossers() if a == c}
        seen_in += (1 in nb[2]) + (0 in nb[3])
        seen_out += (1 not in nb[2]) + (0 not in nb[3])
    assert seen_in > 10 and seen_out > 10       # both sides of the edge
    assert len(plan.crossers()) == 2 * 2 * 2 * 2
    assert not orbit.Plan(MIX, 3000.0, 50.0, 12).crossers()
    with pytest.raises(ValueError):
        orbit.Plan(dict(mix, twin_sites=4), 3000.0, 50.0, 12)


def test_rows_check_catches_a_wrong_list_and_a_stale_avatar():
    rng = np.random.default_rng(3)
    n, cap = 400, 512
    pos = np.zeros((cap, 3), np.float32)
    pos[:n, [0, 2]] = rng.uniform(0.0, 600.0, (n, 2))
    alive = np.arange(cap) < n
    want = neighbourhoods(pos[:n, [0, 2]], 50.0)
    k = max(len(s) for s in want) + 1
    nbr = np.full((n, k), cap, np.int32)
    for i, s in enumerate(want):
        nbr[i, :len(s)] = sorted(s)
    rows = np.arange(0, n, 3)
    final = np.concatenate([pos[:4], np.zeros((4, 1), np.float32)], axis=1)
    ok = R.rows_check(pos, alive, rows, nbr[rows], 50.0, np.arange(4), final)
    assert ok == {"rows_wrong": 0, "avatar_row_off": 0,
                  "rows_wrong_near_border": 0}
    bad = nbr[rows].copy()
    full = next(i for i in range(len(rows)) if bad[i, 0] < cap)
    bad[full, 0] = cap                      # a neighbour left out
    bad[full + 1, -1] = int(rows[full + 1]) ^ 1   # one too many
    final[2, 0] += 0.5                      # the device holds another place
    got = R.rows_check(pos, alive, rows, bad, 50.0, np.arange(4), final)
    assert got == {"rows_wrong": 2, "avatar_row_off": 1,
                   "rows_wrong_near_border": 0}


def test_cross_check_wants_an_answer_after_each_definite_crossing():
    assert R.excursions([0, 1, 2, 3, 4, 5, 6],
                        [60, 52, 49, 46, 51, 54, 60], 50.0, 3.0) == \
        [("enter", 0), ("leave", 3)]
    assert R.excursions([0, 1, 2], [60, 49, 60], 50.0, 3.0) == []
    mix = dict(MIX, twin_sites=1, twin_gap=60.0)
    plan = orbit.Plan(mix, 3000.0, 50.0, 4)
    table = plan.positions(400)
    sends = sorted([(c, q, 0.2 * q + 0.01 * c) for c in (1, 2)
                    for q in range(1, 300)], key=lambda s: s[2])
    ref = R.cross_check([(1, 2)], sends, table, {}, 50.0, 3.0)
    assert ref["crossings"] >= 2 and ref["cross_missed"] == ref["crossings"]
    # answer every crossing a second late: nothing missed
    by = {}
    for c, q, t in sends:
        by.setdefault(c, []).append((t, q))
    qc = qd = 0
    ts, ds = [], []
    for c, q, t in sends:
        qc, qd = (q, qd) if c == 1 else (qc, q)
        ts.append(t)
        ds.append(np.abs(table[1, qc, [0, 2]].astype(float)
                         - table[2, qd, [0, 2]].astype(float)).max())
    ev = [(t + 1.0, kind == "enter")
          for kind, t in R.excursions(ts, ds, 50.0, 3.0)]
    assert R.cross_check([(1, 2)], sends, table, {(1, 2): ev}, 50.0,
                         3.0)["cross_missed"] == 0
    # one answer lost, and an answer that came before its crossing
    assert R.cross_check([(1, 2)], sends, table, {(1, 2): ev[1:]}, 50.0,
                         3.0)["cross_missed"] >= 1
    early = [(t - 2.0, made) for t, made in ev]
    assert R.cross_check([(1, 2)], sends, table, {(1, 2): early}, 50.0,
                         3.0)["cross_missed"] >= 1


def test_reference_by_sorted_x_equals_every_pair():
    """``neighbours_of`` against the brute force over every pair, on
    points of a lattice (many pairs at EXACTLY the radius, in x, in z
    and in both) and on random f32 points."""
    from reference import chebyshev, neighbours_of

    def every_pair(xz, rows, radius):
        d = chebyshev(xz[rows], xz)
        return [{int(j) for j in np.nonzero(d[r] <= radius)[0] if j != i}
                for r, i in enumerate(rows)]

    rng = np.random.default_rng(11)
    lattice = rng.integers(0, 12, (600, 2)).astype(np.float64) * 25.0
    cloud = rng.uniform(0.0, 2000.0, (3000, 2)).astype(np.float32) \
        .astype(np.float64)
    for xz in (lattice, cloud):
        rows = rng.choice(len(xz), 200, replace=False)
        assert neighbours_of(xz, rows, 50.0) \
            == every_pair(xz, rows, 50.0)
