"""From the clients' logs to the end-to-end metrics and to the numbers
that decide ``correct``. Pure numpy: what the bots child and the tests
call; nothing here touches a socket or the program.
"""
from __future__ import annotations

import math

import numpy as np

from reference import chebyshev, neighbourhoods, neighbours_of


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile (0 < q <= 1) by nearest rank: the smallest
    value with at least ``q`` of the sample at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("no sample")
    return float(v[max(math.ceil(q * v.size) - 1, 0)])


def match_sends(send_client, send_seq, observer, rc_recv, rc_sender,
                rc_seq, rc_t) -> np.ndarray:
    """For every send the first instant at which its observer's mirror
    held that position or a later one of the same sender (NaN: never).

    ``observer[c]`` is the client that times ``c``'s sends; receipts
    are (receiver, sender, sequence number, instant handled)."""
    send_client = np.asarray(send_client, np.int64)
    send_seq = np.asarray(send_seq, np.int64)
    observer = np.asarray(observer, np.int64)
    rc_recv, rc_sender, rc_seq = (np.asarray(a, np.int64)
                                  for a in (rc_recv, rc_sender, rc_seq))
    rc_t = np.asarray(rc_t, np.float64)
    seen = np.full(send_client.shape, np.nan)
    keep = observer[rc_sender] == rc_recv
    s, q, t = rc_sender[keep], rc_seq[keep], rc_t[keep]
    order = np.lexsort((t, s))
    s, q, t = s[order], q[order], t[order]
    lo = np.searchsorted(s, np.arange(len(observer)), "left")
    hi = np.searchsorted(s, np.arange(len(observer)), "right")
    for c in np.unique(send_client):
        a, b = lo[c], hi[c]
        if a == b:
            continue
        best = np.maximum.accumulate(q[a:b])
        mine = np.nonzero(send_client == c)[0]
        at = np.searchsorted(best, send_seq[mine], "left")
        ok = at < b - a
        seen[mine[ok]] = t[a:b][at[ok]]
    return seen


def latencies(due, seen, close: float) -> tuple[np.ndarray, int]:
    """Milliseconds from due to seen; an operation never seen by
    ``close`` is failed and enters at its wait until then (it can only
    have been worse). Returns (ms, failed)."""
    due = np.asarray(due, np.float64)
    seen = np.asarray(seen, np.float64)
    failed = ~(seen <= close)
    ms = np.where(failed, close - due, seen - due) * 1e3
    return ms, int(failed.sum())


def stream_faults(rc_recv, rc_sender, rc_seq, rc_vals, table,
                  clients: int) -> tuple[int, int]:
    """Over every receipt of every client: (records whose x, z or yaw
    is not what the sender sent under that sequence number, records
    whose number is lower than that of the record before them on the
    same receiver's stream of the same sender)."""
    rc_recv, rc_sender, rc_seq = (np.asarray(a, np.int64)
                                  for a in (rc_recv, rc_sender, rc_seq))
    rc_vals = np.asarray(rc_vals, np.float32).reshape(-1, 4)
    known = (rc_seq >= 0) & (rc_seq < table.shape[1]) \
        & (rc_vals[:, 1] == rc_seq.astype(np.float32))
    want = table[rc_sender[known], rc_seq[known]]
    wrong = int((~known).sum()) + int(
        (want.view(np.uint32) != rc_vals[known].view(np.uint32))
        .any(axis=1).sum())
    key = rc_recv * clients + rc_sender
    order = np.argsort(key, kind="stable")     # keeps arrival order
    k, q = key[order], rc_seq[order]
    back = int(((k[1:] == k[:-1]) & (q[1:] < q[:-1])).sum())
    return wrong, back


def interest_check(final_xz, radius: float, slack: float, mirrors,
                   final_vals, tile=None, space=None) -> dict[str, int]:
    """The clients' mirrors, once the world has settled, against the
    brute-force reference over true positions.

    ``final_xz[c]`` is client ``c``'s last sent position (true: the
    benchmark made it). ``mirrors[c]`` maps what ``c`` mirrors to
    ``("client", d, vals)`` or ``("npc", id, vals)``. ``final_vals[d]``
    is ``d``'s last sent record (f32[4]).

    * ``final_missing``: a client the reference puts in ``c``'s
      neighbourhood that ``c`` does not mirror, or mirrors at another
      place than its last sent one;
    * ``interest_extra``: a client ``c`` mirrors that the reference
      puts outside;
    * ``npc_stray``: a mirrored NPC farther from ``c`` than the radius
      plus the slack (what an NPC can move while records are in flight);
    * ``npc_cross_missing``: an NPC some client mirrors at a place well
      inside ``c``'s box (radius minus slack) that ``c`` does not hold.

    ``tile[c]`` (a tiled world) is the tile ``c``'s final position lies
    in: ``finals_over_border`` then counts the clients whose reference
    neighbourhood holds a client of another tile, which says how much
    of the above was judged across a seam (no fault by itself).

    ``space[c]`` (a world of many spaces, which share their coordinates
    and nothing else) is the space ``c`` entered last: everything above
    is then judged within each space — the reference's neighbourhood
    holds clients of the same space only, and an NPC is held to the
    space of the first client that mirrors it — and ``space_wrong``
    counts the mirrored clients of another space and the NPCs that
    clients of two spaces mirror.
    """
    final_xz = np.asarray(final_xz, np.float64)
    want = neighbourhoods(final_xz, radius, space)
    out = dict(final_missing=0, interest_extra=0, npc_stray=0,
               npc_cross_missing=0, finals_over_border=0)
    if space is not None:
        out["space_wrong"] = 0
    if tile is not None:
        out["finals_over_border"] = sum(
            any(tile[d] != tile[c] for d in want[c])
            for c in range(len(want)))
    npc_at: dict = {}
    npc_space: dict = {}
    for c, mir in enumerate(mirrors):
        got = set()
        for kind, ident, vals in mir.values():
            if space is not None and (
                    space[ident] if kind == "client"
                    else npc_space.setdefault(ident, space[c])) != space[c]:
                out["space_wrong"] += 1
            if kind == "client":
                got.add(ident)
                if ident in want[c] and not np.array_equal(
                        np.asarray(vals, np.float32),
                        np.asarray(final_vals[ident], np.float32)):
                    out["final_missing"] += 1
            else:
                npc_at[ident] = (vals[0], vals[2])
        out["final_missing"] += len(want[c] - got)
        out["interest_extra"] += len(got - want[c])
    if npc_at:
        ids = list(npc_at)
        col = {n: i for i, n in enumerate(ids)}
        d = chebyshev(final_xz, np.array([npc_at[n] for n in ids]))
        if space is not None:          # another space's NPC is nowhere near
            d[np.asarray(space)[:, None]
              != np.array([npc_space[n] for n in ids])[None, :]] = np.inf
        for c, mir in enumerate(mirrors):
            held = np.zeros(len(ids), bool)
            for kind, ident, _vals in mir.values():
                if kind == "npc":
                    held[col[ident]] = True
            out["npc_stray"] += int((held & (d[c] > radius + slack)).sum())
            out["npc_cross_missing"] += int(
                (~held & (d[c] < radius - slack)).sum())
    return out


def rows_check(pos, alive, rows, nbr, radius: float, avatar_rows,
               final_vals, near_border=None, space=None,
               capacity=None) -> dict[str, int]:
    """What the game read back from the device once the world had
    settled (program-prepared data: positions of every row, the
    neighbour lists of the sampled ``rows``) against the reference.

    * ``rows_wrong``: sampled rows, NPC rows among them, whose
      neighbour list is not the brute-force neighbourhood over the
      positions read back with it (exact; the list's sentinel is the
      capacity);
    * ``avatar_row_off``: clients whose device row does not hold the
      last position they sent, bit for bit.

    A tiled world comes as ONE world: ``pos``, ``alive`` over every
    tile's rows under the global row number (tile * capacity + slot),
    which is also what the lists hold; the brute force knows no tiles.
    ``near_border[i]`` says that sampled row ``i`` lies within the
    radius of a tile border: ``rows_wrong_near_border`` counts the
    wrong ones among those (part of ``rows_wrong``, not a number of its
    own). A world of many spaces comes the same way (space * capacity +
    slot) with ``space[row]`` for every row: the brute force then holds
    a neighbourhood to the rows of the same space. There a list holds
    SLOTS of its own space (every space is a tick of its own, vmapped;
    the sentinel is the ``capacity`` of one space), which are turned
    into row numbers here.
    """
    pos = np.asarray(pos, np.float32)
    live = np.nonzero(np.asarray(alive, bool))[0]
    at = np.full(len(pos), -1, np.int64)
    at[live] = np.arange(len(live))
    rows = np.asarray(rows, np.int64)
    want = neighbours_of(pos[live][:, [0, 2]], at[rows], radius,
                         None if space is None else np.asarray(space)[live])
    wrong = wrong_near = 0
    for i, lst in enumerate(np.asarray(nbr, np.int64)):
        if capacity is None:
            got = {int(j) for j in lst if 0 <= j < len(pos)}
        else:
            base = rows[i] // capacity * capacity
            got = {int(base + j) for j in lst if 0 <= j < capacity}
        if at[rows[i]] < 0 or got != {int(live[j]) for j in want[i]}:
            wrong += 1
            wrong_near += int(near_border is not None and near_border[i])
    fv = np.asarray(final_vals, np.float32)[:, :3]
    held = pos[np.asarray(avatar_rows, np.int64)]
    off = int((held.view(np.uint32) != fv.view(np.uint32)).any(axis=1).sum())
    return {"rows_wrong": wrong, "avatar_row_off": off,
            "rows_wrong_near_border": wrong_near}


def entities_lost(alive, live: int) -> int:
    """Live rows the device holds against the configured number: a
    migration that drops a row reads one too few, one that doubles a
    row one too many (ghost rows are no rows of the world and are never
    read back)."""
    return abs(int(np.count_nonzero(alive)) - int(live))


def space_wrong(avatar_space, space_now, mirrored, npc_space) -> int:
    """A world of many spaces, once it has settled: clients whose
    avatar's row lies in another space than the one they entered last
    (``avatar_space[c]``, from the device's rows, against
    ``space_now[c]``), and mirrored NPCs that live in another space
    than the client that mirrors them (``mirrored[c]``: the ids ``c``
    holds; ``npc_space``: id -> space, for every NPC of the world)."""
    wrong = int((np.asarray(avatar_space) != np.asarray(space_now)).sum())
    for c, ids in enumerate(mirrored):
        wrong += sum(bool(npc_space.get(i, space_now[c]) != space_now[c])
                     for i in ids)
    return int(wrong)


def hop_check(hops, observer, made_at, scheduled: int,
              window_end: float) -> dict[str, int]:
    """Changes of space inside the window. ``hops`` are (client,
    instant sent, instant answered or NaN); ``made_at[(r, c)]`` the
    (instant, created?) events of ``c``'s avatar at the mirror of its
    observer ``r``, in arrival order; ``scheduled`` how many the
    window's schedule held.

    * ``hop_unanswered``: hops never answered, and answered hops that
      the partner's mirror was not told of, between the hop and the
      client's next one, as a leaving and then an entering (both
      members of a pair hop together: the partner sees the avatar go
      where it leaves and come where it arrives). A second leaving and
      entering may follow the first — the gate forwards position syncs
      in batches and calls at once, as upstream's does, so a position
      of the old place sent just before the call can be applied in the
      new one and carry the avatar out of its partner's sight for a
      frame — but never two leavings or two enterings in a row (an
      entity doubled or lost in the mirror), and the last is an
      entering;
    * ``hops_untested``: 1 where the schedule held hops and fewer than
      three quarters of them were answered before the window's end (a
      run that has not tested what its cell is for), else 0."""
    by: dict[int, list] = {}
    for c, sent, seen in hops:
        by.setdefault(int(c), []).append((sent, seen))
    bad = done = 0
    first_bad: list = []
    for c, mine in by.items():
        mine.sort()
        ev = made_at.get((int(observer[c]), c), [])
        for i, (sent, seen) in enumerate(mine):
            if not seen == seen:                   # NaN: never answered
                bad += 1
                continue
            done += int(seen <= window_end)
            until = mine[i + 1][0] if i + 1 < len(mine) else np.inf
            mid = [made for t, made in ev if sent <= t < until]
            if not mid or len(mid) % 2 or any(
                    made != bool(i % 2) for i, made in enumerate(mid)):
                bad += 1
                first_bad.append(
                    [int(c), sent, seen, [[t, made] for t, made in ev]])
    return {"hop_unanswered": bad, "hops": len(hops), "hops_done": done,
            "first_bad": first_bad[:4],
            "hops_untested": int(bool(scheduled) and 4 * done < 3 * scheduled)}


def excursions(t, dist, radius: float, band: float) -> list[tuple]:
    """Definite crossings of the AOI edge in a distance series: (kind,
    instant), kind ``"enter"`` for a swing from beyond ``radius + band``
    to within ``radius - band`` and ``"leave"`` for the reverse; the
    instant is the last one on the side it came from."""
    out, side, last = [], 0, None
    for ti, d in zip(t, dist):
        now = 1 if d > radius + band else -1 if d < radius - band else 0
        if now and side and now != side:
            out.append(("enter" if now < 0 else "leave", last))
        if now:
            side, last = now, ti
    return out


def cross_check(pairs, sends, table, events, radius: float,
                band: float, tile_of=None) -> dict[str, int]:
    """Enters and leaves between clients inside the window. For every
    ordered pair (c, d) the reference walks both clients' sent
    positions in time order; every definite crossing it finds has to be
    answered at ``c``'s mirror by a create (resp. destroy) of ``d``
    after the last instant the reference still had them on the old
    side. ``sends`` are (client, seq, instant) in time order, ``events``
    maps (c, d) to its (instant, created?) list in arrival order.

    ``crossings``: definite crossings the reference found;
    ``cross_missed``: those the mirror never answered;
    ``crossings_over_border``: those at which the two clients stood in
    different tiles (``tile_of(x, z)``: a tiled world's rule)."""
    by: dict[int, list] = {}
    for c, q, t in sends:
        by.setdefault(int(c), []).append((t, int(q)))
    found = missed = over = 0
    for c, d in pairs:
        sc, sd = by.get(c), by.get(d)
        if not sc or not sd:
            continue
        qc, qd = sc[0][1] - 1, sd[0][1] - 1
        merged = sorted([(t, 0, q) for t, q in sc]
                        + [(t, 1, q) for t, q in sd])
        ts, ds, apart = [], [], {}
        for t, who, q in merged:
            if who:
                qd = q
            else:
                qc = q
            a = table[c, qc, [0, 2]].astype(np.float64)
            b = table[d, qd, [0, 2]].astype(np.float64)
            ts.append(t)
            ds.append(float(np.abs(a - b).max()))
            if tile_of is not None:
                apart[t] = tile_of(a[0], a[1]) != tile_of(b[0], b[1])
        got = list(events.get((c, d), ()))
        for kind, after in excursions(ts, ds, radius, band):
            found += 1
            over += int(apart.get(after, False))
            k = next((i for i, (t, made) in enumerate(got)
                      if t > after and made == (kind == "enter")), None)
            if k is None:
                missed += 1
            else:
                del got[:k + 1]
    return {"crossings": found, "cross_missed": missed,
            "crossings_over_border": over}
