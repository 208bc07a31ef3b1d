"""The load generator: a jax-free child of ``run.py`` that holds every
client of the cell, drives the mix's open-loop schedule through the
gate socket, stamps every receipt where the packet is handled, and
reduces its logs to the client-side metrics and check numbers.

Talks to the parent in lines: prints ``READY`` once every client is
logged in, placed and mirrors its whole group; reads ``WINDOW <t0>
<seconds> <grace>`` (``t0`` on ``time.monotonic()``, which parent and
child share); writes its result to ``--out`` and prints ``DONE``.
"""
from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import reduce as R  # noqa: E402
from world import Shape  # noqa: E402
from goworld_tpu.net import codec, proto  # noqa: E402
from goworld_tpu.net.botclient import BotClient  # noqa: E402

MAX_SEQ = 8192          # sends a client can make in one run
LOGIN_TIMEOUT_S = 150.0  # for logins and placement together, and ...
WAVE_TIMEOUT_S = 12.0    # ... this much more for every wave of clients
SETTLE_TIMEOUT_S = 60.0  # an answer that comes late is late, not wrong
ROWS_SAMPLE = 768        # NPC rows read back beside every avatar's row, a
#                          tile; in a tiled world half of them from rows
#                          within the radius of a tile border
CROSS_BAND = 3.0         # a definite crossing swings this far past the edge
# Logins, and then the first sends (each a jump from the parking spot to
# the group's anchor), come in waves that double from WAVE_FIRST clients
# until one would bring a tile more than WAVE_MAX: a tile's tick decodes
# at most enter_cap = leave_cap = 4,096 interest events and drops the
# rest, and a client that enters or jumps makes some 25 to 40 of each.
# In a tiled world the clients log in and jump in an order that goes
# round the tiles (by their groups' anchors; the fixture parks the
# logins round the tiles too), so a wave brings every tile its share
# and grows to nearly WAVE_MAX x tiles. A wave starts once the game has
# answered the one before it (a login frame can take seconds, and waves
# on a timer would pile up in it) and WAVE_CALM_FRAMES calm frames have
# passed (a second at the most): a wave's frame stalls for over a second
# when it brings a staging batch of a new size (the eager scatters
# compile in the serve loop, one set per power-of-two bucket from 8 up),
# two such frames in a row read as severe overload twice, and the
# ladder leaves NORMAL. Doubling, a wave that the game meets in two
# parts brings at most one new bucket.
WAVE_FIRST, WAVE_MAX, WAVE_CALM_FRAMES, WAVE_CALM_MAX_S = 8, 64, 3, 1.0


def round_the_tiles(tile_of_group, members) -> np.ndarray:
    """The clients in the order of their waves: whole groups, every
    tile's groups spread evenly over the whole order (the k-th of a
    tile's m groups stands at (k + 0.5) / m), so any stretch of it holds
    each tile's share. With one tile that is the clients' own order."""
    tile_of_group = np.asarray(tile_of_group, np.int64)
    at = np.empty(len(tile_of_group))
    for t in np.unique(tile_of_group):
        mine = np.nonzero(tile_of_group == t)[0]
        at[mine] = (np.arange(len(mine)) + 0.5) / len(mine)
    groups = np.lexsort((tile_of_group, at))
    return np.array([c for g in groups for c in members(g)], np.int64)


def wave_bounds(tiles_in_order, g: int) -> list[int]:
    """Where the waves begin and end in the order of the clients: whole
    groups of ``g``, each wave twice the one before it, cut where it
    would bring one tile more than WAVE_MAX clients."""
    n = len(tiles_in_order)
    bounds, size = [0], WAVE_FIRST
    while bounds[-1] < n:
        lo = bounds[-1]
        hi = min(n, lo + max(g, size - size % g))
        while hi - g > lo and np.bincount(
                tiles_in_order[lo:hi]).max() > WAVE_MAX:
            hi -= g
        bounds.append(hi)
        size = 2 * (hi - lo)
    return bounds


def does(op: tuple) -> dict:
    """What a generator's ``("call", method, args, does)`` says it does
    to its client (nothing, where it says nothing)."""
    return op[3] if len(op) > 3 else {}


def load_generator(kind: str):
    path = os.path.join(HERE, "generators", f"{kind}.py")
    spec = importlib.util.spec_from_file_location(f"gen_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Log:
    """Receipts of every client, in arrival order."""

    def __init__(self):
        self.by_eid: dict[bytes, int] = {}    # avatar eid -> client
        self.sync: list[tuple] = []   # (receiver, t, senders, vals)
        self.echo: list[tuple] = []   # (receiver, t, token)
        self.reply: list[tuple] = []  # (receiver, t, method, token): the
        #                               fixture's answer to any other call
        self.made: list[tuple] = []   # (receiver, t, eid, created?)
        self.stats: str | None = None
        self.rows: str | None = None


class Client(BotClient):
    """The SDK's bot with its receipts stamped where they are handled.
    Everything but position batches and RPCs goes through the SDK's own
    strict mirror."""

    def __init__(self, *a, log: Log, idx: int, **kw):
        super().__init__(*a, **kw)
        self.log, self.idx = log, idx

    def _handle_inner(self, msgtype, pkt):
        if msgtype == proto.MT_CLIENT_SYNC_POSITION_YAW:
            t = time.monotonic()
            eids, vals = codec.decode_sync_batch(
                memoryview(pkt.buf)[pkt.rpos:])
            who, rows = [], []
            by_eid, ents = self.log.by_eid, self.entities
            for i, eid_b in enumerate(eids.tolist()):
                me = ents.get(eid_b.decode("ascii", "replace"))
                if me is None:
                    continue
                v = vals[i]
                me.pos = (float(v[0]), float(v[1]), float(v[2]))
                me.yaw = float(v[3])
                self.sync_count += 1
                c = by_eid.get(eid_b)
                if c is not None:
                    who.append(c)
                    rows.append(i)
            if who:
                self.log.sync.append((self.idx, t, who, vals[rows]))
            return
        if msgtype == proto.MT_CALL_ENTITY_METHOD_ON_CLIENT:
            t = time.monotonic()
            n = len(self.rpc_log)
            super()._handle_inner(msgtype, pkt)
            for _eid, method, args in self.rpc_log[n:]:
                if method == "OnEcho":
                    self.log.echo.append((self.idx, t, args[0]))
                elif method == "OnStats":
                    self.log.stats = args[0]
                elif method == "OnRows":
                    self.log.rows = args[0]
                elif args:
                    self.log.reply.append((self.idx, t, method, args[0]))
            del self.rpc_log[n:]
            return
        if msgtype in (proto.MT_CREATE_ENTITY_ON_CLIENT,
                       proto.MT_DESTROY_ENTITY_ON_CLIENT):
            at = pkt.rpos
            eid = pkt.read_entity_id()
            pkt.rpos = at
            self.log.made.append(
                (self.idx, time.monotonic(), eid,
                 msgtype == proto.MT_CREATE_ENTITY_ON_CLIENT))
        super()._handle_inner(msgtype, pkt)


async def main_async(a) -> int:
    with open(a.config) as f:
        cfg = json.load(f)
    with open(a.mix) as f:
        mix = json.load(f)
    gen = load_generator(mix["kind"])
    n = int(mix["clients"])
    radius = float(cfg["game"]["aoi_radius"])
    shape = Shape(cfg)
    plan = gen.Plan(mix, shape.extent_x, radius, n,
                    **({"borders": shape.borders} if shape.mega else
                       {"spaces": shape.spaces} if shape.spaces > 1 else {}))
    tile_of = shape.tile_of if shape.mega else None
    table = plan.positions(MAX_SEQ)
    # a plan of a world of many spaces gives the call with which each
    # client enters it, at (space, x, z). Anywhere else the fixture
    # parks the logins itself
    login = [plan.login(c) for c in range(n)] \
        if hasattr(plan, "login") else None
    space_now = np.array([does(op)["at"][0] for op in login], np.int64) \
        if login else np.zeros(n, np.int64)
    stands_at = [does(op)["at"][1:] for op in login] if login else None
    stood_at: list[set] = [set() for _ in range(n)]  # every place so far
    # a plan that keeps state schedules itself, and is told the first
    # instant of every stretch
    schedule = plan.schedule if hasattr(plan, "schedule") else (
        lambda seed, seconds, stream, _start: gen.schedule(
            mix, n, seed, seconds, stream))
    observer = np.array([plan.observer(c) for c in range(n)])
    loop = asyncio.get_running_loop()
    log = Log()
    bots = [Client("127.0.0.1", a.gate_port, bot_id=i, strict=True,
                   nosync=True, log=log, idx=i) for i in range(n)]
    tasks = []
    gap = min(WAVE_CALM_FRAMES / float(cfg["game"]["tick_hz"]),
              WAVE_CALM_MAX_S)
    seq = np.zeros(n, np.int64)            # last sequence number sent
    written: list[dict] = [{} for _ in range(n)]   # attr -> last value

    def call(c: int, op: tuple, token: str, at: float) -> None:
        """``("call", method, args, does)``: a call of one of the
        fixture's ``*_Client`` methods, due at ``at``; the token comes
        back first in the fixture's reply. What the call does to its
        client the operation says itself (``does``): ``at`` (space, x,
        z): the avatar stands there from now on, so the client sends
        from there (the rows of its table that are still to be sent
        move by the difference); ``attrs``: values every holder of the
        avatar must show at the end. No method is known here by name."""
        bots[c].call_server(op[1], *op[2], token)
        if "at" in does(op):
            space, x, z = does(op)["at"]
            space_now[c] = int(space)
            dx, dz = x - stands_at[c][0], z - stands_at[c][1]
            if dx or dz:
                table[c, seq[c] + 1:, 0] += np.float32(dx)
                table[c, seq[c] + 1:, 2] += np.float32(dz)
            stands_at[c] = (x, z)
            stood_at[c].add((np.float32(x), np.float32(z)))
        written[c].update(does(op).get("attrs", {}))
        if hasattr(plan, "called"):
            plan.called(c, op, at)
    # the order of the waves, and every client's place in it
    # (what a wave must not bring too much of: a tile, or a space)
    first_tile = space_now.copy() if tile_of is None \
        else tile_of(table[:, 1, 0], table[:, 1, 2])
    order = round_the_tiles(
        [first_tile[plan.members(g)[0]] for g in range(n // plan.g)],
        plan.members)
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    bounds = wave_bounds(first_tile[order], plan.g)
    waves = list(zip(bounds[:-1], bounds[1:]))
    end = time.monotonic() + LOGIN_TIMEOUT_S + WAVE_TIMEOUT_S * len(waves)
    for lo, hi in waves:
        wave = [bots[c] for c in order[lo:hi]]
        for b in wave:
            await b.connect()
            tasks.append(loop.create_task(b._recv_loop()))
        asked = False
        while not all(b.player is not None for b in wave) or (
                login and not (asked and wanted <= {
                    tok for _r, _t, _m, tok in log.reply[n_reply:]})):
            if time.monotonic() > end:
                print("FAILED login: %d of %d clients have a player" % (
                    sum(b.player is not None for b in bots), n),
                    flush=True)
                return 1
            if login and not asked \
                    and all(b.player is not None for b in wave):
                asked, n_reply = True, len(log.reply)
                wanted = {f"login.{c}" for c in order[lo:hi]}
                for c in order[lo:hi]:
                    call(int(c), login[c], f"login.{c}", time.monotonic())
            await asyncio.sleep(0.05)
        await asyncio.sleep(gap)
    for i, b in enumerate(bots):
        log.by_eid[b.player.eid.encode("ascii")] = i

    wave_at = 0
    moving = waves[0][1]                   # clients that may send yet
    window: dict = {}
    sends: list[tuple] = []                # (client, seq, due, sent)
    calls: list[tuple] = []                # (client, token, due, sent)
    others: list[tuple] = []               # (client, op, token, due, sent)

    async def drive(offs, who, kind, start, stop, record) -> None:
        """Run one stretch of schedule, open loop, until ``stop()``
        gives an instant that has come."""
        for k in range(len(offs)):
            due = start + float(offs[k])
            cut = stop()
            if cut is not None and due >= cut:
                return
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            c = int(who[k])
            if rank[c] >= moving:
                continue                   # its wave has not come yet
            if isinstance(kind[k], tuple):     # ("call", method, args, does)
                token = f"{a.seed}.{c}.{kind[k][1]}.{len(others)}.{record}"
                call(c, kind[k], token, due)
                if record:
                    others.append((c, kind[k], token, due,
                                   time.monotonic()))
            elif kind[k] == gen.SEND:
                seq[c] += 1
                v = table[c, seq[c]]
                bots[c].send_position(float(v[0]), float(v[1]),
                                      float(v[2]), float(v[3]))
                if record:
                    sends.append((c, int(seq[c]), due, time.monotonic()))
            else:
                token = f"{a.seed}.{c}.{len(calls)}.{record}"
                bots[c].call_server("Echo_Client", token)
                if record:
                    calls.append((c, token, due, time.monotonic()))

    def read_window() -> None:
        line = sys.stdin.readline().split()
        if len(line) == 4 and line[0] == "WINDOW":
            window.update(t0=float(line[1]), seconds=float(line[2]),
                          grace=float(line[3]))
        else:
            window.update(t0=time.monotonic(), seconds=0.0, grace=0.0,
                          aborted=True)

    reader = loop.run_in_executor(None, read_window)

    met = np.zeros(n, bool)                # has mirrored its whole group
    #                                        at a position it SENT (height
    #                                        >= 1): once is enough, a change
    #                                        of place puts a partner at
    #                                        height 0 again for a frame

    def placed(upto: int) -> bool:
        for c in order[:upto]:
            if not met[c]:
                ents = bots[c].entities
                met[c] = all(
                    (m := ents.get(bots[d].player.eid)) is not None
                    and m.pos[1] >= 1.0
                    for d in plan.members(plan.group_of(c)) if d != c)
        return bool(met[order[:upto]].all())

    # warm-up: the same cadence as the window, in stretches of 10 s
    ready = False
    stretch = 0
    while "t0" not in window or time.monotonic() < window["t0"]:
        stretch += 1
        start = time.monotonic()
        offs, who, kind = schedule(a.seed, 10.0, stretch, start)
        task = loop.create_task(drive(
            offs, who, kind, start, lambda: window.get("t0"), False))
        while not task.done():
            await asyncio.sleep(0.1)
            if not ready and placed(moving):
                if moving < n:
                    await asyncio.sleep(gap)
                    wave_at += 1
                    moving = waves[wave_at][1]       # the next wave
                else:
                    ready = True
                    print("READY", flush=True)
        await task
        if "t0" in window:
            break
        if not ready and time.monotonic() > end:
            print("FAILED placement: groups never mirrored each other",
                  flush=True)
            return 1
        await asyncio.sleep(max(0.0, start + 10.0 - time.monotonic()))
    await reader
    if window.get("aborted"):
        return 1

    # ---- the window ----------------------------------------------------
    t0, seconds, grace = window["t0"], window["seconds"], window["grace"]
    n_sync0, n_echo0 = len(log.sync), len(log.echo)
    n_reply0 = len(log.reply)
    offs, who, kind = schedule(a.seed, seconds, 0, t0)
    await asyncio.sleep(max(0.0, t0 - time.monotonic()))
    await drive(offs, who, kind, t0, lambda: None, True)
    close = t0 + seconds + grace
    await asyncio.sleep(max(0.0, close - time.monotonic()))

    # ---- settle: wait for the answers that are due, then judge them ----
    slack = float(cfg.get("check", {}).get("npc_slack", 12.0))

    def snapshot():
        final_vals = table[np.arange(n), seq]      # the last position sent
        final_xz = final_vals[:, [0, 2]]
        mirrors = []
        for c, b in enumerate(bots):
            mir = {}
            for eid, m in b.entities.items():
                if b.player is not None and eid == b.player.eid:
                    continue
                vals = (m.pos[0], m.pos[1], m.pos[2], m.yaw)
                d = log.by_eid.get(eid.encode("ascii"))
                mir[eid] = ("client", d, vals) if d is not None \
                    else ("npc", eid, vals)
            mirrors.append(mir)
        out = R.interest_check(
            final_xz, radius, slack, mirrors, final_vals,
            None if tile_of is None
            else tile_of(final_xz[:, 0], final_xz[:, 1]),
            space_now if shape.spaces > 1 else None)
        # an attr a client holds, of its own avatar or of another's, is
        # the last value its owner wrote (none: unset)
        out["attr_wrong"] = sum(
            m.attrs.get(name) != written[d].get(name)
            for b in bots for eid, m in b.entities.items()
            if (d := log.by_eid.get(eid.encode("ascii"))) is not None
            for name in attrs_written)
        return out, final_vals, final_xz

    # the stream ends at the close, a real client's goes on: one that
    # has sent nothing since its last change of place was answered
    # sends its next position then, as it would have 100 ms later. (What
    # it sent meanwhile tells the world nothing for sure: the gate
    # forwards position syncs in batches every 100 ms and calls at once,
    # as upstream's does, so a position sent before the call may be
    # applied after it, in the new place; and the program stages no
    # sync for an avatar between two spaces, as the configuration's
    # guarantee says.)
    attrs_written = sorted({k for w in written for k in w})
    last_sent = {c: sent for c, _q, _due, sent in sends}
    moving_yet = {c: tok for c, op, tok, _due, _sent in others
                  if "at" in does(op)}

    def send_on() -> None:
        told = {(r, tok): t for r, t, _m, tok in log.reply[n_reply0:]}
        for c, tok in list(moving_yet.items()):
            t = told.get((c, tok))
            if t is not None:
                del moving_yet[c]
                if last_sent.get(c, -1.0) <= t:
                    seq[c] += 1
                    v = table[c, seq[c]]
                    bots[c].send_position(float(v[0]), float(v[1]),
                                          float(v[2]), float(v[3]))

    settle_end = time.monotonic() + SETTLE_TIMEOUT_S
    send_on()
    check, final_vals, final_xz = snapshot()
    while (check["final_missing"] or check["interest_extra"]
           or check["attr_wrong"] or moving_yet) \
            and time.monotonic() < settle_end:
        await asyncio.sleep(0.5)
        send_on()
        check, final_vals, final_xz = snapshot()
    settled_s = time.monotonic() - close

    bots[0].call_server("Stats_Client")
    stats_end = time.monotonic() + 30.0
    while log.stats is None and time.monotonic() < stats_end:
        await asyncio.sleep(0.05)
    # ... and, the peak read, what the device holds now (every client's
    # last position has reached it: the mirrors above say so)
    bots[0].call_server("Rows_Client", str(a.seed), ROWS_SAMPLE)
    rows_end = time.monotonic() + 60.0
    while log.rows is None and time.monotonic() < rows_end:
        await asyncio.sleep(0.05)
    # no answer: every row that was to be read counts as wrong, and
    # every entity as lost
    live = int(cfg["world"]["live"])
    rows = {"rows_wrong": ROWS_SAMPLE * shape.tiles + n,
            "avatar_row_off": n, "entities_lost": live, "rows_read": 0,
            "rows_near_border": 0, "rows_wrong_near_border": 0,
            "space_wrong": n}
    if log.rows is not None:
        with np.load(os.path.join(os.path.dirname(a.out), log.rows)) as z:
            order = {e: i for i, e in enumerate(z["avatar_eids"].tolist())}
            mine = [order.get(b.player.eid, -1) for b in bots]
            if min(mine) >= 0:
                # the whole world under one row number, whatever its
                # tiling: the reference knows no tiles
                pos, held = z["pos"], z["rows"]
                near = shape.border_distance(
                    pos[held, 0], pos[held, 2]) <= radius
                many = shape.spaces > 1
                rows = R.rows_check(
                    pos, z["alive"], held, z["nbr"], radius,
                    z["avatar_rows"][mine], final_vals, near,
                    *((shape.space_of(np.arange(len(pos))), shape.capacity)
                      if many else ()))
                # every avatar in the space its client entered last, and
                # no mirror with an NPC of another space (the device's
                # rows say which space an NPC lives in)
                rows["space_wrong"] = R.space_wrong(
                    shape.space_of(z["avatar_rows"][mine]), space_now,
                    [set(b.entities) for b in bots],
                    dict(zip(z["npc_eids"].tolist(),
                             shape.space_of(z["npc_rows"]).tolist()))
                ) if many else 0
                rows["rows_read"] = int(len(held))
                rows["rows_near_border"] = int(near.sum())
                # live rows on the device against the configuration's:
                # a migration that drops or doubles a row reads here
                rows["entities_lost"] = R.entities_lost(z["alive"], live)

    # ---- reduce --------------------------------------------------------
    sync = log.sync[n_sync0:]
    rc_recv = np.concatenate([np.full(len(w), r) for r, _t, w, _v in sync]
                             or [np.zeros(0, np.int64)])
    rc_t = np.concatenate([np.full(len(w), t) for _r, t, w, _v in sync]
                          or [np.zeros(0)])
    rc_sender = np.concatenate([np.asarray(w, np.int64)
                                for _r, _t, w, _v in sync]
                               or [np.zeros(0, np.int64)])
    rc_vals = np.concatenate([v for _r, _t, _w, v in sync]
                             or [np.zeros((0, 4), np.float32)])
    rc_seq = np.rint(rc_vals[:, 1]).astype(np.int64)
    s_client = np.array([s[0] for s in sends], np.int64)
    s_seq = np.array([s[1] for s in sends], np.int64)
    s_due = np.array([s[2] for s in sends])
    s_sent = np.array([s[3] for s in sends])
    seen = R.match_sends(s_client, s_seq, observer, rc_recv, rc_sender,
                         rc_seq, rc_t)
    move_ms, move_failed = R.latencies(s_due, seen, close)
    # late is late, not wrong: what `correct` counts is an operation
    # still unanswered when the settle wait is over
    never_seen = int(np.isnan(seen).sum())
    answered: dict[tuple, float] = {}
    rpc_wrong = 0
    asked = {(c, tok) for c, tok, _d, _s in calls}
    for r, t, tok in log.echo[n_echo0:]:
        if (r, tok) in asked:
            answered.setdefault((r, tok), t)
        elif tok.endswith(".True"):
            rpc_wrong += 1      # a window token at the wrong client, or
            #                     one nobody sent; warm-up echoes may trail
    c_due = np.array([d for _c, _tok, d, _s in calls])
    c_sent = np.array([s for _c, _tok, _d, s in calls])
    c_seen = np.array([answered.get((c, tok), np.nan)
                       for c, tok, _d, _s in calls])
    rpc_ms, rpc_failed = R.latencies(c_due, c_seen, close)
    never_seen += int(np.isnan(c_seen).sum())
    # a record at height 0 is no send's: it is the place a call put
    # the avatar at (the program syncs a row it has just made), to be
    # held against the places that client has stood at, not against a
    # send, and out of the order of the sends
    put = (rc_vals[:, 1] == 0.0) & np.array(
        [bool(stood_at[c]) for c in range(n)], bool)[rc_sender]
    put_wrong = sum(
        (x, z) not in stood_at[c]
        for c, x, z in zip(rc_sender[put].tolist(), rc_vals[put, 0],
                           rc_vals[put, 2]))
    pos_wrong, order_back = R.stream_faults(
        rc_recv[~put], rc_sender[~put], rc_seq[~put], rc_vals[~put],
        table, n)
    pos_wrong += put_wrong
    # the fixture's other calls: answered once each, at their client
    told: dict[tuple, float] = {}
    for r, t, _m, tok in log.reply[n_reply0:]:
        told.setdefault((r, tok), t)
    o_due = np.array([o[3] for o in others])
    o_sent = np.array([o[4] for o in others])
    o_seen = np.array([told.get((o[0], o[2]), np.nan) for o in others])
    other_ms, other_failed = R.latencies(o_due, o_seen, close)
    never_seen += int(np.isnan(o_seen).sum())
    # a change of place: answered, and told to the partner's mirror as
    # a leaving and then an entering
    made_at: dict[tuple, list] = {}
    for r, t, eid, created in log.made:
        d = log.by_eid.get(eid.encode("ascii"))
        if d is not None and r == observer[d] and t >= t0:
            made_at.setdefault((r, d), []).append((t, created))
    hops = R.hop_check(
        [(o[0], o[4], o_seen[i]) for i, o in enumerate(others)
         if "at" in does(o[1])], observer, made_at,
        sum(isinstance(k, tuple) and "at" in does(k) for k in kind),
        t0 + seconds)
    late = np.concatenate([s_sent - s_due, c_sent - c_due,
                           o_sent - o_due]) * 1e3
    # enters and leaves between clients of twin groups, inside the window
    pairs = plan.crossers()
    wanted = set(pairs)
    events: dict[tuple, list] = {}
    for r, t, eid, created in log.made:
        d = log.by_eid.get(eid.encode("ascii"))
        if d is not None and (r, d) in wanted and t >= t0:
            events.setdefault((r, d), []).append((t, created))
    cross = R.cross_check(pairs, [(c, q, t) for c, q, _due, t in sends],
                          table, events, radius, CROSS_BAND, tile_of)
    rows_read = rows.pop("rows_read")
    over_border = {
        "crossings_over_border": cross["crossings_over_border"],
        "finals_over_border": check.pop("finals_over_border"),
        "rows_near_border": rows.pop("rows_near_border"),
        "rows_wrong_near_border": rows.pop("rows_wrong_near_border")}
    rows["space_wrong"] += check.pop("space_wrong", 0)
    numbers = dict(check, **rows, cross_missed=cross["cross_missed"],
                   hop_unanswered=hops["hop_unanswered"],
                   hops_untested=hops["hops_untested"],
                   pos_wrong=pos_wrong, order_back=order_back,
                   rpc_wrong=rpc_wrong, never_seen=never_seen,
                   mirror_errors=sum(len(b.errors) for b in bots))
    out = {
        "metrics": {
            "move_seen_ms.p50": R.nearest_rank(move_ms, 0.50),
            "move_seen_ms.p95": R.nearest_rank(move_ms, 0.95),
            "rpc_ms.p95": R.nearest_rank(rpc_ms, 0.95),
        },
        "attempted": len(sends) + len(calls) + len(others),
        "failed": move_failed + rpc_failed + other_failed,
        "sends": len(sends), "calls": len(calls),
        "move_failed": move_failed, "rpc_failed": rpc_failed,
        "other_calls": {m: sum(o[1][1] == m for o in others)
                        for m in sorted({o[1][1] for o in others})},
        "other_failed": other_failed,
        "other_ms": {"p50": R.nearest_rank(other_ms, 0.5),
                     "p95": R.nearest_rank(other_ms, 0.95)}
        if len(others) else None,
        "hops": hops,
        "numbers": numbers,
        "gen_late_ms": {"p50": R.nearest_rank(late, 0.5),
                        "p95": R.nearest_rank(late, 0.95),
                        "max": float(late.max())},
        "receipts": int(len(rc_seq)),
        "sync_records": sum(b.sync_count for b in bots),
        "npcs_mirrored": sum(
            sum(1 for m in b.entities.values() if m.type_name == "Npc")
            for b in bots),
        "settled_s_after_close": settled_s,
        "crossings": cross["crossings"], "rows_read": rows_read,
        "over_border": over_border,
        "stats": json.loads(log.stats) if log.stats else None,
        "mirror_errors_first": [e for b in bots for e in b.errors][:5],
        "t0": t0, "close": close,
    }
    with open(a.out, "w") as f:
        json.dump(out, f)
    print("DONE", flush=True)
    for t in tasks:
        t.cancel()
    for b in bots:
        b._stop = True
        if b._hb_task is not None:
            b._hb_task.cancel()
        await b.conn.close()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate-port", type=int, required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    return asyncio.run(main_async(ap.parse_args()))


if __name__ == "__main__":
    sys.exit(main())
