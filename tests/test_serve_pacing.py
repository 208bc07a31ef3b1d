"""The serve loop pumps while it paces (net/game.py ``serve_forever``):
through the frame's remainder the logic thread waits on its packet
queue, handles what arrives at once and puts the client events the
handlers staged on the wire, while the frames keep their cadence and
position syncs keep leaving once a tick.

Stub worlds throughout (the loop is host code: no device, no compile);
the last test serves a real tiny world behind a real gate. Every wait
has a deadline of its own."""
import asyncio
import threading
import time
import types

import numpy as np
import pytest

from goworld_tpu.net import proto
from goworld_tpu.net.packet import Packet
from goworld_tpu.utils import metrics, overload

GATE = 7
CLIENT = "c" * 16
INTERVAL = 0.3
RPC = proto.MT_CALL_ENTITY_METHOD_FROM_CLIENT


class _Loop:
    """A GameServer over a stub world whose ``tick`` and ``_send`` are
    the test's: ``frames`` holds the instant each tick began, ``sent``
    every packet handed to the wire as ``(instant, msgtype, records)``
    (records: the inner ``(msgtype, body)`` list of an event batch)."""

    def __init__(self, tick_interval=INTERVAL, tick_cost=0.0,
                 on_tick=None, **kw):
        from goworld_tpu.net.game import GameServer

        world = types.SimpleNamespace(
            _multihost=False, mh_rank=0, sync_stride=1,
            entities={}, spaces={}, op_stats={}, tick_count=0,
        )
        self.gs = gs = GameServer(
            97, world, [], gc_freeze_on_boot=False,
            tick_interval=tick_interval, **kw)
        self.frames: list[float] = []
        self.sent: list[tuple[float, int, list]] = []
        self.handled: list[tuple[float, int]] = []
        self.thread: threading.Thread | None = None
        gs.cluster.select_by_gate_id = lambda gate_id: gate_id

        def send(_conn, p):
            pkt = Packet(bytes(p.buf))
            mt = pkt.read_u16()
            recs = []
            if mt == proto.MT_CLIENT_EVENTS_BATCH:
                pkt.read_u16()
                for _ in range(pkt.read_u32()):
                    inner = pkt.read_u16()
                    recs.append((inner, pkt.read_bytes(pkt.read_u32())))
            self.sent.append((time.monotonic(), mt, recs))

        def tick():
            self.frames.append(time.monotonic())
            world.tick_count += 1
            if tick_cost:
                time.sleep(tick_cost)
            if on_tick is not None:
                on_tick(self)
            gs._flush_sync_out()

        def handle(_didx, msgtype, pkt):
            self.handled.append((time.monotonic(), msgtype))
            self.on_packet(msgtype, pkt)

        gs._send, gs.tick, gs._handle_packet = send, tick, handle

    def on_packet(self, msgtype, pkt):
        """An echo: the handler stages one client event (as
        ``Entity.call_client`` does through ``World.client_sink``)."""
        self.gs._client_sink(GATE, CLIENT, {
            "type": "rpc", "eid": "e" * 16, "method": "OnEcho",
            "args": (bytes(pkt.buf).decode(),)})

    def offer(self, token: str) -> float:
        """What the net thread does with a call off the wire."""
        t = time.monotonic()
        self.gs._on_packet_netthread(0, RPC, Packet(token.encode()))
        return t

    def start(self):
        self.thread = threading.Thread(
            target=self.gs.serve_forever, daemon=True)
        self.thread.start()
        return self

    def wait_frames(self, n: int, timeout: float = 20.0) -> None:
        deadline = time.monotonic() + timeout
        while len(self.frames) < n and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(self.frames) >= n, "the serve loop does not tick"

    def stop(self) -> float:
        """Stop the loop; returns the seconds it took to end."""
        t0 = time.monotonic()
        self.gs.stop()
        if self.thread is not None:
            self.thread.join(timeout=10)
            assert not self.thread.is_alive(), "the loop did not stop"
        return time.monotonic() - t0

    def echoes(self) -> list[tuple[float, str]]:
        """(instant sent, token) of every OnEcho on the wire, in wire
        order."""
        out = []
        for t, mt, recs in self.sent:
            for inner, body in recs:
                if inner == proto.MT_CALL_ENTITY_METHOD_ON_CLIENT:
                    p = Packet(body)
                    p.read_entity_id()
                    p.read_entity_id()
                    assert p.read_var_str() == "OnEcho"
                    out.append((t, p.read_args()[0]))
        return out


@pytest.fixture
def loop():
    made: list[_Loop] = []

    def make(**kw) -> _Loop:
        made.append(_Loop(**kw))
        return made[-1]

    yield make
    for lp in made:
        lp.stop()


def _pumped() -> dict:
    return {where: metrics.counter(
        "game_pump_packets_total", where=where).value
        for where in ("frame", "between", "device_wait")}


def _phase_count(phase: str) -> int:
    for labels, snap in metrics.REGISTRY.histogram_snapshot(
            "tick_phase_ms") or []:
        if labels.get("phase") == phase:
            return snap["count"]
    return 0


# (a) a call in mid-remainder is answered then, not at the next frame
def test_a_call_in_mid_remainder_is_answered_at_once(loop):
    lp = loop().start()
    lp.wait_frames(2)
    time.sleep(INTERVAL / 2 - (time.monotonic() - lp.frames[-1]))
    frames = len(lp.frames)
    t_offer = lp.offer("tok-1")
    lp.wait_frames(frames + 1)
    (t_sent, token), = lp.echoes()
    assert token == "tok-1"
    assert t_sent - t_offer < 0.05, t_sent - t_offer
    # handled and on the wire before the next tick's record opened
    assert lp.handled[0][0] < t_sent < lp.frames[frames]


# (b) packets streaming in move no frame
def test_frames_keep_their_cadence_under_a_stream_of_packets(loop):
    lp = loop(tick_cost=0.02).start()
    lp.wait_frames(1)
    stop = threading.Event()

    def stream():
        i = 0
        while not stop.is_set():
            lp.offer(f"s{i}")
            i += 1
            time.sleep(0.003)

    feeder = threading.Thread(target=stream, daemon=True)
    feeder.start()
    try:
        lp.wait_frames(10)
    finally:
        stop.set()
        feeder.join(timeout=5)
    gaps = np.diff(lp.frames[:10])
    # none skipped, none early, none late by more than a handler burst
    assert np.all(np.abs(gaps - INTERVAL) < 0.05), gaps
    assert abs((lp.frames[9] - lp.frames[0]) - 9 * INTERVAL) < 0.05
    assert len(lp.handled) > 100


# (c) order on the wire is the order of staging
def test_events_of_several_bursts_leave_in_staging_order(loop):
    lp = loop().start()
    lp.wait_frames(2)
    tokens = [f"t{i:02d}" for i in range(12)]
    for i, tok in enumerate(tokens):
        lp.offer(tok)
        if i % 3 == 2:
            time.sleep(0.04)            # several bursts, one frame
    lp.wait_frames(len(lp.frames) + 1)
    assert [tok for _t, tok in lp.echoes()] == tokens
    assert len({t for t, _tok in lp.echoes()}) >= 3      # not one batch


def test_a_create_staged_between_ticks_precedes_the_entitys_first_sync(
        loop):
    eid = "n" * 16

    def on_tick(lp):
        if lp.created:
            lp.gs._sync_sink(GATE, [CLIENT], [eid], np.zeros((1, 4)))

    lp = loop(on_tick=on_tick)
    lp.created = False

    def create(_msgtype, _pkt):
        lp.gs._client_sink(GATE, CLIENT, {
            "type": "create_entity", "eid": eid, "etype": "Mob",
            "is_player": False, "attrs": {}, "pos": (1.0, 0.0, 1.0),
            "yaw": 0.0})
        lp.created = True

    lp.on_packet = create
    lp.start()
    lp.wait_frames(2)
    lp.offer("spawn")
    lp.wait_frames(len(lp.frames) + 2)
    kinds = [mt if not recs else recs[0][0] for _t, mt, recs in lp.sent]
    create_at = kinds.index(proto.MT_CREATE_ENTITY_ON_CLIENT)
    sync_at = kinds.index(proto.MT_SYNC_POSITION_YAW_ON_CLIENTS)
    assert create_at < sync_at, kinds
    # and the create left between two ticks, not with the sync's flush
    t_create = lp.sent[create_at][0]
    assert not any(abs(t_create - f) < 1e-4 for f in lp.frames)
    assert lp.sent[sync_at][0] - t_create > 0.01


# (d) under the DEGRADED coalescing hold nothing leaves early
def test_the_degraded_hold_keeps_events_with_its_syncs(loop):
    def on_tick(lp):
        lp.gs._sync_sink(GATE, [CLIENT], ["m" * 16], np.zeros((1, 4)))

    lp = loop(on_tick=on_tick, degraded_event_coalesce=2,
              overload_enabled=True)
    gs = lp.gs
    # the ladder is held at DEGRADED whatever the loop observes
    gs._observe_overload = lambda dur, backlog: None
    gs.overload.state = overload.DEGRADED
    lp.start()
    # find a frame whose flush was held: records stay in _sync_out
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        n = len(lp.frames)
        time.sleep(0.05)
        if len(lp.frames) == n and n and gs._sync_out \
                and time.monotonic() - lp.frames[-1] < INTERVAL / 2:
            break
    assert gs._sync_out, "no held frame seen"
    held_frame = len(lp.frames)
    sent_before = len(lp.sent)
    t_offer = lp.offer("held")
    time.sleep(0.06)
    if len(lp.frames) == held_frame:      # still between the two ticks
        assert lp.handled and lp.handled[-1][0] - t_offer < 0.05
        assert len(lp.sent) == sent_before, "flushed under the hold"
        assert gs._events_out[GATE], "the event is not staged"
    lp.wait_frames(held_frame + 1)
    time.sleep(0.02)
    # the next tick's flush (the hold's even phase) carries both, the
    # event bundle first
    tail = lp.sent[sent_before:]
    assert [mt for _t, mt, _r in tail[:2]] == [
        proto.MT_CLIENT_EVENTS_BATCH,
        proto.MT_SYNC_POSITION_YAW_ON_CLIENTS], tail
    assert [tok for _t, tok in lp.echoes()] == ["held"]
    assert all(t >= lp.frames[held_frame] for t, _tok in lp.echoes())


# (e) stop() ends a waiting loop at once; a late frame takes no wait
def test_stop_ends_a_waiting_loop_at_once(loop):
    lp = loop(tick_interval=5.0).start()
    lp.wait_frames(1)
    time.sleep(0.05)                       # well inside the remainder
    assert lp.stop() < 1.0
    assert len(lp.frames) == 1


def test_a_frame_that_overran_takes_no_remainder(loop):
    """``delay <= 0``: the loop is the old one — no wait, no burst
    between ticks; the frame's own pump takes what arrived."""
    waits0 = _phase_count("pacing_sleep")
    before = _pumped()
    lp = loop(tick_interval=0.05, tick_cost=0.08)
    for i in range(3):
        lp.offer(f"q{i}")
    lp.start()
    lp.wait_frames(6)
    lp.offer("late")
    lp.wait_frames(len(lp.frames) + 2)
    lp.stop()
    after = _pumped()
    assert _phase_count("pacing_sleep") == waits0
    assert after["between"] == before["between"]
    assert after["frame"] - before["frame"] == 4
    gaps = np.diff(lp.frames)
    assert np.all(gaps >= 0.08) and np.all(gaps < 0.2), gaps


# (f) the counter says where each packet was handled
def test_pump_counter_counts_both_places(loop):
    before = _pumped()
    lp = loop()
    for i in range(3):
        lp.offer(f"early{i}")          # queued before the first frame
    lp.start()
    lp.wait_frames(2)
    for i in range(5):
        lp.offer(f"mid{i}")
        time.sleep(0.01)
    lp.wait_frames(len(lp.frames) + 1)
    lp.stop()
    after = _pumped()
    assert after["frame"] - before["frame"] == 3
    assert after["between"] - before["between"] == 5
    text = metrics.REGISTRY.expose_text()
    assert 'game_pump_packets_total{where="between"}' in text
    assert 'game_pump_packets_total{where="frame"}' in text


def test_a_burst_is_a_lone_drain_inputs_span_and_the_wait_is_idle(loop):
    """The accounting the benchmark reads: a burst between ticks is
    timed under ``drain_inputs`` in no tick's duration; the residency
    plane gets the time really waited and the bursts as host work."""
    rt = types.SimpleNamespace(idle=0.0, host=0.0)
    rt.add_idle = lambda s: setattr(rt, "idle", rt.idle + s)
    rt.add_host = lambda s: setattr(rt, "host", rt.host + s)
    lp = loop()
    lp.gs.world.residency = rt
    lp.on_packet = lambda mt, pkt: time.sleep(0.03)
    drains0 = _phase_count("drain_inputs")
    lp.start()
    lp.wait_frames(2)
    frames0 = lp.gs._m_tick_hist.snapshot()
    t0, idle0, host0 = time.monotonic(), rt.idle, rt.host
    lp.offer("slow")
    lp.wait_frames(len(lp.frames) + 2)
    frames1 = lp.gs._m_tick_hist.snapshot()
    wall = time.monotonic() - t0
    lp.stop()
    n = frames1["count"] - frames0["count"]
    # one lone span beside the frames' own
    assert _phase_count("drain_inputs") - drains0 >= 2 + n + 1
    assert (frames1["sum"] - frames0["sum"]) / n < 10.0   # ms a frame
    assert 0.03 <= rt.host - host0 < 0.06
    assert wall - 0.1 < rt.idle - idle0 < wall


# the same, with a device tick and a gate in the way
def test_a_live_game_answers_a_call_between_ticks():
    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.net.botclient import BotClient
    from goworld_tpu.net.game import GameServer
    from goworld_tpu.net.standalone import ClusterHarness
    from goworld_tpu.ops.aoi import GridSpec

    class PaceAccount(Entity):
        def Echo_Client(self, token):
            self.call_client("OnEcho", token)

    interval = 0.5
    harness = ClusterHarness(n_dispatchers=1, n_gates=1,
                             desired_games=1)
    harness.start()
    world = World(
        WorldConfig(capacity=64, grid=GridSpec(
            radius=10.0, extent_x=40.0, extent_z=40.0)),
        n_spaces=1,
    )
    world.register_entity("PaceAccount", PaceAccount)
    world.create_nil_space()
    world.tick()                          # compile before serving
    gs = GameServer(1, world, list(harness.dispatcher_addrs),
                    boot_entity="PaceAccount", gc_freeze_on_boot=False,
                    tick_interval=interval)
    gs.start_network()
    t = threading.Thread(target=gs.serve_forever, daemon=True)
    t.start()
    before = _pumped()

    async def calls():
        bot = BotClient(*harness.gate_addrs[0])
        await bot.connect()
        recv = asyncio.ensure_future(bot._recv_loop())
        try:
            await asyncio.wait_for(bot.player_ready.wait(), 60)
            took = []
            for i in range(8):
                # spread over the frame: 8 x 0.57 s against 0.5 s
                await asyncio.sleep(0.57)
                t0, n0 = time.monotonic(), len(bot.rpc_log)
                bot.call_server("Echo_Client", f"tok{i}")
                while len(bot.rpc_log) == n0 \
                        and time.monotonic() - t0 < 5:
                    await asyncio.sleep(0.002)
                took.append(time.monotonic() - t0)
                assert bot.rpc_log[-1][1:] == ("OnEcho", [f"tok{i}"])
            return took
        finally:
            recv.cancel()
            await bot.conn.close()

    try:
        took = harness.submit(calls()).result(timeout=120)
    finally:
        gs.stop()
        t.join(timeout=10)
        harness.stop()
    assert not t.is_alive()
    # at one pump a frame the median call waits a quarter of a second
    # and the slowest of eight nearly half; answered between ticks,
    # every call but one that met a tick is back within a few ms
    assert sorted(took)[len(took) // 2] < 0.1, took
    assert sorted(took)[-2] < 0.2, took
    after = _pumped()
    assert after["between"] - before["between"] >= 8


# ======================================================================
# the serve loop pumps while the device computes (``_serve_in_flight``)
# ======================================================================
# Real small worlds (the invariants are the World's), no network: the
# wire is ``_send`` captured, a packet is a job the test hands to the
# queue as the net thread would, and the device is held by a waiter the
# test releases.
from goworld_tpu.core.state import WorldConfig            # noqa: E402
from goworld_tpu.entity import manager as _manager        # noqa: E402
from goworld_tpu.entity.entity import Entity, GameClient  # noqa: E402
from goworld_tpu.entity.manager import World              # noqa: E402
from goworld_tpu.entity.space import Space                # noqa: E402
from goworld_tpu.net import codec                         # noqa: E402
from goworld_tpu.ops.aoi import GridSpec                  # noqa: E402

RADIUS = 10.0


class Mob(Entity):
    pass


class Room(Space):
    pass


class _Device:
    """The device, held: while ``held`` no tick in flight reads as
    landed and its waiter blocks until ``release()``."""

    def __init__(self, monkeypatch):
        self.held = False
        self.waiting = threading.Event()
        self._go = threading.Event()
        block, landed = (_manager._block_until_landed,
                         _manager._has_landed)

        def held_block(tree):
            if self.held:
                self.waiting.set()
                assert self._go.wait(60), "the test never released"
            block(tree)

        monkeypatch.setattr(_manager, "_block_until_landed", held_block)
        monkeypatch.setattr(
            _manager, "_has_landed",
            lambda tree: not self.held and landed(tree))

    def hold(self):
        self.held = True
        self.waiting.clear()
        self._go.clear()

    def release(self):
        self.held = False
        self._go.set()


class _Wire:
    """What every client was sent, in wire order, as ``(seq, kind,
    client, eid)`` (kinds: create, destroy, rpc, attrs, sync), with the
    instant each packet left; ``errors`` is a strict mirror's."""

    KINDS = {proto.MT_CREATE_ENTITY_ON_CLIENT: "create",
             proto.MT_DESTROY_ENTITY_ON_CLIENT: "destroy",
             proto.MT_CALL_ENTITY_METHOD_ON_CLIENT: "rpc",
             proto.MT_NOTIFY_ATTR_CHANGE_ON_CLIENT: "attrs"}

    def __init__(self):
        self.log: list[tuple[int, str, str, str]] = []
        self.packets: list[tuple[float, int]] = []

    def send(self, _conn, p):
        pkt = Packet(bytes(p.buf))
        mt = pkt.read_u16()
        seq = len(self.packets)
        self.packets.append((time.monotonic(), mt))
        if mt == proto.MT_CLIENT_EVENTS_BATCH:
            pkt.read_u16()
            for _ in range(pkt.read_u32()):
                inner = pkt.read_u16()
                body = Packet(pkt.read_bytes(pkt.read_u32()))
                self.log.append((seq, self.KINDS[inner],
                                 body.read_entity_id(),
                                 body.read_entity_id()))
        elif mt == proto.MT_SYNC_POSITION_YAW_ON_CLIENTS:
            pkt.read_u16()
            cids, eids, _v = codec.decode_client_sync_batch(
                memoryview(pkt.buf)[pkt.rpos:])
            for c, e in zip(cids, eids):
                self.log.append((seq, "sync", c.decode(), e.decode()))

    def of(self, client: str, eid: str | None = None):
        return [(seq, kind, e) for seq, kind, c, e in self.log
                if c == client and (eid is None or e == eid)]

    def errors(self) -> list[str]:
        held: dict[str, set] = {}
        out = []
        for _seq, kind, c, e in self.log:
            mirror = held.setdefault(c, set())
            if kind == "create":
                mirror.add(e)
            elif kind == "destroy":
                if e not in mirror:
                    out.append(f"{c}: destroy of unknown {e}")
                mirror.discard(e)
            elif kind in ("sync", "attrs") and e not in mirror:
                out.append(f"{c}: {kind} about unknown {e}")
        return out


class _Served:
    """A GameServer over a real World, ticked by the test: ``tick()``
    runs one ``GameServer.tick`` on a thread of its own (the logic
    thread of that frame) and, with the device held, returns once the
    loop waits for it; ``job(fn)`` queues ``fn`` as a packet."""

    def __init__(self, monkeypatch, n_spaces=1, capacity=64,
                 extent=100.0, npc_speed=0.0, **world_kw):
        from goworld_tpu.net.game import GameServer

        self.device = _Device(monkeypatch)
        self.world = w = World(WorldConfig(
            capacity=capacity,
            grid=GridSpec(radius=RADIUS, extent_x=extent, extent_z=extent,
                          k=16, cell_cap=32, row_block=capacity),
            npc_speed=npc_speed, turn_prob=0.2 if npc_speed else 0.0,
            enter_cap=1024, leave_cap=1024, sync_cap=1024,
            input_cap=capacity), n_spaces=n_spaces, **world_kw)
        w.register_entity("Mob", Mob)
        w.register_space("Room", Room)
        w.create_nil_space()
        self.gs = gs = GameServer(98, w, [], gc_freeze_on_boot=False,
                                  tick_interval=0.3)
        self.wire = _Wire()
        gs.cluster.select_by_gate_id = lambda gate_id: gate_id
        gs.cluster.select_by_entity_id = lambda eid: 0
        gs._send = self.wire.send
        self.jobs: dict[bytes, object] = {}
        self.handled: list[float] = []
        self.decodes: list[float] = []
        gs._handle_packet = self._handle
        decode = w._decode_outputs

        def stamped(outs):
            self.decodes.append(time.monotonic())
            decode(outs)

        w._decode_outputs = stamped
        self.thread: threading.Thread | None = None

    def _handle(self, _didx, _msgtype, pkt):
        self.jobs.pop(bytes(pkt.buf))()
        self.handled.append(time.monotonic())

    def client(self, e, cid: str) -> str:
        cid = cid.ljust(16, "_")
        self.world.set_entity_client(e, GameClient(GATE, cid, self.world))
        return cid

    def job(self, fn) -> None:
        token = f"job{len(self.jobs)}-{time.monotonic_ns()}".encode()
        self.jobs[token] = fn
        self.gs._on_packet_netthread(0, RPC, Packet(token))

    def job_done(self, fn, timeout=20.0) -> None:
        """Queue ``fn`` and wait until the loop has handled it."""
        n = len(self.handled)
        self.job(fn)
        deadline = time.monotonic() + timeout
        while len(self.handled) == n and time.monotonic() < deadline:
            time.sleep(0.002)
        assert len(self.handled) > n, "the loop did not handle the job"

    def tick(self) -> None:
        assert self.thread is None
        if not self.device.held:
            self.gs.tick()
            return
        self.thread = threading.Thread(target=self.gs.tick, daemon=True)
        self.thread.start()
        assert self.device.waiting.wait(60), "no tick waits for the device"

    def land(self) -> None:
        """Release the device and wait for the tick's end."""
        self.device.release()
        self.thread.join(timeout=60)
        assert not self.thread.is_alive(), "the tick did not end"
        self.thread = None

    def device_rows(self) -> int:
        return int(np.asarray(self.world.state.alive).sum())


@pytest.fixture
def served(monkeypatch):
    made: list[_Served] = []

    def make(**kw) -> _Served:
        made.append(_Served(monkeypatch, **kw))
        return made[-1]

    yield make
    for sv in made:
        sv.device.release()
        if sv.thread is not None:
            sv.thread.join(timeout=10)
        sv.gs._stop.set()


def _pair(sv, apart=5.0):
    """A room with a watcher and a mover ``apart`` from it, both with a
    client, and a still mob beside the watcher; settled."""
    w = sv.world
    room = w.create_space("Room")
    watcher = w.create_entity("Mob", space=room, pos=(50.0, 0.0, 50.0))
    mover = w.create_entity("Mob", space=room,
                            pos=(50.0 + apart, 0.0, 50.0))
    w.create_entity("Mob", space=room, pos=(48.0, 0.0, 50.0))
    sv.wc = sv.client(watcher, "watcher")
    sv.mc = sv.client(mover, "mover")
    for _ in range(3):
        sv.tick()
    return room, watcher, mover


def _echo(sv, e, token):
    return lambda: e.call_client("OnEcho", token)


# a call that arrives after the dispatch is answered before the decode
def test_a_call_in_the_device_wait_is_answered_before_the_decode(served):
    sv = served()
    _room, watcher, mover = _pair(sv)
    w = sv.world
    tick = w.tick_count
    anchor = w.sync_age_anchor
    assert anchor[0] == tick - 1
    mover.set_position((54.0, 0.0, 50.0))     # a sync record this tick
    decodes = len(sv.decodes)
    sv.device.hold()
    sv.tick()
    t_offer = time.monotonic()
    sv.job_done(_echo(sv, watcher, "tok"))
    # on the wire while the device is still held, ahead of any decode
    (seq, kind, _eid), = [r for r in sv.wire.of(sv.wc) if r[1] == "rpc"]
    assert sv.wire.packets[seq][0] - t_offer < 0.5
    assert len(sv.decodes) == decodes
    # what decode and fan-out read of THIS tick is still the last one's
    assert w.sync_age_anchor == anchor
    assert w.tick_count == tick
    sv.land()
    assert len(sv.decodes) == decodes + 1
    assert sv.wire.packets[seq][0] < sv.decodes[-1]
    # ... and ahead of the tick's sync batch
    syncs = [s for s, k, _e in sv.wire.of(sv.wc, mover.id) if k == "sync"]
    assert syncs and syncs[-1] > seq
    assert sv.wire.packets[syncs[-1]][1] == \
        proto.MT_SYNC_POSITION_YAW_ON_CLIENTS
    assert w.sync_age_anchor[0] == tick and w.tick_count == tick + 1
    assert not sv.wire.errors()


def test_under_a_flood_the_frame_ends_within_a_handler_of_the_landing(
        served):
    sv = served()
    _pair(sv)
    cost = 0.03
    stop = threading.Event()

    def flood():
        while not stop.is_set():
            sv.job(lambda: time.sleep(cost))
            time.sleep(0.002)

    sv.device.hold()
    sv.tick()
    feeder = threading.Thread(target=flood, daemon=True)
    feeder.start()
    try:
        time.sleep(0.3)
        handled = len(sv.handled)
        assert handled >= 5                # the loop does pump meanwhile
        t_land = time.monotonic()
        sv.land()
    finally:
        stop.set()
        feeder.join(timeout=5)
    # one handler may straddle the landing; the queue is not drained
    assert sv.decodes[-1] - t_land < 2 * cost + 0.05
    assert len(sv.handled) - handled <= 2
    assert sv.gs._packet_q.qsize() > 0
    assert sv.gs._wake.is_set()      # the remainder's loop takes over


# what a handler does to an entity while its row is in flight
@pytest.mark.parametrize("known", [True, False],
                         ids=["known", "entering"])
@pytest.mark.parametrize("what", ["destroy", "enter_space"])
def test_a_row_left_during_the_wait_is_skipped_by_the_decode(
        served, what, known):
    """``known``: the watcher's client mirrors the mover already, so it
    hears of its leaving once, a tick later, and of nothing after.
    ``entering``: the mover comes into range in the very tick in flight
    and is gone before the decode: the watcher's client never hears of
    it (no create, so no destroy of an unknown entity either)."""
    sv = served(n_spaces=2)
    w = sv.world
    _room, watcher, mover = _pair(sv, apart=5.0 if known else 30.0)
    other = w.create_space("Room")
    assert (mover.id in watcher.interested_in) == known
    mover.set_position((53.0, 0.0, 50.0))    # a sync, or the entering
    shard, slot = mover.shard, mover.slot
    rows = sv.device_rows()
    releases: list[list] = []
    decode = w._decode_outputs
    w._decode_outputs = lambda outs: (
        releases.append(list(w._release_now)), decode(outs))
    spawned = []

    def leave():
        if what == "destroy":
            w.destroy_entity(mover)
        else:
            w.enter_space(mover, other.id, (50.0, 0.0, 50.0))
        spawned.append(w.create_entity(
            "Mob", space=watcher.space, pos=(90.0, 0.0, 90.0)))

    sv.device.hold()
    sv.tick()                                # tick N is in flight
    sv.job_done(leave)
    cut = len(sv.wire.log)
    sv.land()                                # decode N meets the row
    # the row is alive on the device and nobody's: not released in N,
    # not handed to the spawn the same handler made
    assert (shard, slot) not in [(sh, sl) for sh, sl, _ in releases[-1]]
    assert slot not in w._free[shard]
    assert (spawned[0].shard, spawned[0].slot) != (shard, slot)
    assert w._slot_owner[shard][slot] == mover.id
    in_n = [r for r in sv.wire.log[cut:] if r[3] == mover.id]
    assert [r for r in in_n if r[2] == sv.wc] == [], in_n
    assert mover.id not in watcher.interested_in or known
    sv.tick()                                # N+1: the despawn, the leaves
    assert slot in w._free[shard] or \
        w._slot_owner[shard].get(slot) != mover.id
    sv.tick()
    told = sv.wire.of(sv.wc, mover.id)
    if known:
        # one leaving, and it is the last word about the mover
        assert [k for _s, k, _e in told].count("destroy") == 1
        assert told[-1][1] == "destroy"
    else:
        assert told == []
    assert mover.id not in watcher.interested_in
    assert watcher.id not in mover.interested_by
    assert not sv.wire.errors(), sv.wire.errors()
    if what == "destroy":
        assert mover.id not in w.entities
        assert sv.device_rows() == rows - 1 + 1
    else:
        assert mover.space is other and mover.slot is not None
        assert mover.shard == other.shard
        assert sv.device_rows() == rows + 1
        assert bool(np.asarray(w.state.alive)[mover.shard, mover.slot])
    assert sv.device_rows() == sum(len(o) for o in w._slot_owner)


def test_a_position_read_during_the_wait_is_the_ticks_and_stays_cached(
        served):
    sv = served()
    _room, watcher, mover = _pair(sv)
    w = sv.world
    mover.set_position((57.0, 0.0, 51.0))
    seen = []
    sv.device.hold()
    sv.tick()
    assert w._pos_cache is None               # dropped at the flush
    sv.job_done(lambda: seen.append(tuple(mover.position)))
    assert seen == [(57.0, 0.0, 51.0)]        # the tick in flight's row
    cache = w._pos_cache
    assert cache is not None
    sv.land()
    assert w._pos_cache is cache              # the decode read the same
    sv.device.hold()
    sv.tick()
    assert w._pos_cache is None
    sv.land()


@pytest.mark.parametrize("how", ["stop", "freeze"])
def test_stop_and_freeze_end_a_loop_that_waits_for_the_device(
        served, how):
    sv = served()
    _pair(sv)
    gs = sv.gs
    frozen = []
    gs._do_freeze = lambda: (frozen.append(sv.world.tick_count),
                             gs._stop.set())
    sv.device.hold()
    ticks = sv.world.tick_count
    loop = threading.Thread(target=gs.serve_forever, daemon=True)
    loop.start()
    assert sv.device.waiting.wait(60)
    time.sleep(0.05)                 # the loop waits on its queue now
    t0 = time.monotonic()
    if how == "stop":
        gs._stop.set()
        gs._wake.set()               # what stop() does, less the network
    else:
        # the last dispatcher's ack, handled while the device computes
        sv.job(lambda: setattr(gs, "run_state", "freezing"))
    loop.join(timeout=10)
    assert not loop.is_alive(), "the loop still waits for the device"
    assert time.monotonic() - t0 < 2.0
    # the device was never released: the tick in flight was fetched as
    # a standalone World fetches it, and ended
    assert sv.device.held and sv.world.tick_count == ticks + 1
    assert frozen == ([] if how == "stop" else [ticks + 1])


def test_the_pump_counter_counts_three_places(served):
    sv = served()
    _room, watcher, _mover = _pair(sv)
    gs = sv.gs
    before = _pumped()
    for i in range(3):
        sv.job(_echo(sv, watcher, f"early{i}"))   # before the frame
    sv.device.hold()
    loop = threading.Thread(target=gs.serve_forever, daemon=True)
    loop.start()
    try:
        assert sv.device.waiting.wait(60)
        for i in range(2):
            sv.job_done(_echo(sv, watcher, f"wait{i}"))
        sv.device.release()
        deadline = time.monotonic() + 20
        while not sv.decodes and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.02)
        for i in range(4):
            sv.job_done(_echo(sv, watcher, f"mid{i}"))
    finally:
        gs._stop.set()
        gs._wake.set()
        loop.join(timeout=10)
    after = _pumped()
    assert {k: after[k] - before[k] for k in after} == {
        "frame": 3, "device_wait": 2, "between": 4}
    assert 'game_pump_packets_total{where="device_wait"}' in \
        metrics.REGISTRY.expose_text()
    assert not sv.wire.errors()


def test_the_wait_is_fetch_spans_and_each_burst_a_drain_span(served):
    sv = served()
    _room, watcher, _mover = _pair(sv)
    waits0, drains0 = (_phase_count("fetch_outputs"),
                       _phase_count("drain_inputs"))
    sv.device.hold()
    sv.tick()
    for i in range(2):                        # two bursts
        sv.job_done(_echo(sv, watcher, f"b{i}"))
        time.sleep(0.02)
    sv.land()
    _wall, dur, spans, _args = metrics.timeline.records()[-1]
    names = [name for name, _t, _d, _a in spans]
    assert names[:3] == ["flush_staging", "device_step", "fetch_outputs"]
    wait = names[2:names.index("decode_fanout")]
    # a wait before and after every burst; the fetch itself (a copy by
    # then) follows the wait the landing ended
    assert wait.count("drain_inputs") == 2, names
    assert set(wait) == {"fetch_outputs", "drain_inputs"}
    assert wait[-2:] == ["fetch_outputs", "fetch_outputs"], names
    assert all((a, b) != ("drain_inputs", "drain_inputs")
               for a, b in zip(wait, wait[1:])), names
    # sequential, never nested, and inside the tick's duration
    end = 0.0
    for _name, start, sdur, _a in spans:
        assert start >= end - 1e-6, names
        end = start + sdur
    assert end <= dur + 1e-6
    # each is observed under its name: the waits sum to the time the
    # thread truly waited, the bursts to pump work
    assert _phase_count("fetch_outputs") - waits0 == \
        wait.count("fetch_outputs")
    assert _phase_count("drain_inputs") - drains0 == 2


class _NoWaiter:
    """Fails the test where a tick starts a waiter."""

    def __init__(self, monkeypatch):
        def watch(_world, _flight, _wake):
            raise AssertionError("a blocking tick asked for a waiter")

        monkeypatch.setattr(World, "watch_landing", watch)


@pytest.mark.parametrize("kind", ["standalone", "multihost", "standby"])
def test_a_world_without_a_queue_takes_the_blocking_fetch(
        monkeypatch, kind):
    """A standalone ``World.tick()`` has no queue; a multihost world's
    fetch is a collective every controller reaches at the same point; a
    standby that has not been promoted runs no device tick at all."""
    from goworld_tpu.net.game import GameServer

    ticks = []
    if kind == "standalone":
        sv = _Served(monkeypatch)
        _pair(sv)
        _NoWaiter(monkeypatch)
        n = sv.world.tick_count
        sv.world.tick()
        assert sv.world.tick_count == n + 1 and sv.decodes
        return
    world = types.SimpleNamespace(
        _multihost=kind == "multihost", mh_rank=0, sync_stride=1,
        entities={}, spaces={}, op_stats={}, tick_count=0,
        tick=lambda: ticks.append("tick"))
    gs = GameServer(96, world, [], gc_freeze_on_boot=False)
    _NoWaiter(monkeypatch)
    gs._send = lambda _conn, _p: None
    if kind == "multihost":
        gs._mh_exchange_mutations = lambda: ticks.append("exchange")
        gs._on_packet_netthread(0, RPC, Packet(b"queued"))
        gs.tick()
        assert ticks == ["exchange", "tick"]
        assert gs._packet_q.qsize() == 1      # nothing pumped inside
    else:
        gs._standby_applier = object()
        gs._standby_tick = lambda: ticks.append("standby")
        gs.tick()
        assert ticks == ["standby"]


# ----------------------------------------------------------------------
# the worlds whose decode re-points rows: what it reads that a handler
# may have written meanwhile
# ----------------------------------------------------------------------
def test_a_migration_in_flight_is_not_rewritten_by_a_handler(served):
    """A mesh world: ``_migrate_tags`` is what the flush of the tick in
    flight wrote and its decode reads. A second ``EnterSpace`` handled
    meanwhile is STAGED (the next flush's); a destroy of the entity in
    flight leaves the source row alone (it has departed in-step) and
    the arrivals' reconciliation drops the row that arrived."""
    from goworld_tpu.parallel.mesh import make_mesh

    sv = served(n_spaces=8, mesh=make_mesh(8), migrate_cap=4)
    w = sv.world
    rooms = [w.create_space("Room") for _ in range(8)]
    a, b, c = (w.create_entity("Mob", space=rooms[0],
                               pos=(50.0 + 2 * i, 0.0, 50.0))
               for i in range(3))
    sv.client(b, "b")
    for _ in range(2):
        sv.tick()
    assert b.interested_in == {a.id, c.id}
    a_row = (a.shard, a.slot)
    w.enter_space(a, rooms[5].id, (20.0, 0.0, 20.0))
    seen = {}

    def meanwhile():
        seen["tags"] = dict(w._migrate_tags)
        w.enter_space(c, rooms[3].id, (30.0, 0.0, 30.0))
        w.destroy_entity(a)
        seen["tags_after"] = dict(w._migrate_tags)
        seen["staged"] = [m[3] for m in w._staged_migrate]
        seen["despawn"] = list(w._staged_despawn)

    sv.device.hold()
    sv.tick()
    sv.job_done(meanwhile)
    sv.land()
    assert seen["tags"] == {0: (a.id,) + a_row} == seen["tags_after"]
    assert seen["staged"] == [c.id] and a_row not in seen["despawn"]
    assert w._migrate_tags == {}
    # a's row did arrive on shard 5 and is dropped; c's move is staged
    assert [m[3] for m in w._staged_migrate] == [c.id]
    assert [sh for sh, _sl in w._staged_despawn] == [5]
    sv.tick()
    assert c.space is rooms[3] and c.shard == rooms[3].shard
    assert bool(np.asarray(w.state.alive)[c.shard, c.slot])
    for _ in range(2):
        sv.tick()
    assert a.id not in w.entities and b.interested_in == set()
    assert sv.device_rows() == 2 == sum(len(o) for o in w._slot_owner)
    assert not sv.wire.errors(), sv.wire.errors()


def test_an_arrival_takes_the_row_and_a_staged_spawn_moves(served):
    """The device hands a free row to an arrival in the tick in flight;
    a spawn a handler staged into the same row meanwhile (nothing on
    the device names it yet) moves to another one with everything
    staged for it. With no row left it is parked in the nil space."""
    sv = served(capacity=8)
    w = sv.world
    room = w.create_space("Room")
    z = w.create_entity("Mob", space=room, pos=(5.0, 0.0, 5.0),
                        moving=True)
    sv.client(z, "z")
    shard, slot = z.shard, z.slot
    z.set_position((6.0, 0.0, 6.0))
    w.stage_pos_sync_batch([z.id], np.array([[7.0, 0.0, 7.0, 1.0]]))
    w._claim_arrival_row(shard, slot, "arrival".ljust(16, "_"))
    assert z.slot != slot and z.shard == shard
    new = (shard, z.slot)
    assert w._slot_owner[shard][slot] == "arrival".ljust(16, "_")
    assert w._slot_owner[shard][z.slot] == z.id
    assert [(sh, sl) for sh, sl, _d in w._staged_spawn] == [new]
    assert list(w._staged_pos) == [new]
    assert {x[:2] for x in w._staged_client} == {new}
    assert bool(w._batch_pos_mask[new]) and \
        not w._batch_pos_mask[shard, slot]
    assert tuple(w._batch_pos_vals[new]) == (7.0, 0.0, 7.0, 1.0)
    assert slot not in w._free[shard] and z.slot not in w._free[shard]
    # the same with the shard full
    w._free[shard].clear()
    w._claim_arrival_row(shard, z.slot, "second".ljust(16, "_"))
    assert z.space is w.nil_space and z.slot is None
    assert w._staged_spawn == [] and w._staged_pos == {}
    assert not w._batch_pos_mask.any()


class _Tiles:
    """A 2x2 megaspace on 4 host devices, avatars at its x border."""
    TILE = 60.0
    NEAR, FAR = 57.0, 63.0        # either side of x = 60

    def __init__(self, served):
        from goworld_tpu.parallel.mesh import make_mesh

        self.sv = sv = served(
            n_spaces=4, capacity=96, extent=self.TILE + 2 * RADIUS,
            npc_speed=20.0, mesh=make_mesh(4), megaspace=True,
            halo_cap=64, migrate_cap=32, mega_shape=(2, 2))
        self.w = w = sv.world
        w.register_space("Plain", Room, megaspace=True)
        self.plain = w.create_space("Plain")
        rng = np.random.default_rng(3)
        self.walkers = [
            w.create_entity("Mob", space=self.plain, moving=True, pos=(
                float(rng.uniform(30, 90)), 0.0,
                float(rng.uniform(0, 120))))
            for _ in range(48)]
        self.avatars = []
        for i in range(8):
            e = w.create_entity("Mob", space=self.plain,
                                pos=(self.NEAR, 0.0, 8.0 + 14.0 * i))
            e.cid = sv.client(e, f"av{i}")
            e.z, e.side = 8.0 + 14.0 * i, self.NEAR
            self.avatars.append(e)

    def hop(self, e) -> tuple:
        """The avatar's next place: the other side of the border."""
        e.side = self.FAR if e.side == self.NEAR else self.NEAR
        return (e.side, 0.0, e.z)

    def check(self):
        """What the benchmark's read-back holds a tiled world to:
        nothing lost or doubled, every row where its entity is, every
        avatar where it was last sent, interest exact over the tiles."""
        w = self.w
        alive = np.asarray(w.state.alive)
        pos = np.asarray(w.state.pos)
        live = [e for e in w.entities.values()
                if e.space is self.plain and not e.destroyed]
        assert int(alive.sum()) == len(live) == \
            sum(len(o) for o in w._slot_owner)
        at = {}
        for e in live:
            assert alive[e.shard, e.slot]
            assert w._slot_owner[e.shard][e.slot] == e.id
            x, z = (float(v) for v in pos[e.shard, e.slot][[0, 2]])
            assert e.shard == w._tile_of((x, 0.0, z)), (e.id, x, z)
            at[e.id] = (x, z)
        for e in self.avatars:
            if not e.destroyed:
                assert at[e.id] == (e.side, e.z), e.cid
        for e in live:
            want = {o.id for o in live if o is not e and max(
                abs(at[o.id][0] - at[e.id][0]),
                abs(at[o.id][1] - at[e.id][1])) <= RADIUS}
            assert e.interested_in == want, e.id


def test_a_served_megaspace_with_crossings_calls_and_a_destroy_in_flight(
        served):
    """The 2x2 rehearsal (4 host devices): in every tick the avatars
    cross the tile border by the position staged for them, and while
    that tick is in flight the loop handles their next position (by the
    client's batch and by ``set_position``: both staged for a row the
    decode then re-points), a call each, once a destroy of an avatar
    whose row is hopping and a spawn into the tile the arrivals land
    on. Nothing lost, arrivals reconcile, interest exact."""
    t = _Tiles(served)
    sv, w = t.sv, t.w
    for _ in range(2):
        sv.tick()
    moved = []
    move = w._move_staged_spawn
    w._move_staged_spawn = lambda i, eid: (moved.append(eid),
                                           move(i, eid))
    hops0 = {e.id: e.shard for e in t.avatars}
    crossed = set()
    echoes = 0

    def next_places(tick):
        for k, e in enumerate(t.avatars):
            if e.destroyed:
                continue
            if k % 2:
                e.set_position(t.hop(e))
            else:
                x, y, z = t.hop(e)
                w.stage_pos_sync_batch(
                    [e.id], np.array([[x, y, z, 0.0]], np.float32))
            e.call_client("OnEcho", f"{tick}-{k}")

    next_places(0)                  # staged ahead of the first flush
    echoes += 8
    for tick in range(1, 9):
        sv.device.hold()
        sv.tick()                   # the staged hop is in flight
        flying = {e.id: e.side for e in t.avatars if not e.destroyed}
        sv.job_done(lambda: next_places(tick))
        echoes += sum(not e.destroyed for e in t.avatars)
        if tick == 3:
            # its row arrives on the other tile in THIS tick
            sv.job_done(lambda: w.destroy_entity(t.avatars[2]))
        if tick in (2, 4, 5):
            # into the tile the avatars' rows arrive on in THIS tick
            side = t.avatars[0].side      # the place staged for N+1 ...
            x = 75.0 if side == t.NEAR else 45.0       # ... so N's tile
            sv.job_done(lambda: t.walkers.append(w.create_entity(
                "Mob", space=t.plain, pos=(x, 0.0, 100.0))))
        sv.land()
        crossed |= {e.id for e in t.avatars
                    if not e.destroyed and e.shard != hops0[e.id]}
        # every hop lands in its tick: none was written to a row its
        # entity had left
        pos = np.asarray(w.state.pos)
        for e in t.avatars:
            if not e.destroyed:
                assert float(pos[e.shard, e.slot][0]) == flying[e.id], \
                    (tick, e.cid)
        assert int(np.asarray(
            w.last_outputs.migrate_dropped).sum()) == 0
    assert len(crossed) == 8        # the one destroyed at tick 3 too
    for _ in range(3):              # the last hop lands, leaves decode
        sv.tick()
    t.check()
    assert t.avatars[2].id not in w.entities
    assert moved, "no arrival ever met a staged spawn: the mix is off"
    rpcs = [r for r in sv.wire.log if r[1] == "rpc"]
    assert len(rpcs) == echoes
    assert not sv.wire.errors(), sv.wire.errors()[:5]
