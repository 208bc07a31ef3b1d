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
        for where in ("frame", "between")}


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
