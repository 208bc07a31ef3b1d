"""End-to-end sync-age plane (ISSUE 15): the 45-byte per-batch stamp
trailer (wire format + byte-identical-off contract), the World's
fetch-anchored epoch capture, gate age-at-delivery histograms with
exact per-hop lane sums, the ``sync_age_breach`` flight-recorder
trigger, the ``/syncage`` endpoint and the deployment aggregator —
capped by a live standalone gate -> dispatcher -> game harness over
real sockets (test_tracing style) asserting nonzero monotone ages on
both sync legs (full-record 1503 and delta 1505)."""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from goworld_tpu.net import codec, proto
from goworld_tpu.net.packet import (
    AGE_FLAG,
    MSGTYPE_MASK,
    TRACE_FLAG,
    Packet,
    decode_wire,
    new_packet,
    wire_payload,
)
from goworld_tpu.utils import debug_http, flightrec, metrics, syncage

pytestmark = pytest.mark.syncage


@pytest.fixture(autouse=True)
def _fresh_registries():
    """Metric families are process-global; sync-age series must start
    empty per test or cross-test counts leak into lane-sum asserts."""
    metrics.REGISTRY.reset()
    syncage.reset()
    yield
    metrics.REGISTRY.reset()
    syncage.reset()


# =======================================================================
# stamp + lanes
# =======================================================================
def test_stamp_pack_unpack_roundtrip():
    s = syncage.SyncAgeStamp(7, 1000, 2000, 3000, 4000, 4500)
    b = s.pack()
    assert len(b) == syncage.STAMP_WIRE_SIZE == 45
    back = syncage.SyncAgeStamp.unpack(b)
    assert (back.seq, back.t_tick_us, back.t_fetch_us,
            back.t_stage_us, back.t_send_us, back.t_disp_us) == \
        (7, 1000, 2000, 3000, 4000, 4500)
    with pytest.raises(ValueError):
        syncage.SyncAgeStamp.unpack(b[:-1])
    with pytest.raises(ValueError):
        syncage.SyncAgeStamp.unpack(b"\x07" + b[1:])  # bad version


def test_lanes_exact_sum_and_zero_disp_fold():
    s = syncage.SyncAgeStamp(1, 1000, 2000, 3000, 4000, 0)
    lanes, warped = s.lanes_us(10000)
    assert warped == 0
    assert lanes == {"device_tick": 1000, "drain_decode": 1000,
                     "encode": 1000, "dispatcher": 0,
                     "gate_flush": 6000}
    assert sum(lanes.values()) == 10000 - 1000
    # with a dispatcher instant the wire leg splits
    s.t_disp_us = 7000
    lanes, _ = s.lanes_us(10000)
    assert lanes["dispatcher"] == 3000 and lanes["gate_flush"] == 3000
    assert sum(lanes.values()) == 9000


def test_lanes_clock_warp_clamps_and_counts():
    # fetch/stage behind tick, deliver behind send: every negative
    # boundary clamps (never a negative histogram sample) and is
    # counted; the lane sum still covers max(boundary) - t_tick
    s = syncage.SyncAgeStamp(1, 5000, 4000, 4500, 6000, 5500)
    lanes, warped = s.lanes_us(5400)
    assert warped == 4
    assert all(v >= 0 for v in lanes.values())
    assert sum(lanes.values()) == 1000  # 6000 (send) - 5000 (tick)


def test_histogram_observe_n_weighting():
    h = metrics.Histogram(buckets=(1.0, 10.0))
    h.observe_n(5.0, 100)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["buckets"][1][1] == 100
    assert snap["sum"] == pytest.approx(500.0)
    h.observe_n(5.0, 0)  # no-op
    assert h.count == 100


# =======================================================================
# wire format: AGE_FLAG trailer, byte-identical when absent
# =======================================================================
def _sync_packet() -> Packet:
    p = new_packet(proto.MT_SYNC_POSITION_YAW_ON_CLIENTS)
    p.append_u16(1)
    p.append_bytes(b"x" * 96)
    return p


def test_age_flag_constants():
    assert AGE_FLAG == 0x4000
    assert AGE_FLAG & TRACE_FLAG == 0
    # every routing range stays clear of bit 14 (the proto invariant
    # suite holds the ranges themselves)
    assert proto.MT_GATE_SERVICE_MSG_TYPE_STOP < AGE_FLAG


def test_stamped_wire_roundtrip_and_strip():
    p = _sync_packet()
    legacy = wire_payload(p)
    p.age = syncage.SyncAgeStamp(9, 10, 20, 30, 40, 0)
    stamped = wire_payload(p)
    assert len(stamped) == len(legacy) + syncage.STAMP_WIRE_SIZE
    assert int.from_bytes(stamped[:2], "little") & AGE_FLAG
    mt, back = decode_wire(stamped)
    assert mt == proto.MT_SYNC_POSITION_YAW_ON_CLIENTS
    assert back.age is not None and back.age.seq == 9
    # handlers see payload bytes identical to an unstamped packet's
    assert bytes(back.buf) == legacy
    # re-serializing the decoded packet keeps the stamp (the
    # dispatcher's forward path: decode -> patch -> send)
    back.age.t_disp_us = 50
    rewire = wire_payload(back)
    _, back2 = decode_wire(rewire)
    assert back2.age.t_disp_us == 50


def test_stamp_and_trace_trailers_coexist():
    from goworld_tpu.utils import tracing

    p = _sync_packet()
    legacy = wire_payload(p)
    p.age = syncage.SyncAgeStamp(9, 10, 20, 30, 40, 0)
    p.trace = tracing.new_trace()
    mt, back = decode_wire(wire_payload(p))
    assert mt == proto.MT_SYNC_POSITION_YAW_ON_CLIENTS
    assert back.age is not None and back.trace is not None
    assert bytes(back.buf) == legacy


def test_unstamped_wire_byte_identical():
    """The always-on-able contract: with no stamp attached the framed
    bytes are EXACTLY the pre-plane wire."""
    p = _sync_packet()
    assert wire_payload(p) == bytes(p.buf)
    assert not int.from_bytes(wire_payload(p)[:2], "little") & AGE_FLAG


def test_truncated_stamp_trailer_is_connection_error():
    raw = bytearray(_sync_packet().buf[:4])
    raw[1] |= 0x40  # AGE_FLAG set but no room for a 45 B trailer
    with pytest.raises(ConnectionError):
        decode_wire(bytes(raw))


def test_packet_release_clears_stamp():
    p = _sync_packet()
    p.age = syncage.SyncAgeStamp(1, 1, 2)
    p.release()
    assert p.age is None


# =======================================================================
# tracker
# =======================================================================
def test_tracker_record_weighted_lanes_and_snapshot():
    t = syncage.AgeTracker(target_ms=16.0)
    s = syncage.SyncAgeStamp(3, 0, 1000, 2000, 3000, 4000)
    t.observe(s, 8000, 500)
    snap = t.snapshot()
    assert snap["e2e"]["samples"] == 500
    for hop in syncage.HOPS:
        assert snap["hops"][hop]["samples"] == 500
    assert snap["pass"] is True
    assert t.last_seq == 3
    assert sum(t.last_lanes_ms.values()) == pytest.approx(
        t.last_e2e_ms)
    # /syncage raw vectors merge exactly into a fresh histogram
    h = metrics.Histogram(buckets=snap["edges_ms"])
    h.add_counts(snap["e2e_counts"])
    assert h.count == 500


def test_tracker_window_verdict_deltas():
    t = syncage.AgeTracker()
    s = syncage.SyncAgeStamp(1, 0, 0, 0, 0, 0)
    t.observe(s, 5000, 10)
    p99, n = t.window_verdict()   # first call: establishes the mark
    assert (p99, n) == (None, 0)
    t.observe(s, 50000, 20)       # 50 ms ages
    p99, n = t.window_verdict()
    assert n == 20 and p99 is not None and p99 > 16.0
    p99, n = t.window_verdict()   # empty window
    assert (p99, n) == (None, 0)


def test_syncage_registry_weakref():
    t = syncage.AgeTracker()
    syncage.register("gate9", t)
    assert "gate9" in syncage.snapshot_all()
    del t
    import gc

    gc.collect()
    assert "error" in syncage.snapshot_all()


# =======================================================================
# flight-recorder trigger
# =======================================================================
def test_sync_age_breach_trigger_fires_and_cools_down():
    clock = [0.0]
    rec = flightrec.FlightRecorder(ring=16, cooldown_secs=30.0,
                                   clock=lambda: clock[0])
    frame = {"tick": 1, "sync_age_p99_ms": 40.0,
             "sync_age_target_ms": 16.0,
             "sync_age_hops": {"device_tick": 30.0,
                               "gate_flush": 10.0}}
    out = rec.record(dict(frame))
    assert len(out) == 1 and out[0]["trigger"] == "sync_age_breach"
    assert "40" in out[0]["detail"]
    # the per-hop breakdown rides the frozen frames
    assert out[0]["frames"][-1]["sync_age_hops"]["device_tick"] == 30.0
    # cooldown dedups the second breach
    clock[0] = 5.0
    assert rec.record(dict(frame, tick=2)) == []
    clock[0] = 35.0
    out = rec.record(dict(frame, tick=3))
    assert len(out) == 1
    # under target: no trigger
    ok = {"tick": 4, "sync_age_p99_ms": 3.0,
          "sync_age_target_ms": 16.0}
    clock[0] = 99.0
    assert rec.record(ok) == []


# =======================================================================
# encoder byte-kind split (satellite)
# =======================================================================
def test_delta_encoder_splits_keyframe_vs_delta_bytes():
    enc = codec.DeltaSyncEncoder(step=0.25, keyframe_every=100)
    cids = np.asarray([b"C%015d" % 1], "S16")
    eids = np.asarray([b"E%015d" % 1], "S16")
    v0 = np.asarray([[1.0, 2.0, 3.0, 0.5]], np.float32)
    enc.encode_batch(cids, eids, v0, tick=0)
    assert enc.stats["keyframe_bytes"] == 53
    assert enc.stats["delta_bytes"] == 0
    enc.encode_batch(cids, eids, v0 + 0.25, tick=1)
    assert enc.stats["delta_bytes"] == 13
    assert enc.stats["keyframe_bytes"] == 53
    # the per-kind split never exceeds the wire total (headers make up
    # the difference)
    assert (enc.stats["keyframe_bytes"] + enc.stats["delta_bytes"]
            <= enc.stats["wire_bytes"])


# =======================================================================
# game-side flush stamping (unit, no sockets)
# =======================================================================
def _tiny_world():
    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.manager import World
    from goworld_tpu.ops.aoi import GridSpec

    cfg = WorldConfig(
        capacity=32,
        grid=GridSpec(radius=50.0, extent_x=200.0, extent_z=200.0),
        input_cap=32,
    )
    return World(cfg, n_spaces=1)


class _CaptureConn:
    def __init__(self):
        self.wires: list[bytes] = []

    def send(self, p) -> None:
        self.wires.append(wire_payload(p))
        p.release()


def _flush_capture(gs, cids, eids, vals):
    conn = _CaptureConn()
    gs.cluster.select_by_gate_id = lambda gid: conn
    gs._sync_sink(1, cids, eids, vals)
    gs._flush_sync_out()
    return conn.wires


@pytest.fixture(scope="module")
def tiny_world_ticked():
    """One ticked world shared by the flush-stamping units (the tick
    compiles the device step once; sync_age_anchor is then set)."""
    w = _tiny_world()
    w.tick()
    w.tick()
    return w


def test_world_tick_sets_age_anchor(tiny_world_ticked):
    w = tiny_world_ticked
    anchor = w.sync_age_anchor
    assert anchor is not None
    seq, t_tick, t_fetch = anchor
    assert t_fetch >= t_tick > 0
    # wall-anchored: within a day of now (catches unit mixups)
    assert abs(t_fetch / 1e6 - time.time()) < 86400


def test_flush_stamps_when_enabled_and_legacy_when_off(
        tiny_world_ticked):
    from goworld_tpu.net.game import GameServer

    w = tiny_world_ticked
    cids = np.asarray([b"C%015d" % i for i in range(4)], "S16")
    eids = np.asarray([b"E%015d" % i for i in range(4)], "S16")
    vals = np.ones((4, 4), np.float32)

    gs_on = GameServer(97, w, [], gc_freeze_on_boot=False)
    wires_on = _flush_capture(gs_on, cids, eids, vals)
    assert len(wires_on) == 1
    mt, p = decode_wire(wires_on[0])
    assert mt == proto.MT_SYNC_POSITION_YAW_ON_CLIENTS
    assert p.age is not None
    anchor = w.sync_age_anchor
    assert p.age.seq == anchor[0]
    assert p.age.t_tick_us == anchor[1]
    assert p.age.t_fetch_us == anchor[2]
    # the flush instants are monotone after the fetch anchor
    assert p.age.t_send_us >= p.age.t_stage_us >= p.age.t_fetch_us
    assert p.age.t_disp_us == 0  # dispatcher hop not taken yet
    # the full-record byte counter saw the payload
    assert metrics.counter("sync_bytes_out",
                           kind="full").value == 4 * 48

    gs_off = GameServer(98, w, [], gc_freeze_on_boot=False,
                        sync_age=False)
    wires_off = _flush_capture(gs_off, cids, eids, vals)
    assert len(wires_off) == 1
    # THE acceptance contract: stamp off => byte-identical legacy wire
    expected = new_packet(proto.MT_SYNC_POSITION_YAW_ON_CLIENTS)
    expected.append_u16(1)
    expected.append_bytes(
        codec.encode_client_sync_batch(cids, eids, vals))
    assert wires_off[0] == bytes(expected.buf)
    # and the stamped wire is exactly legacy + flag + trailer
    unflagged = bytearray(wires_on[0][:len(wires_off[0])])
    unflagged[1] &= 0xBF
    assert bytes(unflagged) == wires_off[0]


def test_delta_leg_carries_stamp_and_kind_split(tiny_world_ticked):
    from goworld_tpu.net.game import GameServer

    w = tiny_world_ticked
    cids = np.asarray([b"C%015d" % i for i in range(3)], "S16")
    eids = np.asarray([b"E%015d" % i for i in range(3)], "S16")
    vals = np.ones((3, 4), np.float32)
    gs = GameServer(96, w, [], gc_freeze_on_boot=False,
                    sync_delta=True)
    wires = _flush_capture(gs, cids, eids, vals)
    mt, p = decode_wire(wires[0])
    assert mt == proto.MT_SYNC_POSITION_YAW_DELTA_ON_CLIENTS
    assert p.age is not None and p.age.seq == w.sync_age_anchor[0]
    # first batch is all keyframes -> the keyframe byte series moved
    assert metrics.counter("sync_bytes_out",
                           kind="keyframe").value == 3 * 53
    assert metrics.counter("sync_bytes_out",
                           kind="delta").value == 0


# =======================================================================
# gate-side delivery aging (unit, no sockets)
# =======================================================================
def test_gate_relay_ages_delivered_records(monkeypatch):
    """_relay_sync_records observes the tracker weighted by the records
    that actually left toward connected clients (unknown cids don't
    count)."""
    from goworld_tpu.net.gate import GateService

    gate = GateService.__new__(GateService)
    gate.gate_id = 5
    gate.clients = {}
    gate._m_down_batch = metrics.histogram(
        "gate_downstream_batch_records",
        buckets=metrics.DEFAULT_SIZE_BUCKETS)
    gate._m_relay_ms = metrics.histogram("gate_relay_ms")
    gate.syncage = syncage.AgeTracker()
    gate.downstream_max_bytes = 0

    sent = []

    class _Conn:
        def send_raw(self, msgtype, body):
            sent.append(body)
            return True

    class _CP:
        client_id = "C" + "0" * 15
        conn = _Conn()

    gate.clients[_CP.client_id] = _CP()
    cids = np.asarray([_CP.client_id.encode(), b"C%015d" % 9], "S16")
    eids = np.asarray([b"E%015d" % i for i in range(2)], "S16")
    vals = np.ones((2, 4), np.float32)
    now = syncage.now_us()
    stamp = syncage.SyncAgeStamp(1, now - 5000, now - 4000,
                                 now - 3000, now - 2000, now - 1000)
    gate._relay_sync_records(cids, eids, vals, age=stamp)
    snap = gate.syncage.snapshot()
    # only the ONE connected client's record was delivered and aged
    assert snap["e2e"]["samples"] == 1
    assert len(sent) == 1
    lanes = gate.syncage.last_lanes_ms
    assert lanes["device_tick"] == pytest.approx(1.0)
    assert sum(lanes.values()) == pytest.approx(
        gate.syncage.last_e2e_ms)
    # no stamp -> no observation, relay unchanged
    gate._relay_sync_records(cids, eids, vals, age=None)
    assert gate.syncage.snapshot()["e2e"]["samples"] == 1
    assert len(sent) == 2


# =======================================================================
# live standalone harness: game -> dispatcher -> gate over real sockets
# =======================================================================
def _run_loopback(sync_delta: bool, ticks: int = 20,
                  records_per_client: int = 32):
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.net.botclient import BotClient
    from goworld_tpu.net.game import GameServer
    from goworld_tpu.net.standalone import ClusterHarness

    class Account(Entity):
        ATTRS: dict = {}

    harness = ClusterHarness(n_dispatchers=1, n_gates=1,
                             desired_games=1)
    harness.start()
    gs = None
    stop = threading.Event()
    t = None
    try:
        world = _tiny_world()
        world.register_entity("Account", Account)
        world.create_nil_space()
        gs = GameServer(1, world, list(harness.dispatcher_addrs),
                        boot_entity="Account", gc_freeze_on_boot=False,
                        sync_delta=sync_delta)
        gs.start_network()
        inject = {"batch": None, "left": 0}

        def loop():
            while not stop.is_set():
                gs.pump()
                if inject["left"] > 0 and inject["batch"] is not None:
                    gs._sync_sink(1, *inject["batch"])
                    inject["left"] -= 1
                gs.tick()
                time.sleep(0.01)

        t = threading.Thread(target=loop, daemon=True)
        t.start()
        assert gs.ready_event.wait(30), "deployment never ready"
        bots = [BotClient(*harness.gate_addrs[0], bot_id=i)
                for i in range(2)]

        async def drain(bot):
            await bot.connect()
            try:
                await bot._recv_loop()
            except Exception:
                pass

        for b in bots:
            harness.submit(drain(b))
        deadline = time.time() + 20
        while time.time() < deadline:
            live = [e for e in world.entities.values()
                    if e.client is not None]
            if len(live) >= 2:
                break
            time.sleep(0.05)
        live = [e for e in world.entities.values()
                if e.client is not None]
        assert len(live) >= 2, "bots never reached the game"
        cids = np.repeat(np.asarray(
            [e.client.client_id for e in live], "S16"),
            records_per_client)
        eids = np.asarray([b"E%015d" % i for i in range(len(cids))],
                          "S16")
        vals = np.random.default_rng(0).random(
            (len(cids), 4), dtype=np.float32)
        tracker = harness.gates[0].syncage
        inject["batch"] = (cids, eids, vals)
        inject["left"] = ticks
        deadline = time.time() + 30
        while time.time() < deadline and (
                inject["left"] > 0
                or int(tracker.snapshot()["batches"]) < ticks // 2):
            time.sleep(0.1)
        return tracker, len(cids), harness, gs, stop, t
    except BaseException:
        stop.set()
        if t is not None:
            t.join(timeout=5)
        if gs is not None:
            gs.stop()
        harness.stop()
        raise


def _teardown(harness, gs, stop, t):
    stop.set()
    t.join(timeout=5)
    gs.stop()
    harness.stop()


def test_e2e_loopback_full_leg_ages_monotone_and_sum():
    tracker, n_rec, harness, gs, stop, t = _run_loopback(
        sync_delta=False)
    try:
        snap = tracker.snapshot()
        # nonzero ages, every record delivered was aged
        assert snap["batches"] >= 10
        assert snap["e2e"]["samples"] >= 10 * n_rec
        assert snap["e2e"]["p50_ms"] > 0
        # monotone boundaries on one host: ZERO warped clamps
        assert snap["clock_warp_total"] == 0
        # the dispatcher hop was actually stamped mid-path
        assert snap["hops"]["dispatcher"]["samples"] == \
            snap["e2e"]["samples"]
        # per-hop lanes sum EXACTLY to the e2e age (the freshest
        # observation is pre-bucketing; bucket tolerance not needed)
        lanes = tracker.last_lanes_ms
        assert lanes["device_tick"] > 0
        assert sum(lanes.values()) == pytest.approx(
            tracker.last_e2e_ms, abs=1e-6)
        # histogram-level: every lane saw the same weighted count
        for hop in syncage.HOPS:
            assert snap["hops"][hop]["samples"] == \
                snap["e2e"]["samples"]

        # /syncage endpoint serves this tracker (registered by the
        # GateService constructor)
        srv = debug_http.start(0, process_name="gate1-test")
        try:
            port = srv.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/syncage",
                    timeout=5) as resp:
                payload = json.loads(resp.read())
            assert "gate1" in payload
            assert payload["gate1"]["e2e"]["samples"] == \
                snap["e2e"]["samples"]

            # deployment aggregator merges this process's plane and
            # renders the ONE verdict line (cli.py watch path)
            import obs_aggregate

            agg = obs_aggregate.aggregate(
                [("gate1", f"http://127.0.0.1:{port}")])
            assert agg["e2e"]["samples"] == snap["e2e"]["samples"]
            assert "pass" in agg
            line = obs_aggregate.verdict_line(agg)
            assert "deployment sync-age" in line and "p99" in line
            assert obs_aggregate.hop_table(agg)
        finally:
            srv.shutdown()
    finally:
        _teardown(harness, gs, stop, t)


def test_e2e_loopback_delta_leg_carries_ages():
    tracker, n_rec, harness, gs, stop, t = _run_loopback(
        sync_delta=True, ticks=12)
    try:
        snap = tracker.snapshot()
        assert snap["batches"] >= 6
        assert snap["e2e"]["samples"] >= 6 * n_rec
        assert snap["clock_warp_total"] == 0
        assert tracker.last_lanes_ms["device_tick"] > 0
        # the delta codec's byte-kind split moved on the game side
        assert metrics.counter("sync_bytes_out",
                               kind="keyframe").value > 0
    finally:
        _teardown(harness, gs, stop, t)


# =======================================================================
# aggregator units (no sockets)
# =======================================================================
def test_aggregator_merges_counts_exactly(monkeypatch):
    import obs_aggregate

    t1 = syncage.AgeTracker(name="g1")
    t2 = syncage.AgeTracker(name="g2")
    s = syncage.SyncAgeStamp(1, 0, 1000, 2000, 3000, 4000)
    t1.observe(s, 8000, 100)
    t2.observe(s, 30000, 50)  # 30 ms ages on the second gate
    snaps = {"g1": {"gate1": t1.snapshot()},
             "g2": {"gate2": t2.snapshot()}}

    def fake_fetch(url, timeout=2.0):
        for label, payload in snaps.items():
            if url.startswith(f"http://{label}"):
                if url.endswith("/syncage"):
                    return payload
                raise OSError("only /syncage faked")
        raise OSError("unknown target")

    monkeypatch.setattr(obs_aggregate, "_fetch_json", fake_fetch)
    agg = obs_aggregate.aggregate(
        [("g1", "http://g1"), ("g2", "http://g2"),
         ("dead", "http://dead")])
    assert agg["e2e"]["samples"] == 150
    assert len(agg["gates"]) == 2
    assert "dead" in agg["skipped"]
    # the merged p99 reflects the slow gate's mass
    assert agg["e2e"]["p99_ms"] == "inf" or \
        agg["e2e"]["p99_ms"] > 16.0
    assert agg["pass"] is False
    assert "FAIL" in obs_aggregate.verdict_line(agg)


def test_aggregator_honest_when_nothing_answers(monkeypatch):
    import obs_aggregate

    def fail(url, timeout=2.0):
        raise OSError("down")

    monkeypatch.setattr(obs_aggregate, "_fetch_json", fail)
    agg = obs_aggregate.aggregate([("g1", "http://g1")])
    assert agg["gates"] == [] and "e2e" not in agg
    assert "no stamped deliveries" in obs_aggregate.verdict_line(agg)
