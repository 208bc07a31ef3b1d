"""Overload-protection plane (ISSUE 4): admission control, prioritized
backpressure, tick-deadline load shedding, circuit breakers.

Unit tier: traffic classification, governor ladder + hysteresis with
the seeded-replay determinism contract (equal signal streams ->
byte-identical transition logs), class-priority queues, token bucket,
circuit breaker (incl. the kvdb fail-fast integration), gate
downstream bounds + kick, game ingress shedding.

Live tier (``overload`` marker): a standalone cluster under a seeded
delay-fault schedule takes a bot flood of slow RPCs + position spam;
the ladder must engage (>= SHEDDING), only cheap classes may shed
(``shed_total`` for critical/rpc stays zero), the serve loop survives,
and the process returns to NORMAL after the flood stops.
"""

import asyncio
import json
import threading
import time
import types
import urllib.request
from random import Random

import pytest

from goworld_tpu.net import proto
from goworld_tpu.net.packet import Packet, new_packet
from goworld_tpu.utils import faults, metrics, overload


@pytest.fixture(autouse=True)
def _clean_planes():
    yield
    faults.uninstall()


# =======================================================================
# traffic classification
# =======================================================================
def test_classify_traffic_classes():
    # the PROCESS-level control plane is critical
    for mt in (proto.MT_SET_GAME_ID, proto.MT_NOTIFY_CLIENT_CONNECTED,
               proto.MT_KVREG_REGISTER, proto.MT_START_FREEZE_GAME,
               proto.MT_NOTIFY_DEPLOYMENT_READY):
        assert overload.classify(mt) == overload.CLASS_CRITICAL, mt
    # RPC (both directions), the client event bundle, AND the
    # entity-addressed order-sensitive control (migration legs,
    # disconnects) — never shed, and FIFO with each other so an ack /
    # disconnect can never overtake the same entity's queued calls
    for mt in (proto.MT_CALL_ENTITY_METHOD,
               proto.MT_CALL_ENTITY_METHOD_FROM_CLIENT,
               proto.MT_CLIENT_EVENTS_BATCH,
               proto.MT_CREATE_ENTITY_ON_CLIENT,
               proto.MT_REAL_MIGRATE, proto.MT_MIGRATE_REQUEST_ACK,
               proto.MT_CANCEL_MIGRATE,
               proto.MT_NOTIFY_CLIENT_DISCONNECTED):
        assert overload.classify(mt) == overload.CLASS_RPC, mt
    # server->client sync fan-out above client-origin event streams
    assert overload.classify(proto.MT_SYNC_POSITION_YAW_ON_CLIENTS) \
        == overload.CLASS_SYNC
    assert overload.classify(proto.MT_SYNC_POSITION_YAW_FROM_CLIENT) \
        == overload.CLASS_EVENTS
    assert overload.classify(proto.MT_CLIENT_SYNC_POSITION_YAW) \
        == overload.CLASS_EVENTS
    assert overload.classify(proto.MT_HEARTBEAT) == overload.CLASS_NOISE
    # unknown msgtypes fail SAFE: never shed
    assert overload.classify(31337) == overload.CLASS_RPC


def test_shed_floor_never_reaches_critical_or_rpc():
    g = overload.OverloadGovernor("floor-test")
    for state in (overload.NORMAL, overload.DEGRADED,
                  overload.SHEDDING, overload.REJECTING):
        g.state = state
        assert not g.should_shed(overload.CLASS_CRITICAL)
        assert not g.should_shed(overload.CLASS_RPC)
    g.state = overload.NORMAL
    assert not g.should_shed(overload.CLASS_NOISE)
    g.state = overload.DEGRADED
    assert not g.should_shed(overload.CLASS_EVENTS)
    g.state = overload.SHEDDING
    assert g.should_shed(overload.CLASS_EVENTS)
    assert g.should_shed(overload.CLASS_NOISE)
    assert not g.should_shed(overload.CLASS_SYNC)
    g.state = overload.REJECTING
    assert g.should_shed(overload.CLASS_SYNC)


# =======================================================================
# governor: ladder + hysteresis + deterministic replay
# =======================================================================
def test_ladder_escalates_one_rung_per_threshold():
    g = overload.OverloadGovernor("ladder", up_ticks=3, down_ticks=4)
    # two pressured ticks are not enough
    g.observe(2.0)
    g.observe(2.0)
    assert g.state == overload.NORMAL
    g.observe(2.0)
    assert g.state == overload.DEGRADED
    # the score resets per rung: three more to climb again
    for _ in range(3):
        g.observe(2.0)
    assert g.state == overload.SHEDDING
    for _ in range(3):
        g.observe(2.0)
    assert g.state == overload.REJECTING
    # REJECTING is the top rung
    for _ in range(10):
        g.observe(10.0)
    assert g.state == overload.REJECTING
    # rungs never skip: transitions are adjacent pairs
    for _, frm, to, _r in g.transitions:
        assert abs(to - frm) == 1


def test_hysteresis_band_holds_the_rung():
    g = overload.OverloadGovernor("hyst", up_ticks=2, down_ticks=3,
                                  latency_ratio=1.5)
    g.observe(2.0)
    g.observe(2.0)
    assert g.state == overload.DEGRADED
    # in-band observations (between calm and pressured) hold the rung
    # forever — no flapping in the gray zone
    for _ in range(50):
        g.observe(1.2)
    assert g.state == overload.DEGRADED
    assert len(g.transitions) == 1
    # a calm run shorter than down_ticks is reset by one pressured tick
    g.observe(0.1)
    g.observe(0.1)
    g.observe(2.0)
    g.observe(2.0)
    assert g.state == overload.SHEDDING
    # sustained calm descends one rung per down_ticks run
    for _ in range(3):
        g.observe(0.1)
    assert g.state == overload.DEGRADED
    for _ in range(3):
        g.observe(0.1)
    assert g.state == overload.NORMAL


def test_severe_pressure_climbs_faster():
    slow = overload.OverloadGovernor("sev-a", up_ticks=8)
    fast = overload.OverloadGovernor("sev-b", up_ticks=8)
    for _ in range(2):
        slow.observe(1.6)   # plain pressure: 2/8 — still NORMAL
        fast.observe(20.0)  # severe: 2 * boost(4) = 8/8 — DEGRADED
    assert slow.state == overload.NORMAL
    assert fast.state == overload.DEGRADED


def _seeded_signals(seed: int, n: int = 2000):
    """A reproducible synthetic load trace: calm / pressured / severe
    stretches chosen by a seeded RNG (the same shape a seeded fault
    schedule produces in a live run)."""
    rng = Random(seed)
    out = []
    while len(out) < n:
        kind = rng.random()
        run = rng.randrange(1, 40)
        for _ in range(run):
            if kind < 0.4:
                out.append((rng.uniform(0.0, 0.5), 0.0, 0.0, 0.0))
            elif kind < 0.8:
                out.append((rng.uniform(1.6, 2.5),
                            rng.uniform(0.0, 3.0), 0.0, 0.0))
            else:
                out.append((rng.uniform(4.0, 30.0),
                            rng.uniform(8.0, 20.0),
                            rng.uniform(0.5, 1.0), 0.0))
    return out[:n]


def test_equal_seeds_produce_identical_transition_logs():
    """ISSUE 4 acceptance: the ladder is a pure function of the
    observation stream — equal seeds replay byte-identical transition
    logs; a different seed diverges."""
    a = overload.OverloadGovernor("replay-a", up_ticks=4, down_ticks=8)
    b = overload.OverloadGovernor("replay-b", up_ticks=4, down_ticks=8)
    c = overload.OverloadGovernor("replay-c", up_ticks=4, down_ticks=8)
    for sig in _seeded_signals(42):
        a.observe(*sig)
        b.observe(*sig)
    for sig in _seeded_signals(43):
        c.observe(*sig)
    assert a.log_lines() == b.log_lines()
    assert a.log_lines()          # the trace does transition
    assert a.log_lines() != c.log_lines()


# =======================================================================
# class-priority queues
# =======================================================================
def test_class_queues_priority_order_and_bounds():
    q = overload.ClassQueues(bounds={overload.CLASS_EVENTS: 2},
                             stage="t_q")
    assert q.offer(overload.CLASS_EVENTS, "e1")
    assert q.offer(overload.CLASS_SYNC, "s1")
    assert q.offer(overload.CLASS_CRITICAL, "c1")
    assert q.offer(overload.CLASS_RPC, "r1")
    assert q.offer(overload.CLASS_EVENTS, "e2")
    # events bound = 2: the third is dropped AND counted
    drop0 = overload.shed_counter(overload.CLASS_EVENTS, "t_q").value
    assert not q.offer(overload.CLASS_EVENTS, "e3")
    assert overload.shed_counter(
        overload.CLASS_EVENTS, "t_q").value == drop0 + 1
    assert q.qsize() == 5
    # drain: strict priority order, FIFO within a class
    assert q.drain() == ["c1", "r1", "s1", "e1", "e2"]
    assert q.qsize() == 0
    with pytest.raises(IndexError):
        q.pop()


# =======================================================================
# token bucket (deterministic under an injected clock)
# =======================================================================
def test_token_bucket_rate_and_burst():
    now = [0.0]
    b = overload.TokenBucket(10.0, burst=5.0, clock=lambda: now[0])
    assert all(b.allow() for _ in range(5))   # burst drains
    assert not b.allow()                      # empty
    now[0] += 0.1                             # refills 1 token
    assert b.allow()
    assert not b.allow()
    now[0] += 10.0                            # refill caps at burst
    assert all(b.allow() for _ in range(5))
    assert not b.allow()
    # disabled bucket always allows
    free = overload.TokenBucket(0.0, clock=lambda: now[0])
    assert all(free.allow() for _ in range(100))


# =======================================================================
# circuit breaker
# =======================================================================
def test_circuit_breaker_opens_half_opens_and_recovers():
    now = [0.0]
    br = overload.CircuitBreaker("t_br", failure_threshold=3,
                                 reset_timeout=5.0,
                                 clock=lambda: now[0])
    assert br.allow()
    for _ in range(3):
        br.record_failure()
    assert br.state == br.OPEN
    assert not br.allow()                 # fail fast while open
    now[0] += 5.0
    assert br.allow()                     # the half-open probe
    assert br.state == br.HALF_OPEN
    assert not br.allow()                 # only ONE probe per window
    br.record_failure()                   # probe failed -> re-open
    assert br.state == br.OPEN
    assert not br.allow()
    now[0] += 5.0
    assert br.allow()
    br.record_success()                   # probe succeeded -> closed
    assert br.state == br.CLOSED
    assert br.allow()


def test_circuit_breaker_unsettled_probe_cannot_wedge():
    """A probe whose caller died without record_success/record_failure
    (e.g. a non-transient exception path) must not pin the breaker
    HALF_OPEN forever: another probe is granted after a reset window."""
    now = [0.0]
    br = overload.CircuitBreaker("t_wedge", failure_threshold=1,
                                 reset_timeout=5.0,
                                 clock=lambda: now[0])
    br.record_failure()
    now[0] += 5.0
    assert br.allow()          # probe granted... and never settled
    assert not br.allow()
    now[0] += 5.0
    assert br.allow()          # the slot frees after another window
    br.record_success()
    assert br.state == br.CLOSED


def test_kvdb_circuit_open_fails_fast_without_retries():
    """A dead backend must stop costing 3 retry attempts per op: once
    the breaker opens, ops fail fast through the callback with
    CircuitOpenError and the backend is not touched."""
    import queue

    from goworld_tpu.kvdb import KVDB, MemoryKVDB
    from goworld_tpu.utils.asyncwork import AsyncWorkers

    faults.plane = faults.FaultPlane(
        faults.parse_schedule("err:kvdb.get:1.0"), 7, process="t")
    faults.active = True
    posted = queue.Queue()
    kv = KVDB(MemoryKVDB(), AsyncWorkers(posted.put))
    kv.breaker = overload.CircuitBreaker(
        "t_kvdb", failure_threshold=2, reset_timeout=60.0)

    def run_get():
        out = []
        kv.get("k", lambda v, e: out.append((v, e)))
        deadline = time.time() + 10
        while not out and time.time() < deadline:
            try:
                posted.get(timeout=0.1)()
            except queue.Empty:
                pass
        assert out, "kvdb get callback never fired"
        return out[0]

    # first op: 3 failing attempts -> breaker (threshold 2) opens
    _, err = run_get()
    assert isinstance(err, faults.InjectedFaultError)
    assert kv.breaker.state == kv.breaker.OPEN
    # second op: rejected fast, no backend attempt (trials frozen)
    trials_before = faults.plane.rules[0].trials
    rejected0 = kv._m_circuit_rejected.value
    _, err = run_get()
    assert isinstance(err, overload.CircuitOpenError)
    assert faults.plane.rules[0].trials == trials_before
    assert kv._m_circuit_rejected.value == rejected0 + 1


# =======================================================================
# gate: downstream bounds + kick, admission refusal
# =======================================================================
class _FakeTransport:
    def __init__(self):
        self.buffered = 0

    def get_write_buffer_size(self):
        return self.buffered


class _FakeWriter:
    def __init__(self):
        self.transport = _FakeTransport()
        self.chunks = []

    def write(self, data):
        self.chunks.append(bytes(data))

    def close(self):
        pass

    async def wait_closed(self):
        pass

    def get_extra_info(self, _):
        return None


def _mk_gate(**kw):
    from goworld_tpu.net.gate import GateService

    return GateService(1, "127.0.0.1", 0, [("127.0.0.1", 1)],
                       exit_on_dispatcher_loss=False, **kw)


def test_gate_downstream_bound_drops_and_kicks():
    from goworld_tpu.net.gate import ClientProxy
    from goworld_tpu.net.packet import PacketConnection

    async def scenario():
        gate = _mk_gate(downstream_max_bytes=100,
                        downstream_kick_secs=0.05)
        w = _FakeWriter()
        cp = ClientProxy(PacketConnection(None, w))
        gate.clients[cp.client_id] = cp

        def pkt():
            p = new_packet(proto.MT_CLIENT_SYNC_POSITION_YAW)
            p.append_bytes(b"z" * 40)
            return p

        drop0 = gate._m_down_dropped.value
        kick0 = gate._m_kicked.value
        gate._send_to_client(cp, pkt())        # fits
        assert len(w.chunks) == 1
        w.transport.buffered = 90              # consumer stalled
        gate._send_to_client(cp, pkt())        # over budget: dropped
        assert len(w.chunks) == 1
        assert gate._m_down_dropped.value == drop0 + 1
        assert cp.down_full_since is not None
        assert cp.client_id in gate.clients    # not kicked yet
        await asyncio.sleep(0.08)              # past the kick window
        gate._send_to_client(cp, pkt())
        assert gate._m_kicked.value == kick0 + 1
        assert cp.client_id not in gate.clients  # kicked, never wedged
        # a draining buffer clears the strike (and the governor's
        # stalled-client set)
        cp2 = ClientProxy(PacketConnection(None, _FakeWriter()))
        gate.clients[cp2.client_id] = cp2
        cp2.conn.writer.transport.buffered = 90
        gate._send_to_client(cp2, pkt())
        assert cp2.down_full_since is not None
        assert cp2.client_id in gate._down_full
        cp2.conn.writer.transport.buffered = 0
        gate._send_to_client(cp2, pkt())
        assert cp2.down_full_since is None
        assert cp2.client_id not in gate._down_full
        # a correctness-critical message that cannot be buffered kicks
        # IMMEDIATELY — dropping a create_entity would silently desync
        # the client's world forever
        cp3 = ClientProxy(PacketConnection(None, _FakeWriter()))
        gate.clients[cp3.client_id] = cp3
        cp3.conn.writer.transport.buffered = 90
        crit = new_packet(proto.MT_CREATE_ENTITY_ON_CLIENT)
        crit.append_bytes(b"y" * 40)
        kick1 = gate._m_kicked.value
        gate._send_to_client(cp3, crit)
        assert gate._m_kicked.value == kick1 + 1
        assert cp3.client_id not in gate.clients

    asyncio.run(scenario())


def test_gate_refuses_handshakes_at_cap_and_in_rejecting():
    gate = _mk_gate(max_clients=1)
    assert gate._refuse_new_client() is None
    gate.clients["x" * 16] = object()
    assert "max_clients" in gate._refuse_new_client()
    gate.clients.clear()
    gate.overload.state = overload.REJECTING
    assert "REJECTING" in gate._refuse_new_client()
    gate.overload.state = overload.SHEDDING
    assert gate._refuse_new_client() is None


def test_gate_rate_limit_sheds_rpc_but_never_heartbeats():
    from goworld_tpu.net.gate import ClientProxy
    from goworld_tpu.net.packet import PacketConnection

    async def scenario():
        gate = _mk_gate(rate_limit_pps=2.0)
        w = _FakeWriter()
        cp = ClientProxy(PacketConnection(None, w))
        cp.bucket = overload.TokenBucket(2.0, burst=2.0)
        gate.clients[cp.client_id] = cp
        limited0 = overload.shed_counter(
            overload.CLASS_RPC, "gate_ratelimit").value

        def rpc():
            p = new_packet(proto.MT_CALL_ENTITY_METHOD_FROM_CLIENT)
            p.append_entity_id("e" * 16)
            p.append_var_str("M")
            p.append_args(())
            q = Packet(bytes(p.buf))
            q.read_u16()
            return q

        for _ in range(5):
            gate._handle_client_packet(
                cp, proto.MT_CALL_ENTITY_METHOD_FROM_CLIENT, rpc())
        assert overload.shed_counter(
            overload.CLASS_RPC, "gate_ratelimit").value >= limited0 + 3
        # heartbeats bypass the limiter entirely (liveness)
        hb0 = len(w.chunks)
        for _ in range(3):
            p = new_packet(proto.MT_HEARTBEAT)
            q = Packet(bytes(p.buf))
            q.read_u16()
            gate._handle_client_packet(cp, proto.MT_HEARTBEAT, q)
        assert len(w.chunks) == hb0 + 3

    asyncio.run(scenario())


# =======================================================================
# game: ingress shedding + priority pump
# =======================================================================
def _mk_gameserver(**kw):
    from goworld_tpu.net.game import GameServer

    world = types.SimpleNamespace(
        _multihost=False, mh_rank=0, sync_stride=1,
        entities={}, spaces={}, op_stats={},
    )
    return GameServer(99, world, [], gc_freeze_on_boot=False, **kw)


def test_game_ingress_sheds_cheap_classes_only():
    gs = _mk_gameserver()
    gs.overload.state = overload.SHEDDING
    shed0 = overload.shed_counter(
        overload.CLASS_EVENTS, "game_ingress").value

    gs._on_packet_netthread(
        0, proto.MT_SYNC_POSITION_YAW_FROM_CLIENT, Packet(b""))
    assert gs._packet_q.qsize() == 0          # shed at ingress
    assert overload.shed_counter(
        overload.CLASS_EVENTS, "game_ingress").value == shed0 + 1

    # rpc + critical always get through, even in REJECTING
    gs.overload.state = overload.REJECTING
    gs._on_packet_netthread(
        0, proto.MT_CALL_ENTITY_METHOD_FROM_CLIENT, Packet(b""))
    gs._on_packet_netthread(0, proto.MT_NOTIFY_DEPLOYMENT_READY,
                            Packet(b""))
    gs._on_packet_netthread(0, proto.MT_REAL_MIGRATE, Packet(b""))
    assert gs._packet_q.qsize() == 3

    # the pump drains process-control first; entity-addressed traffic
    # (RPCs, migration legs) stays FIFO within the rpc class
    seen = []
    gs._handle_packet = lambda d, mt, p: seen.append(mt)
    gs.pump()
    assert seen == [proto.MT_NOTIFY_DEPLOYMENT_READY,
                    proto.MT_CALL_ENTITY_METHOD_FROM_CLIENT,
                    proto.MT_REAL_MIGRATE]


def test_game_observe_pushes_sync_stride_into_world():
    gs = _mk_gameserver(degraded_sync_stride=4,
                        overload_up_ticks=1)
    gs._observe_overload(10.0 * gs.tick_interval, 8.0)  # severe
    assert gs.overload.state == overload.DEGRADED
    assert gs.world.sync_stride == 4
    gs.overload.state = overload.NORMAL
    gs._observe_overload(0.0, 0.0)
    assert gs.world.sync_stride == 1


def test_degraded_event_coalesce_flushes_every_nth_tick():
    gs = _mk_gameserver(degraded_event_coalesce=2)
    flushed = []
    gs._flush_events_out = lambda: flushed.append(True)
    gs.overload.state = overload.DEGRADED
    gs._flush_sync_out()           # odd phase: held
    gs._flush_sync_out()           # even phase: flushed
    assert len(flushed) == 1
    gs._flush_sync_out(force=True)  # freeze path always flushes
    assert len(flushed) == 2
    gs.overload.state = overload.NORMAL
    gs._flush_sync_out()
    assert len(flushed) == 3


# =======================================================================
# /overload endpoint
# =======================================================================
def test_debug_http_overload_endpoint():
    from goworld_tpu.utils import debug_http

    overload.register(overload.OverloadGovernor("ep-test"))
    srv = debug_http.start(0, process_name="overload-test")
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/overload", timeout=5) as r:
            snap = json.loads(r.read())
        assert snap["governors"]["ep-test"]["state"] == "NORMAL"
        assert "shed" in snap and "breakers" in snap
        assert snap["classes"]["critical"] == 0
    finally:
        srv.shutdown()
        srv.server_close()
        overload.unregister("ep-test")


# =======================================================================
# live overload smoke (the acceptance scenario; `overload` marker)
# =======================================================================
OVERLOAD_SEED = 4242


@pytest.mark.overload
def test_overload_smoke_ladder_engages_sheds_cheap_and_recovers():
    """ISSUE 4 acceptance: under a bot flood (slow RPCs + position
    spam) with seeded delay faults active, the game's ladder engages
    (>= SHEDDING), every shed packet is counted, the
    migration/persistence/RPC classes shed NOTHING, the serve loop
    never dies, and the process returns to NORMAL within a bounded
    interval after the flood stops."""
    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.net.botclient import BotClient
    from goworld_tpu.net.game import GameServer
    from goworld_tpu.net.standalone import ClusterHarness
    from goworld_tpu.ops.aoi import GridSpec

    class OvAccount(Entity):
        ATTRS = {"status": "client"}

        def OnClientConnected(self):
            self.attrs["status"] = "online"

        def Stress_Client(self, ms):
            # simulated expensive handler: the flood's tick-budget hog
            time.sleep(ms / 1000.0)

        def Ping_Client(self):
            self.call_client("OnPong")

    # the PR-3 fault grammar supplies the wire chaos (delay faults on
    # the client-facing edge), seeded for reproducibility
    faults.plane = faults.FaultPlane(
        faults.parse_schedule("delay:gate->dispatcher:0.5:5ms"),
        OVERLOAD_SEED, process="overload-smoke",
    )
    faults.active = True

    harness = ClusterHarness(n_dispatchers=1, n_gates=1,
                             desired_games=1)
    harness.start()
    world = World(
        WorldConfig(capacity=64, grid=GridSpec(
            radius=10.0, extent_x=40.0, extent_z=40.0)),
        n_spaces=1,
    )
    world.register_entity("OvAccount", OvAccount)
    world.create_nil_space()
    gs = GameServer(
        1, world, list(harness.dispatcher_addrs),
        boot_entity="OvAccount", gc_freeze_on_boot=False,
        overload_up_ticks=3, overload_down_ticks=3,
        degraded_sync_stride=2, degraded_event_coalesce=2,
    )
    gs.start_network()
    # per-class shed baselines (the registry is process-global)
    base = {
        (cls, stage): overload.shed_counter(cls, stage).value
        for cls in range(overload.N_CLASSES)
        for stage in ("game_ingress", "game_queue", "gate_ingress",
                      "gate_ratelimit", "dispatcher_pend", "stride")
    }
    t = None
    try:
        # warm the boot compile + reach readiness on the test thread,
        # then SIZE the tick budget from the measured steady tick cost
        # — the smoke must engage the ladder on any machine speed, so
        # the "deadline" is defined relative to what this box can do
        deadline = time.monotonic() + 60
        while not gs.ready_event.is_set() \
                and time.monotonic() < deadline:
            gs.pump()
            gs.tick()
            time.sleep(0.01)
        assert gs.ready_event.is_set(), "deployment never became ready"
        costs = []
        for _ in range(8):
            t0 = time.perf_counter()
            gs.pump()
            gs.tick()
            costs.append(time.perf_counter() - t0)
        steady = sorted(costs)[len(costs) // 2]
        # idle ratio ~0.4 (calm, under the 0.9 hysteresis floor); one
        # stressed tick is ~3.9x (severe) — each climbs a full rung
        gs.tick_interval = max(0.05, 2.5 * steady)
        stress_ms = int(gs.tick_interval * 3500)

        t = threading.Thread(target=gs.serve_forever, daemon=True)
        t.start()
        assert gs.overload.state == overload.NORMAL

        peak = [overload.NORMAL]

        async def flood():
            bot = BotClient(*harness.gate_addrs[0])
            await bot.connect()
            recv = asyncio.ensure_future(bot._recv_loop())
            try:
                await asyncio.wait_for(bot.player_ready.wait(), 60)
                # phase 1: slow RPCs until the ladder engages (each
                # stressed tick is 'severe': one rung per up_ticks
                # run). Paced AT the stress duration so arrival ~=
                # service — the ticks run hot (~3.5x budget) but stay
                # bounded, and the governor gets an observation per
                # tick instead of one mega-tick swallowing the clock.
                sent = 0
                deadline = time.monotonic() + 90
                while peak[0] < overload.SHEDDING \
                        and time.monotonic() < deadline:
                    bot.call_server("Stress_Client", stress_ms)
                    bot.send_position(float(sent % 7), 0.0,
                                      float(sent % 5), 0.0)
                    sent += 1
                    await asyncio.sleep(stress_ms / 1000.0 * 1.1)
                    peak[0] = max(peak[0], gs.overload.state)
                # phase 2: keep events-class traffic flowing while the
                # ladder is engaged so shedding demonstrably happens
                deadline = time.monotonic() + 30
                while gs.overload.state >= overload.SHEDDING \
                        and time.monotonic() < deadline:
                    bot.send_position(1.0, 0.0, 1.0, 0.0)
                    await asyncio.sleep(0.02)
                return sent
            finally:
                recv.cancel()
                await bot.conn.close()

        sent = harness.submit(flood()).result(timeout=240)
        assert sent >= 3, "flood never ran"
        assert peak[0] >= overload.SHEDDING, (
            f"ladder never engaged (peak {overload.STATE_NAMES[peak[0]]};"
            f" transitions {gs.overload.log_lines()})"
        )
        assert t.is_alive(), "serve loop died under the flood"

        # every shed is counted, and ONLY cheap classes shed: the
        # critical + rpc rows stay exactly at their baselines while
        # the cheap classes demonstrably dropped something
        cheap_shed = 0.0
        for (cls, stage), v0 in base.items():
            v = overload.shed_counter(cls, stage).value
            if cls in (overload.CLASS_CRITICAL, overload.CLASS_RPC):
                assert v == v0, (
                    f"{overload.CLASS_NAMES[cls]} shed at {stage}: "
                    f"{v - v0} packets"
                )
            else:
                cheap_shed += v - v0
        assert cheap_shed > 0, "ladder engaged but nothing was shed"

        # recovery: flood stopped -> NORMAL within a bounded interval
        deadline = time.monotonic() + 120
        while gs.overload.state != overload.NORMAL \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert gs.overload.state == overload.NORMAL, (
            f"never recovered: {gs.overload.log_lines()}"
        )
        assert t.is_alive()

        # the transition log walked the ladder one rung at a time
        for _, frm, to, _r in gs.overload.transitions:
            assert abs(to - frm) == 1

        # post-recovery liveness: a fresh RPC round trip completes
        async def ping():
            bot = BotClient(*harness.gate_addrs[0])
            await bot.connect()
            recv = asyncio.ensure_future(bot._recv_loop())
            try:
                await asyncio.wait_for(bot.player_ready.wait(), 30)
                bot.call_server("Ping_Client")
                for _ in range(200):
                    if any(m == "OnPong" for _, m, _a in bot.rpc_log):
                        return True
                    await asyncio.sleep(0.05)
                return False
            finally:
                recv.cancel()
                await bot.conn.close()

        assert harness.submit(ping()).result(timeout=60), \
            "post-recovery RPC round trip failed"
    finally:
        gs._stop.set()
        if t is not None:
            t.join(timeout=30)
        gs.stop()
        harness.stop()


# =======================================================================
# slow tier: chaos_soak overload scenario (double-run JSON report)
# =======================================================================
@pytest.mark.overload
@pytest.mark.slow
def test_chaos_soak_overload_scenario_report(tmp_path):
    """tools/chaos_soak.py --scenario overload drives a bot flood at a
    configured msg/s against a real CLI cluster while delay faults are
    active, and must report an engaged + recovered ladder with zero
    critical/rpc sheds, in the same JSON report shape as the kill
    scenario."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    out = str(tmp_path / "overload_report.json")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "chaos_soak.py"),
         "--scenario", "overload",
         "--dir", str(tmp_path / "cluster"),
         "--seed", "77", "--flood-secs", "6", "--msg-rate", "120",
         "--out", out],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    with open(out) as f:
        report = json.load(f)
    assert report["scenario"] == "overload"
    assert report["converged"]
    assert report["engaged"] and report["returned_normal"]
    assert report["critical_shed"] == 0
