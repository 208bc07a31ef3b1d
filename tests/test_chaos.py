"""Chaos: deterministic fault injection + supervised cluster recovery.

Unit tier: schedule grammar, seeded-decision determinism, wire-fault
application at the PacketConnection seam, the bounded reconnect pend
queue, and the kvdb/storage op-fault + retry wrappers.

Live tier (``chaos`` marker): a real 1-dispatcher/1-game/1-gate cluster
(OS processes via the ops CLI, the test_cli.py pattern) runs under a
seeded schedule with ≥3 wire-fault kinds plus a deterministic game kill
(``crash:game.tick@n=...``); `supervise` restarts the game from its
crash-recovery checkpoint, the census re-handshake completes (a fresh
client logs in and audits), the persistent Vault entity survives with
its exact pre-kill value, and the gate's ``/faults`` log equals the log
computed locally from (seed, spec, trial count) — the seeded-replay
guarantee. The full double-run soak lives behind ``-m slow``
(tools/chaos_soak.py).
"""

import asyncio
import json
import os
import threading
import time
import urllib.request

import pytest

from goworld_tpu import cli
from goworld_tpu.net import proto
from goworld_tpu.net.packet import Packet, PacketConnection, new_packet
from goworld_tpu.utils import faults


def _chaos_soak_mod():
    """tools/chaos_soak.py is the ONE copy of the chaos harness (game
    script, cluster ini, fault spec); the live smoke below reuses it so
    the smoke and the slow double-run soak can never drift apart."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "chaos_soak.py",
    )
    spec = importlib.util.spec_from_file_location("gw_chaos_soak", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_plane():
    yield
    faults.uninstall()


def _install(spec: str, seed: int = 7, process: str = "test") -> faults.FaultPlane:
    """Install a plane directly (bypassing env)."""
    faults.plane = faults.FaultPlane(
        faults.parse_schedule(spec), seed, process=process
    )
    faults.active = True
    return faults.plane


# =======================================================================
# grammar + determinism
# =======================================================================
def test_parse_schedule_kinds():
    rules = faults.parse_schedule(
        "drop:game->dispatcher:0.05,"
        "delay:gate->dispatcher:mt=13:0.5:20ms,"
        "truncate:*->dispatcher:0.1,"
        "disconnect:game->*:0.01,"
        "dup:gate->dispatcher:1.0,"
        "kill:game1@t+10s,"
        "err:kvdb.put:0.2,"
        "err:storage.*:0.1,"
        "crash:freeze.write:1.0,"
        "crash:game.tick@n=600"
    )
    kinds = [r.kind for r in rules]
    assert kinds == ["drop", "delay", "truncate", "disconnect", "dup",
                     "kill", "err", "err", "crash", "crash"]
    assert rules[1].msgtype == 13 and rules[1].delay_s == 0.02
    assert rules[2].src == "*" and rules[2].dst == "dispatcher"
    assert rules[5].target == "game1" and rules[5].at_s == 10.0
    assert rules[7].op == "*"
    assert rules[9].at_n == 600


@pytest.mark.parametrize("bad", [
    "explode:game->dispatcher:0.5",      # unknown kind
    "drop:nodirection:0.5",              # missing ->
    "drop:game->dispatcher",             # missing probability
    "kill:game1",                        # missing @t+...s
    "err:frobnicator.put:0.5",           # unknown subsystem
    "delay:game->dispatcher:0.5:20",     # delay without ms
])
def test_parse_schedule_rejects(bad):
    with pytest.raises(ValueError):
        faults.parse_schedule(bad)


def test_seeded_decisions_are_reproducible():
    spec = "drop:gate->dispatcher:0.3,dup:gate->dispatcher:0.3"
    p1 = faults.FaultPlane(faults.parse_schedule(spec), 42)
    p2 = faults.FaultPlane(faults.parse_schedule(spec), 42)
    p3 = faults.FaultPlane(faults.parse_schedule(spec), 43)
    for _ in range(300):
        p1.wire_fault("gate->dispatcher", 13)
        p2.wire_fault("gate->dispatcher", 13)
        p3.wire_fault("gate->dispatcher", 13)
    assert p1.log_lines() == p2.log_lines()      # byte-identical replay
    assert p1.log_lines() != p3.log_lines()      # the seed is the input
    assert p1.injected_total > 0


def test_deterministic_tick_crash_rule():
    p = faults.FaultPlane(
        faults.parse_schedule("crash:game.tick@n=3"), 1)
    died = []
    p.exit_hook = lambda: died.append(True)
    p.crash("game.tick")
    p.crash("game.tick")
    assert not died
    p.crash("game.tick")
    assert died


# =======================================================================
# wire faults at the PacketConnection seam
# =======================================================================
class _StubTransport:
    def __init__(self):
        self.aborted = False

    def abort(self):
        self.aborted = True


class _StubWriter:
    def __init__(self):
        self.chunks: list[bytes] = []
        self.transport = _StubTransport()

    def write(self, data: bytes) -> None:
        self.chunks.append(bytes(data))

    def close(self):
        pass


def _conn(edge="game->dispatcher"):
    w = _StubWriter()
    return PacketConnection(None, w, edge=edge), w


def _pkt(mt=proto.MT_CALL_ENTITY_METHOD):
    p = new_packet(mt)
    p.append_var_str("payload")
    return p


def test_wire_drop_dup_truncate_disconnect():
    # p=1 rules fire on every trial: each kind observable via the writer
    _install("drop:game->dispatcher:1.0")
    c, w = _conn()
    c.send(_pkt())
    assert w.chunks == []                        # dropped

    _install("dup:game->dispatcher:1.0")
    c, w = _conn()
    c.send(_pkt())
    assert len(w.chunks) == 2 and w.chunks[0] == w.chunks[1]

    _install("truncate:game->dispatcher:1.0")
    c, w = _conn()
    c.send(_pkt())
    (data,) = w.chunks
    body = _pkt()
    import struct
    full = struct.pack("<I", len(body.buf)) + bytes(body.buf)
    assert len(data) < len(full)                 # cut short...
    (size,) = struct.unpack_from("<I", data)
    assert size == len(data) - 4                 # ...but framed

    _install("disconnect:game->dispatcher:1.0")
    c, w = _conn()
    c.send(_pkt())
    assert w.transport.aborted and c.closed

    # wrong edge: untouched
    _install("drop:gate->dispatcher:1.0")
    c, w = _conn(edge="game->dispatcher")
    c.send(_pkt())
    assert len(w.chunks) == 1

    # msgtype filter: only the named type is injected
    _install("drop:game->dispatcher:mt=9999:1.0")
    c, w = _conn()
    c.send(_pkt())
    assert len(w.chunks) == 1


def test_injected_faults_are_counted_and_logged():
    from goworld_tpu.utils import metrics

    plane = _install("drop:game->dispatcher:1.0")
    c, _w = _conn()
    for _ in range(5):
        c.send(_pkt())
    assert plane.injected_total == 5
    assert plane.log_lines() == [
        "drop:game->dispatcher:1.0 -> 0,1,2,3,4"
    ]
    snap = faults.snapshot()
    assert snap["active"] and snap["rules"][0]["trials"] == 5
    assert "faults_injected_total" in metrics.REGISTRY.expose_text()


# =======================================================================
# bounded reconnect pend queue (drop-oldest + counter)
# =======================================================================
def test_cluster_pend_queue_drop_oldest():
    from goworld_tpu.net.cluster import DispatcherConn

    conn = DispatcherConn(
        0, ("127.0.0.1", 1), lambda *a: None, None,
        pend_max_packets=4, pend_max_bytes=1 << 20,
    )
    drop0 = conn._m_pend_dropped.value  # registry counters are global
    for i in range(10):   # disconnected: everything pends
        p = new_packet(proto.MT_CALL_ENTITY_METHOD)
        p.append_u32(i)
        conn.send(p)
    assert len(conn._pending) == 4
    # drop-OLDEST: the survivors are the newest four (ids 6..9)
    kept = [Packet(raw) for raw in conn._pending]
    ids_ = [(p.read_u16(), p.read_u32())[1] for p in kept]
    assert ids_ == [6, 7, 8, 9]
    assert conn._m_pend_dropped.value == drop0 + 6

    # byte budget binds independently of the packet budget
    conn2 = DispatcherConn(
        1, ("127.0.0.1", 1), lambda *a: None, None,
        pend_max_packets=1000, pend_max_bytes=100,
    )
    for _ in range(10):
        p = new_packet(proto.MT_CALL_ENTITY_METHOD)
        p.append_bytes(b"x" * 30)
        conn2.send(p)
    assert conn2._pending_bytes <= 100
    assert conn2._m_pend_dropped.value > 0


# =======================================================================
# boot requests during a zero-game outage (the mid-restart window)
# =======================================================================
def test_boot_request_queued_during_game_outage():
    """A client connecting while NO game is live (between a crash and
    its supervised restart) must have its boot request parked and
    flushed to the next game that handshakes — not silently dropped
    (which left the client hanging forever)."""
    from goworld_tpu.net.dispatcher import DispatcherService

    svc = DispatcherService(1, "127.0.0.1", 0,
                            desired_games=1, desired_gates=0)

    class _Conn:
        edge = ""

        def __init__(self):
            self.sent = []

        def send(self, p, release=True):
            mt = int.from_bytes(bytes(p.buf[:2]), "little") & 0x7FFF
            self.sent.append(mt)

    boot = proto.pack_notify_client_connected("b" * 16, "c" * 16, 1)
    pkt = Packet(bytes(boot.buf))
    pkt.rpos = 2
    svc._h_client_connected(None, None,
                            proto.MT_NOTIFY_CLIENT_CONNECTED, pkt)
    assert len(svc._boot_pending) == 1          # parked, not dropped

    conn = _Conn()
    hs = proto.pack_set_game_id(1, False, True, False, [])
    hp = Packet(bytes(hs.buf))
    hp.rpos = 2
    svc._handle_set_game_id(conn, hp)
    assert not svc._boot_pending                # flushed on handshake
    assert proto.MT_NOTIFY_CLIENT_CONNECTED in conn.sent
    assert svc.entities["b" * 16].game_id == 1  # routed to the new game


# =======================================================================
# op faults + retry wrappers (kvdb / storage)
# =======================================================================
def test_kvdb_op_fault_exhausts_bounded_retries():
    import queue

    from goworld_tpu.kvdb import KVDB, MemoryKVDB
    from goworld_tpu.utils.asyncwork import AsyncWorkers

    _install("err:kvdb.get:1.0")      # every attempt fails
    posted = queue.Queue()
    kv = KVDB(MemoryKVDB(), AsyncWorkers(posted.put))
    err0 = kv._m_err.value            # registry counters are global
    out = []
    kv.get("k", lambda v, e: out.append((v, e)))
    deadline = time.time() + 10
    while not out and time.time() < deadline:
        try:
            posted.get(timeout=0.1)()
        except queue.Empty:
            pass
    assert out, "kvdb get callback never fired"
    v, err = out[0]
    assert isinstance(err, faults.InjectedFaultError)   # bounded: failed
    assert kv._m_err.value == err0 + 1


def test_kvdb_recovers_when_fault_is_transient():
    import queue

    from goworld_tpu.kvdb import KVDB, MemoryKVDB
    from goworld_tpu.utils.asyncwork import AsyncWorkers

    # fires on trial 0 only -> first attempt raises, retry succeeds
    plane = _install("err:kvdb.get:0.5")
    plane.rules[0].at_n = 1    # deterministic: exactly the first trial
    posted = queue.Queue()
    kv = KVDB(MemoryKVDB(), AsyncWorkers(posted.put))
    retry0 = kv._m_retry["get"].value  # registry counters are global
    kv.backend.put("k", "v")
    out = []
    kv.get("k", lambda v, e: out.append((v, e)))
    deadline = time.time() + 10
    while not out and time.time() < deadline:
        try:
            posted.get(timeout=0.1)()
        except queue.Empty:
            pass
    assert out == [("v", None)]
    assert kv._m_retry["get"].value == retry0 + 1


def test_storage_save_retries_through_injected_faults():
    import queue

    from goworld_tpu.storage import Storage, MemoryStorage

    plane = _install("err:storage.save:0.9")
    plane.rules[0].prob = 0.0          # arm per-trial below
    plane.rules[0].at_n = 1            # first attempt fails, then clean
    post_q = queue.Queue()
    st = Storage(MemoryStorage(), post_q.put)
    done = []
    st.save("T", "e" * 16, {"x": 1}, cb=lambda: done.append(True))
    deadline = time.time() + 15
    while not done and time.time() < deadline:
        try:
            post_q.get(timeout=0.1)()
        except queue.Empty:
            pass
    assert done, "save never completed"
    assert st.backend.read("T", "e" * 16) == {"x": 1}
    assert st._m_retry.value >= 1
    st.shutdown()


# =======================================================================
# live cluster: seeded chaos smoke (the acceptance scenario)
# =======================================================================
N_DEPOSITS = 30
CHAOS_SEED = 1234


def _scrape_faults(hport: int) -> dict:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{hport}/faults", timeout=5
    ) as r:
        return json.loads(r.read())


async def _session(gport: int, actions):
    """One bot session; ``actions(bot)`` is an async callable."""
    from goworld_tpu.net.botclient import BotClient

    bot = BotClient("127.0.0.1", gport)
    await bot.connect()
    recv = asyncio.ensure_future(bot._recv_loop())
    try:
        await asyncio.wait_for(bot.player_ready.wait(), 90)
        for _ in range(200):
            if bot.player.attrs.get("status") == "online":
                break
            await asyncio.sleep(0.05)
        assert bot.player.attrs.get("status") == "online"
        return await actions(bot)
    finally:
        recv.cancel()
        await bot.conn.close()


@pytest.mark.chaos
def test_chaos_smoke_kill_recovery_and_seeded_replay(tmp_path,
                                                     monkeypatch):
    soak = _chaos_soak_mod()
    dst, gport, hport = soak.build_server_dir(
        str(tmp_path / "chaos_game"))
    chaos_spec = soak.spec_for()
    monkeypatch.setenv("GOWORLD_FAULTS", chaos_spec)
    monkeypatch.setenv("GOWORLD_FAULTS_SEED", str(CHAOS_SEED))
    stop = threading.Event()
    sup = None
    try:
        assert cli.cmd_start(dst) == 0, _logs(dst)
        # the spawned processes inherited the schedule; respawns must
        # not (one deterministic kill, then a clean recovery)
        monkeypatch.delenv("GOWORLD_FAULTS")
        monkeypatch.delenv("GOWORLD_FAULTS_SEED")
        game_pid = cli._read_pid(dst, "game", 1)

        # -- deposit phase: RPCs through the faulted gate->dispatcher
        # edge; drops are allowed (that is the fault), but SOME deposits
        # must land and the audit attr reports the applied total
        async def deposit(bot):
            for _ in range(N_DEPOSITS):
                bot.call_server("Deposit_Client", 1)
                await asyncio.sleep(0.02)
            deadline = time.time() + 20
            while time.time() < deadline:
                a = bot.player.attrs.get("audit")
                if a is not None:
                    await asyncio.sleep(1.0)  # let stragglers apply
                    return bot.player.attrs.get("audit")
                await asyncio.sleep(0.1)
            return None

        gold = asyncio.run(asyncio.wait_for(_session(gport, deposit),
                                            120))
        t_gold = time.time()
        assert gold and 0 < gold <= 2 * N_DEPOSITS, \
            f"no deposit survived the faults (audit={gold})"

        # wait until ALL 30 RPCs have passed the gate's decision point
        # (poll the trial counter instead of sleeping a fixed margin —
        # the client->gate stream is ordered, so trials only grow to
        # exactly N_DEPOSITS), then check the deterministic fault log
        # equals the pure function of (seed, spec, trials) — which is
        # exactly what a re-run with the same seed replays
        deadline = time.time() + 30
        live = _scrape_faults(hport)
        while time.time() < deadline and \
                live["rules"][0]["trials"] < N_DEPOSITS:
            time.sleep(0.2)
            live = _scrape_faults(hport)
        assert live["rules"][0]["trials"] == N_DEPOSITS, live["rules"]
        assert live["active"] and live["seed"] == CHAOS_SEED
        expected = faults.FaultPlane(
            faults.parse_schedule(chaos_spec), CHAOS_SEED)
        for _ in range(N_DEPOSITS):
            expected.wire_fault(
                "gate->dispatcher",
                proto.MT_CALL_ENTITY_METHOD_FROM_CLIENT)
        assert live["log"] == expected.log_lines(), \
            "live fault log diverged from the seeded replay"
        assert live["injected_total"] > 0

        # a checkpoint newer than the last applied deposit must exist
        # before the kill (1 s cadence; the kill tick is ~15 s in), so
        # the restore carries the audited vault value exactly
        ckpt = os.path.join(dst, "game1_checkpoint.dat")
        deadline = time.time() + 30
        while time.time() < deadline and (
            not os.path.exists(ckpt)
            or os.path.getmtime(ckpt) < t_gold + 0.5
        ):
            time.sleep(0.2)
        assert os.path.exists(ckpt) \
            and os.path.getmtime(ckpt) >= t_gold + 0.5, \
            "no post-deposit crash-recovery checkpoint\n" + _logs(dst)

        # -- the deterministic kill: crash:game.tick@n fires, the game
        # process dies hard (exit code 86, no freeze, no goodbye)
        deadline = time.time() + 60
        while time.time() < deadline and cli._alive(game_pid):
            time.sleep(0.2)
        assert not cli._alive(game_pid), "kill rule never fired"

        # -- supervised recovery: `supervise` notices the crash
        # signature (dead pid, pidfile present) and restarts the game
        # with -restore from the checkpoint, with backoff bookkeeping
        sup = threading.Thread(
            target=cli.cmd_supervise,
            args=(dst,), kwargs=dict(interval=0.5, stop=stop),
            daemon=True,
        )
        sup.start()
        deadline = time.time() + 180
        new_pid = None
        while time.time() < deadline:
            new_pid = cli._read_pid(dst, "game", 1)
            if new_pid != game_pid and cli._alive(new_pid):
                break
            time.sleep(0.3)
        assert new_pid != game_pid and cli._alive(new_pid), \
            "supervisor never restarted the game\n" + _logs(dst)

        # -- convergence: census re-handshake done (a FRESH client boots
        # and is routed to the restarted game) and ZERO persistent-
        # entity loss (the Vault restored with its exact audited value)
        async def audit(bot):
            bot.call_server("Audit_Client")
            deadline = time.time() + 30
            while time.time() < deadline:
                a = bot.player.attrs.get("audit")
                if a is not None:
                    return a
                await asyncio.sleep(0.1)
            return None

        seen = asyncio.run(asyncio.wait_for(_session(gport, audit), 240))
        assert seen == gold, (
            f"persistent entity lost or stale: audited {seen}, "
            f"expected {gold}\n" + _logs(dst)
        )
        # the vault also reached durable storage (explicit save path)
        vault_file = os.path.join(
            dst, "entity_storage", "Vault", "Vault00000000001.mp")
        assert os.path.exists(vault_file)
    finally:
        stop.set()
        if sup is not None:
            sup.join(timeout=60)
        cli.cmd_stop(dst)


def _logs(server_dir: str) -> str:
    out = []
    rd = os.path.join(server_dir, "run")
    if os.path.isdir(rd):
        for name in sorted(os.listdir(rd)):
            if name.endswith(".log"):
                with open(os.path.join(rd, name), errors="replace") as f:
                    out.append(f"==== {name} ====\n" + f.read()[-3000:])
    return "\n".join(out)


# =======================================================================
# full soak: double run, byte-identical fault logs (slow tier)
# =======================================================================
@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_soak_same_seed_replays_identical_log(tmp_path):
    """Run tools/chaos_soak.py twice with the same seed against two
    fresh clusters and require byte-identical fault logs plus converged
    recovery in both runs."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    outs = []
    for run in (1, 2):
        out = str(tmp_path / f"soak{run}.json")
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "chaos_soak.py"),
             "--dir", str(tmp_path / f"cluster{run}"),
             "--seed", "77", "--deposits", "25", "--out", out],
            env=env, capture_output=True, text=True, timeout=600,
        )
        assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
        with open(out) as f:
            outs.append(json.load(f))
    assert outs[0]["converged"] and outs[1]["converged"]
    assert outs[0]["fault_log"] == outs[1]["fault_log"], \
        "same seed, different fault sequence"
    assert outs[0]["injected_total"] > 0
