"""Gate transport parity (VERDICT #5): compression + TLS on the client
edge, mirroring the reference CI which runs with compression and
encryption ON (goworld_actions.ini; ClientProxy.go:38-53). The KCP
deviation is documented in net/transport.py."""

import asyncio
import threading
import time

import pytest

from goworld_tpu.core.state import WorldConfig
from goworld_tpu.entity.entity import Entity
from goworld_tpu.entity.manager import World
from goworld_tpu.entity.space import Space
from goworld_tpu.net.botclient import BotClient
from goworld_tpu.net.game import GameServer
from goworld_tpu.net.packet import PacketConnection, new_packet
from goworld_tpu.net.standalone import ClusterHarness
from goworld_tpu.net import snappy as _snappy
from goworld_tpu.ops.aoi import GridSpec

# snappy is the DEFAULT codec for compress=True, so every compressed
# test needs the native core; skip (not error) where it can't build,
# like tests/test_snappy.py does
requires_snappy = pytest.mark.skipif(
    not _snappy.available(), reason="native snappy core failed to build")
_snappy_param = pytest.param("snappy", marks=requires_snappy)


# =======================================================================
# packet-level compression
# =======================================================================
@pytest.mark.parametrize("codec", [_snappy_param, "zlib"])
def test_compressed_packet_roundtrip(codec):
    async def main():
        got = []

        async def handle(reader, writer):
            conn = PacketConnection(reader, writer, compress=True,
                                    compress_codec=codec)
            mt, p = await conn.recv()
            got.append((mt, p.read_var_str(), p.read_data()))
            reply = new_packet(77)
            reply.append_var_str("pong")
            conn.send(reply)
            await conn.drain()
            await conn.close()  # 3.12 Server.wait_closed waits on this

        server = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        conn = PacketConnection(reader, writer, compress=True,
                                compress_codec=codec)
        p = new_packet(42)
        p.append_var_str("hello" * 200)  # compressible payload
        p.append_data({"k": [1, 2, 3]})
        conn.send(p)
        await conn.drain()
        mt, reply = await conn.recv()
        assert mt == 77 and reply.read_var_str() == "pong"
        await conn.close()
        server.close()
        await server.wait_closed()
        assert got == [(42, "hello" * 200, {"k": [1, 2, 3]})]

    asyncio.run(main())


@requires_snappy
def test_compression_mismatch_detected():
    """An uncompressed sender against a compressed receiver must fail
    loudly (bad zlib header), not feed garbage into the packet codec."""
    async def main():
        errs = []

        async def handle(reader, writer):
            conn = PacketConnection(reader, writer, compress=True)
            try:
                await conn.recv()
            except ConnectionError as exc:
                errs.append(str(exc))
            finally:
                await conn.close()

        server = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        plain = PacketConnection(reader, writer)  # no compression
        p = new_packet(42)
        p.append_var_str("hello")
        plain.send(p)
        await plain.drain()
        for _ in range(100):
            if errs:
                break
            await asyncio.sleep(0.02)
        await plain.close()
        server.close()
        await server.wait_closed()
        assert errs and "compressed" in errs[0]

    asyncio.run(main())


class _CaptureWriter:
    def __init__(self):
        self.data = bytearray()

    def write(self, b):
        self.data += b

    async def drain(self):
        pass

    def close(self):
        pass


@requires_snappy
def test_stream_compression_beats_plain_on_hot_path():
    """Per-connection streaming compression must SHRINK a realistic
    client-edge stream (repeated small sync records); per-packet zlib
    would inflate it (fresh header per packet)."""
    import struct

    plain_w, comp_w = _CaptureWriter(), _CaptureWriter()
    plain = PacketConnection(None, plain_w)
    comp = PacketConnection(None, comp_w, compress=True)
    for i in range(200):
        for conn in (plain, comp):
            p = new_packet(3)  # sync-record-shaped payload
            p.append_bytes(b"E" * 16 + struct.pack("<4f", 1.0 * i, 0, 2.0,
                                                   0.5))
            conn.send(p)
    assert len(comp_w.data) < len(plain_w.data), (
        f"compression inflated the stream: {len(comp_w.data)} vs "
        f"{len(plain_w.data)} plain"
    )


@pytest.mark.parametrize("codec", [_snappy_param, "zlib"])
def test_decompression_bomb_rejected(codec):
    """A crafted high-ratio stream must be rejected by the output cap,
    not materialized (gate OOM)."""
    import struct

    async def main():
        if codec == "zlib":
            import zlib as _z

            comp = _z.compressobj(1)
            payload = comp.compress(b"\0" * (64 * 1024 * 1024))
            payload += comp.flush(_z.Z_SYNC_FLUSH)
            match = "too large"
        else:
            from goworld_tpu.net import snappy as _snappy

            payload = _snappy.StreamCompressor().compress(
                b"\0" * (64 * 1024 * 1024))
            match = "size bound"
        assert len(payload) < 32 * 1024 * 1024  # passes the wire check
        reader = asyncio.StreamReader()
        reader.feed_data(struct.pack("<I", len(payload)) + payload)
        reader.feed_eof()
        conn = PacketConnection(reader, _CaptureWriter(), compress=True,
                                compress_codec=codec)
        with pytest.raises(ConnectionError, match=match):
            await conn.recv()

    asyncio.run(main())


# =======================================================================
# full cluster over compressed + TLS transport
# =======================================================================
class Account(Entity):
    ATTRS = {"status": "client"}

    def OnClientConnected(self):
        self.attrs["status"] = "online"

    def Login_Client(self, name):
        avatar = self.world.create_entity(
            "Avatar", space=self.world._test_space, pos=(50.0, 0.0, 50.0)
        )
        avatar.attrs["name"] = name
        self.give_client_to(avatar)
        self.destroy()


class Avatar(Entity):
    ATTRS = {"name": "allclients", "level": "client"}

    def OnClientConnected(self):
        self.attrs["level"] = 1


class Arena(Space):
    pass


def _cluster(**harness_kwargs):
    """Shared 1-dispatcher/1-gate/1-game bring-up for the transport
    variants; yields (harness, world, game_server) and tears down."""
    harness = ClusterHarness(
        n_dispatchers=1, n_gates=1, desired_games=1,
        position_sync_interval_ms=20, **harness_kwargs,
    )
    harness.start()
    cfg = WorldConfig(
        capacity=128,
        grid=GridSpec(radius=50.0, extent_x=200.0, extent_z=200.0),
        input_cap=128,
    )
    world = World(cfg, n_spaces=1)
    world.register_entity("Account", Account)
    world.register_entity("Avatar", Avatar)
    world.register_space("Arena", Arena)
    world.create_nil_space()
    world._test_space = world.create_space("Arena")
    gs = GameServer(1, world, list(harness.dispatcher_addrs),
                    boot_entity="Account")
    gs.start_network()
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            gs.pump()
            gs.tick()
            time.sleep(0.01)

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    assert gs.ready_event.wait(60), "deployment never became ready"
    yield harness, world, gs
    stop.set()
    t.join(timeout=5)
    gs.stop()
    harness.stop()


@pytest.fixture()
def secure_cluster(tmp_path):
    yield from _cluster(compress=True, tls_dir=str(tmp_path))


async def _login_and_walk(bot: BotClient, world) -> list[bool]:
    """Returns, per live server-side Avatar, whether it owned a client
    WHILE the bot was still connected: the script closes the connection
    when it ends, and the game then detaches the client."""
    await bot.connect()
    recv = asyncio.ensure_future(bot._recv_loop())
    try:
        await asyncio.wait_for(bot.player_ready.wait(), 10)
        assert bot.player.type_name == "Account"
        bot.call_server("Login_Client", "alice")
        # wait for the avatar handoff
        for _ in range(200):
            if bot.player is not None and bot.player.type_name == "Avatar":
                break
            await asyncio.sleep(0.05)
        assert bot.player.type_name == "Avatar"
        # position syncs flow over the compressed+TLS link
        bot.send_position(60.0, 0.0, 60.0, 1.0)
        for _ in range(200):
            if bot.player.attrs.get("name") == "alice":
                break
            await asyncio.sleep(0.05)
        assert bot.player.attrs.get("name") == "alice"
        return [e.client is not None for e in world.entities.values()
                if e.type_name == "Avatar" and not e.destroyed]
    finally:
        recv.cancel()
        await bot.conn.close()


@requires_snappy
def test_bot_over_compressed_tls(secure_cluster):
    harness, world, gs = secure_cluster
    host, port = harness.gate_addrs[0]
    bot = BotClient(host, port, strict=True, compress=True, tls=True)
    owned = harness.submit(_login_and_walk(bot, world)).result(timeout=40)
    assert not bot.errors, bot.errors
    assert owned == [True]


def test_plaintext_bot_rejected_by_tls_gate(secure_cluster):
    """A client skipping TLS can't talk to an encrypted gate."""
    harness, _, _ = secure_cluster
    host, port = harness.gate_addrs[0]
    bot = BotClient(host, port, compress=True, tls=False)

    async def attempt():
        await bot.connect()
        try:
            await asyncio.wait_for(bot._recv_loop(), 3)
        except (asyncio.TimeoutError, ConnectionError, EOFError):
            return False
        return bot.player is not None

    ok = harness.submit(attempt()).result(timeout=20)
    assert not ok, "plaintext client slipped through a TLS gate"


# =======================================================================
# KCP (reliable-UDP) client edge — reference GateService.go:129-161
# =======================================================================
@pytest.fixture()
def kcp_cluster():
    yield from _cluster(with_kcp=True)


def test_bot_over_kcp(kcp_cluster):
    """Full client flow (boot entity, RPC login, avatar handoff, strict
    attr mirror, position sync) over the reliable-UDP listener."""
    harness, world, gs = kcp_cluster
    host, port = harness.gate_kcp_addrs[0]
    bot = BotClient(host, port, strict=True, kcp=True)
    owned = harness.submit(_login_and_walk(bot, world)).result(timeout=40)
    assert not bot.errors, bot.errors
    assert owned == [True]


def test_bot_swarm_over_kcp(kcp_cluster):
    """A strict bot swarm over the reliable-UDP edge (the reference CI
    drives test_client -kcp against its gates)."""
    from goworld_tpu.net.botclient import run_swarm

    harness, world, gs = kcp_cluster
    host, port = harness.gate_kcp_addrs[0]
    n = 8
    bots = harness.submit(
        run_swarm(host, port, n, 8.0, strict=True, kcp=True)
    ).result(timeout=90)
    errs = [e for b in bots for e in b.errors]
    assert not errs, errs[:5]
    # every bot's boot entity arrived over reliable UDP (this fixture's
    # Account stays in the nil space, so no AOI syncs are expected; the
    # 8 s window absorbs full-suite machine load)
    assert all(b.player is not None for b in bots)
    accounts = [e for e in world.entities.values()
                if e.type_name == "Account" and not e.destroyed]
    assert len(accounts) == n


@pytest.fixture()
def kcp_compressed_cluster():
    yield from _cluster(with_kcp=True, compress=True)


@requires_snappy
def test_bot_over_kcp_with_snappy(kcp_compressed_cluster):
    """Compression composes with the reliable-UDP edge: the gate's KCP
    sessions reuse the TCP client handler, so the snappy stream codec
    must run unchanged over (reader, writer) adapters backed by KCP."""
    harness, world, gs = kcp_compressed_cluster
    host, port = harness.gate_kcp_addrs[0]
    bot = BotClient(host, port, strict=True, kcp=True, compress=True)
    owned = harness.submit(_login_and_walk(bot, world)).result(timeout=40)
    assert not bot.errors, bot.errors
    assert owned == [True]
