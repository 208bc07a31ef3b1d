"""End-to-end cluster integration: dispatcher + gate + game + bot clients
over real localhost sockets.

Mirrors the reference's de-facto distributed test (``test_game.yml``: start
the cluster, drive it with ``test_client -N ... -strict``) at unit-test
scale: bots log in, get a boot Account, RPC to create an Avatar in a space,
random-walk, and strict-mode mirrors must stay consistent.
"""

import threading
import time

import pytest

from goworld_tpu.core.state import WorldConfig
from goworld_tpu.entity.entity import Entity
from goworld_tpu.entity.manager import World
from goworld_tpu.entity.space import Space
from goworld_tpu.net.botclient import BotClient
from goworld_tpu.net.game import GameServer
from goworld_tpu.net.standalone import ClusterHarness
from goworld_tpu.ops.aoi import GridSpec


class Account(Entity):
    ATTRS = {"status": "client"}

    def OnClientConnected(self):
        self.attrs["status"] = "online"

    def Login_Client(self, name):
        avatar = self.world.create_entity(
            "Avatar", space=self.world._test_space,
            pos=(50.0, 0.0, 50.0),
        )
        avatar.attrs["name"] = name
        self.give_client_to(avatar)
        self.destroy()


class Avatar(Entity):
    ATTRS = {"name": "allclients", "level": "client", "hp": "allclients"}

    def OnClientConnected(self):
        self.attrs["level"] = 1

    def Say_Client(self, text):
        self.call_all_clients("OnSay", self.id, text)


class Arena(Space):
    pass


@pytest.fixture()
def cluster():
    harness = ClusterHarness(
        n_dispatchers=2, n_gates=1, desired_games=1,
        position_sync_interval_ms=20,
    )
    harness.start()

    cfg = WorldConfig(
        capacity=256,
        grid=GridSpec(radius=50.0, extent_x=200.0, extent_z=200.0),
        input_cap=256,
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
    assert gs.ready_event.wait(20), "deployment never became ready"
    yield harness, world, gs
    stop.set()
    t.join(timeout=5)
    gs.stop()
    harness.stop()


def _run_bot(harness, bot: BotClient, duration: float):
    return harness.submit(bot.run(duration))


def test_login_creates_boot_entity_and_avatar(cluster):
    harness, world, gs = cluster
    host, port = harness.gate_addrs[0]
    bot = BotClient(host, port, strict=True)

    # the script closes the bot's connection when it ends, and the game
    # then detaches the client (test_client_disconnect_detaches_entity):
    # look at the server-side ownership while the bot is still connected
    owned: list[bool] = []

    def look() -> None:
        owned.extend(e.client is not None
                     for e in world.entities.values()
                     if e.type_name == "Avatar" and not e.destroyed)

    done = harness.submit(_bot_login_script(bot, while_connected=look))
    done.result(timeout=30)

    assert not bot.errors, bot.errors
    # bot saw its Account first, then the Avatar after Login
    assert bot.player is not None
    assert bot.player.type_name == "Avatar"
    assert bot.player.attrs.get("name") == "bob"
    # the server-side avatar exists and owns the client
    assert owned == [True]


async def _bot_login_script(bot: BotClient, while_connected=None):
    import asyncio

    await bot.connect()
    recv = asyncio.ensure_future(bot._recv_loop())
    try:
        await asyncio.wait_for(bot.player_ready.wait(), 10)
        assert bot.player.type_name == "Account"
        # status attr set in OnClientConnected must reach the mirror
        for _ in range(100):
            if bot.player.attrs.get("status") == "online":
                break
            await asyncio.sleep(0.05)
        assert bot.player.attrs.get("status") == "online"
        bot.call_server("Login_Client", "bob")
        # wait for the Avatar handoff
        for _ in range(100):
            if bot.player is not None and bot.player.type_name == "Avatar":
                break
            await asyncio.sleep(0.05)
        assert bot.player is not None
        assert bot.player.type_name == "Avatar"
        for _ in range(100):
            if bot.player.attrs.get("name") == "bob":
                break
            await asyncio.sleep(0.05)
        if while_connected is not None:
            while_connected()
    finally:
        recv.cancel()
        await bot.conn.close()


def test_two_bots_see_each_other_and_sync(cluster):
    harness, world, gs = cluster
    host, port = harness.gate_addrs[0]
    b1 = BotClient(host, port, bot_id=1, strict=True)
    b2 = BotClient(host, port, bot_id=2, strict=True)

    f1 = harness.submit(_bot_play_script(b1, "alice"))
    f2 = harness.submit(_bot_play_script(b2, "bob"))
    f1.result(timeout=40)
    f2.result(timeout=40)

    assert not b1.errors, b1.errors
    assert not b2.errors, b2.errors
    # both avatars spawn at the same point -> each mirror contains the
    # other avatar (AOI enter -> create_entity on client)
    names1 = {e.attrs.get("name") for e in b1.entities.values()
              if e.type_name == "Avatar"}
    assert "bob" in names1, f"alice's mirror: {names1}"
    names2 = {e.attrs.get("name") for e in b2.entities.values()
              if e.type_name == "Avatar"}
    assert "alice" in names2
    # position syncs flowed (b2 moved -> b1 receives records)
    assert b1.sync_count > 0 or b2.sync_count > 0
    # RPC broadcast: alice Say -> both clients got OnSay
    assert any(m == "OnSay" for _, m, _ in b1.rpc_log)
    assert any(m == "OnSay" for _, m, _ in b2.rpc_log)


async def _bot_play_script(bot: BotClient, name: str):
    import asyncio

    await bot.connect()
    recv = asyncio.ensure_future(bot._recv_loop())
    try:
        await asyncio.wait_for(bot.player_ready.wait(), 10)
        bot.call_server("Login_Client", name)
        for _ in range(100):
            if bot.player is not None and bot.player.type_name == "Avatar":
                break
            await asyncio.sleep(0.05)
        assert bot.player is not None and bot.player.type_name == "Avatar"
        # move around for a while
        for i in range(20):
            x, y, z = bot.player.pos
            bot.send_position(x + 1.0, y, z + 1.0, 0.1)
            bot.player.pos = (x + 1.0, y, z + 1.0)
            await asyncio.sleep(0.05)
        if name == "alice":
            bot.call_server("Say_Client", "hello world")
        await asyncio.sleep(1.0)
    finally:
        recv.cancel()
        await bot.conn.close()


def test_client_disconnect_detaches_entity(cluster):
    harness, world, gs = cluster
    host, port = harness.gate_addrs[0]
    bot = BotClient(host, port, strict=True)
    harness.submit(_bot_login_script(bot)).result(timeout=30)
    # bot's connection is closed by the script; the gate notifies the
    # dispatcher which notifies the game
    deadline = time.time() + 10
    while time.time() < deadline:
        avatars = [e for e in world.entities.values()
                   if e.type_name == "Avatar" and not e.destroyed]
        if avatars and avatars[0].client is None:
            break
        time.sleep(0.1)
    avatars = [e for e in world.entities.values()
               if e.type_name == "Avatar" and not e.destroyed]
    assert avatars and avatars[0].client is None


def test_create_space_anywhere_and_kvreg_traverse(cluster):
    """CreateSpaceAnywhere rides the anywhere placement path (reference
    goworld.go) and kvreg.TraverseByPrefix walks the local mirror."""
    harness, world, gs = cluster
    world.register_space("Lobby", Space, use_aoi=False)
    n_before = sum(1 for s in world.spaces.values()
                   if s.type_name == "Lobby")
    gs.create_entity_anywhere("Lobby", None)
    deadline = time.time() + 10
    while time.time() < deadline:
        lobbies = [s for s in world.spaces.values()
                   if s.type_name == "Lobby"]
        if len(lobbies) > n_before:
            break
        time.sleep(0.05)
    assert len(lobbies) == n_before + 1, "space never placed anywhere"

    gs.kvreg_register("Zone/alpha", "1")
    gs.kvreg_register("Zone/beta", "2")
    gs.kvreg_register("Other/x", "9")
    deadline = time.time() + 10
    while time.time() < deadline and len(
        [k for k in gs.kvreg if k.startswith("Zone/")]
    ) < 2:
        time.sleep(0.05)
    seen = []
    gs.kvreg_traverse("Zone/", lambda k, v: seen.append((k, v)))
    assert seen == [("Zone/alpha", "1"), ("Zone/beta", "2")]


def test_nosync_bot_mirrors_without_sending(cluster):
    """-nosync parity: the bot logs in and mirrors entities but never
    sends a position sync upstream (reference test_client -nosync)."""
    import asyncio

    harness, world, gs = cluster
    host, port = harness.gate_addrs[0]
    from goworld_tpu.net.botclient import BotClient

    bot = BotClient(host, port, strict=True, nosync=True)

    sent = []
    orig = bot.send_position
    bot.send_position = lambda *a: sent.append(a) or orig(*a)

    async def script():
        await bot.connect()
        recv = asyncio.ensure_future(bot._recv_loop())
        move = asyncio.ensure_future(bot._move_loop())
        try:
            await asyncio.wait_for(bot.player_ready.wait(), 15)
            await asyncio.sleep(1.0)   # move loop runs; must stay silent
        finally:
            move.cancel()
            recv.cancel()
            await bot.conn.close()

    harness.submit(script()).result(timeout=40)
    assert bot.player is not None
    assert not sent, "nosync bot sent position syncs"
