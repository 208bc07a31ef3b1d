"""Cluster-level 3-phase entity migration across game processes.

The hardest distributed protocol in the system (reference
``Entity.go:956-1115`` EnterSpace -> OnMigrateOut -> real migrate, and
``DispatcherService.go:834-891`` query-space-gameid -> block+queue ->
real-migrate -> unblock): an avatar on game1 enters a space hosted by
game2 while client RPCs are in flight. The dispatcher must queue every
packet aimed at the migrating entity and flush it to the new game, so no
RPC is ever lost; attrs, timers and the client binding must survive the
hop. Also covers the cancel path (``Entity.go:1014-1023`` cancelEnterSpace
/ MT_CANCEL_MIGRATE): an entity destroyed mid-protocol must not migrate,
and the dispatcher's block must be lifted.
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

    def Login_Client(self, name):
        avatar = self.world.create_entity(
            "Avatar", space=self.world._test_space, pos=(50.0, 0.0, 50.0),
        )
        avatar.attrs["name"] = name
        self.give_client_to(avatar)
        self.destroy()


class Avatar(Entity):
    ATTRS = {
        "name": "allclients",
        "pings": "client",
        "heartbeats": "client",
    }

    def OnClientConnected(self):
        if self.attrs.get("pings") is None:
            self.attrs["pings"] = 0
        if self.attrs.get("heartbeats") is None:
            self.attrs["heartbeats"] = 0
        self.add_timer(0.05, "Heartbeat")

    def Heartbeat(self):
        self.attrs["heartbeats"] = (self.attrs.get("heartbeats") or 0) + 1

    def Ping_Client(self):
        self.attrs["pings"] = (self.attrs.get("pings") or 0) + 1

    def JumpTo_Client(self, space_id):
        self.enter_space(space_id, (10.0, 0.0, 10.0))

    def JumpAndDie_Client(self, space_id):
        # destroy immediately after requesting the cross-game jump: the
        # protocol must cancel (reference destroyEntity during EnterSpace)
        self.enter_space(space_id, (10.0, 0.0, 10.0))
        self.destroy()

    def OnMigrateIn(self):
        self.call_client("OnArrived", self.world.game_id)


class Arena(Space):
    pass


def _make_world(game_id: int) -> World:
    cfg = WorldConfig(
        capacity=128,
        grid=GridSpec(radius=50.0, extent_x=200.0, extent_z=200.0),
        input_cap=128,
    )
    world = World(cfg, n_spaces=1, game_id=game_id)
    world.register_entity("Account", Account)
    world.register_entity("Avatar", Avatar)
    world.register_space("Arena", Arena)
    world.create_nil_space()
    return world


@pytest.fixture()
def two_game_cluster():
    harness = ClusterHarness(
        n_dispatchers=2, n_gates=1, desired_games=2,
        position_sync_interval_ms=20,
    )
    harness.start()

    worlds, servers, threads = [], [], []
    stop = threading.Event()
    for gid in (1, 2):
        world = _make_world(gid)
        gs = GameServer(
            gid, world, list(harness.dispatcher_addrs),
            boot_entity="Account",
            # all boot entities land on game1; game2 only receives migrants
            ban_boot=(gid == 2),
        )

        def _mk_space(w=world):
            w._test_space = w.create_space("Arena")

        gs.on_deployment_ready = _mk_space
        gs.start_network()

        def loop(gs=gs):
            while not stop.is_set():
                gs.pump()
                gs.tick()
                time.sleep(0.01)

        t = threading.Thread(target=loop, daemon=True)
        t.start()
        worlds.append(world)
        servers.append(gs)
        threads.append(t)

    for gs in servers:
        assert gs.ready_event.wait(60), "deployment never became ready"
    # spaces are created on the logic threads after deployment-ready
    deadline = time.time() + 10
    while time.time() < deadline and not all(
        hasattr(w, "_test_space") for w in worlds
    ):
        time.sleep(0.05)
    assert all(hasattr(w, "_test_space") for w in worlds)

    yield harness, worlds, servers
    stop.set()
    for t in threads:
        t.join(timeout=5)
    for gs in servers:
        gs.stop()
    harness.stop()


def _avatar_in(world):
    avs = [e for e in world.entities.values()
           if e.type_name == "Avatar" and not e.destroyed]
    return avs[0] if avs else None


async def _login(bot: BotClient, name: str):
    import asyncio

    await bot.connect()
    recv = asyncio.ensure_future(bot._recv_loop())
    await asyncio.wait_for(bot.player_ready.wait(), 10)
    bot.call_server("Login_Client", name)
    for _ in range(200):
        if bot.player is not None and bot.player.type_name == "Avatar":
            return recv
        await asyncio.sleep(0.05)
    raise AssertionError("avatar never arrived")


async def _migrate_script(bot: BotClient, space_id: str, n_pings: int,
                          hold: threading.Event | None = None):
    """``hold``: the connection stays up until it is set, for a caller
    that looks at the server's side of it (the close unbinds it)."""
    import asyncio

    recv = await _login(bot, "bob")
    try:
        # pings in flight BEFORE, DURING and AFTER the jump: the
        # dispatcher's block+queue must deliver every single one
        for _ in range(n_pings // 2):
            bot.call_server("Ping_Client")
        bot.call_server("JumpTo_Client", space_id)
        for _ in range(n_pings - n_pings // 2):
            bot.call_server("Ping_Client")
            await asyncio.sleep(0.002)
        # wait for the migrate-in client RPC
        for _ in range(200):
            if any(m == "OnArrived" for _, m, _ in bot.rpc_log):
                break
            await asyncio.sleep(0.05)
        assert any(m == "OnArrived" for _, m, _ in bot.rpc_log), \
            "client never told about migrate-in"
        await asyncio.sleep(0.5)
        for _ in range(2000):
            if hold is None or hold.is_set():
                break
            await asyncio.sleep(0.01)
    finally:
        recv.cancel()
        await bot.conn.close()
    return True


def test_cross_game_enter_space_with_rpcs_in_flight(two_game_cluster):
    harness, (w1, w2), (gs1, gs2) = two_game_cluster
    host, port = harness.gate_addrs[0]
    bot = BotClient(host, port, strict=True)
    n_pings = 40

    hold = threading.Event()
    fut = harness.submit(
        _migrate_script(bot, w2._test_space.id, n_pings, hold)
    )
    # client binding survived (OnArrived already proves the downstream
    # path; this proves the server-side handle): looked at while the
    # connection is up, since the game unbinds a client that has gone
    # as soon as it hears of it
    bound = False
    deadline = time.time() + 60
    while time.time() < deadline and not bound:
        av = _avatar_in(w2)
        bound = av is not None and av.client is not None
        time.sleep(0.02)
    hold.set()
    fut.result(timeout=60)
    assert bound, "the migrated avatar never had its client"
    assert not bot.errors, bot.errors

    # the avatar left game1 entirely...
    assert _avatar_in(w1) is None
    # ...and lives on game2, in the target space
    deadline = time.time() + 10
    av = None
    while time.time() < deadline:
        av = _avatar_in(w2)
        if av is not None and (av.attrs.get("pings") or 0) >= n_pings:
            break
        time.sleep(0.05)
    assert av is not None, "avatar never arrived on game2"
    assert av.space is w2._test_space

    # attrs survived
    assert av.attrs.get("name") == "bob"
    # EVERY ping was delivered exactly once (block+queue, no loss): the
    # counter is an attr, so it also proves attr state moved intact
    assert av.attrs.get("pings") == n_pings
    # timers survived and keep firing on the new game
    assert av.timer_ids, "timers were not restored after migration"
    hb0 = av.attrs.get("heartbeats") or 0
    deadline = time.time() + 5
    while time.time() < deadline:
        if (av.attrs.get("heartbeats") or 0) > hb0:
            break
        time.sleep(0.05)
    assert (av.attrs.get("heartbeats") or 0) > hb0, \
        "migrated timer never fired on game2"


async def _cancel_script(bot: BotClient, space_id: str):
    import asyncio

    recv = await _login(bot, "bob")
    try:
        bot.call_server("JumpAndDie_Client", space_id)
        await asyncio.sleep(1.0)
    finally:
        recv.cancel()
        await bot.conn.close()
    return True


def test_migration_cancelled_when_entity_destroyed(two_game_cluster):
    """Entity destroyed right after requesting the jump: no copy may
    appear on game2, and the dispatcher's entity block must be lifted
    (MT_CANCEL_MIGRATE) so the route table doesn't wedge."""
    harness, (w1, w2), (gs1, gs2) = two_game_cluster

    # destroy() runs in the same handler as enter_space(), i.e. before the
    # query-space ack returns -> exercises the early-out. To exercise the
    # LATE cancel (destroyed between migrate-request and its ack, which
    # must emit MT_CANCEL_MIGRATE), flip a switch in the ack handler:
    orig = gs1._h_query_space_ack

    def late_destroy(pkt):
        orig(pkt)
        for pending in list(gs1._migrating_out.values()):
            pending[0].destroy()

    gs1._h_query_space_ack = late_destroy

    host, port = harness.gate_addrs[0]
    bot = BotClient(host, port, strict=True)
    fut = harness.submit(_cancel_script_late(bot, w2._test_space.id))
    fut.result(timeout=60)
    assert not bot.errors, bot.errors

    time.sleep(1.0)
    assert _avatar_in(w1) is None
    assert _avatar_in(w2) is None, "cancelled migration still migrated"
    # the dispatcher shard must have dropped/unblocked the route: a fresh
    # login + jump must work end to end (would hang if the table wedged)
    gs1._h_query_space_ack = orig
    bot2 = BotClient(host, port, bot_id=2, strict=True)
    fut = harness.submit(_migrate_script(bot2, w2._test_space.id, 4))
    fut.result(timeout=60)
    assert not bot2.errors, bot2.errors


async def _cancel_script_late(bot: BotClient, space_id: str):
    import asyncio

    recv = await _login(bot, "bob")
    try:
        bot.call_server("JumpTo_Client", space_id)  # destroy injected at ack
        await asyncio.sleep(1.0)
    finally:
        recv.cancel()
        await bot.conn.close()
    return True


def test_early_cancel_before_query_ack(two_game_cluster):
    """destroy() in the same handler as enter_space(): the pending
    migration must be dropped at the query-space ack."""
    harness, (w1, w2), (gs1, gs2) = two_game_cluster
    host, port = harness.gate_addrs[0]
    bot = BotClient(host, port, strict=True)
    fut = harness.submit(_cancel_script(bot, w2._test_space.id))
    fut.result(timeout=60)
    assert not bot.errors, bot.errors
    time.sleep(0.5)
    assert _avatar_in(w1) is None
    assert _avatar_in(w2) is None
    assert not gs1._migrating_out, "pending migration leaked"


def test_enter_space_survives_target_game_death(two_game_cluster):
    """EnterSpace to a space whose hosting game DIED: the dispatcher's
    cleanup dropped the space route (DispatcherService.go:586-634), the
    query ack returns game 0, and the migrating entity must recover —
    alive, in its source space, timers firing, RPCs still served
    (reference semantics: nothing was packed yet, so nothing is lost)."""
    import asyncio

    harness, worlds, servers = two_game_cluster
    host, port = harness.gate_addrs[0]
    bot = BotClient(host, port, strict=True)

    target_space_id = worlds[1]._test_space.id

    async def script():
        recv = await _login(bot, "carol")
        try:
            # kill game2 and wait for the dispatchers to drop its routes
            servers[1].stop()
            await asyncio.sleep(1.0)
            bot.call_server("JumpTo_Client", target_space_id)
            await asyncio.sleep(1.5)
            # the avatar must still answer RPCs on game1
            before = bot.player.attrs.get("pings") or 0
            bot.call_server("Ping_Client")
            for _ in range(100):
                if (bot.player.attrs.get("pings") or 0) > before:
                    break
                await asyncio.sleep(0.05)
            assert (bot.player.attrs.get("pings") or 0) > before
        finally:
            recv.cancel()
            await bot.conn.close()

    harness.submit(script()).result(timeout=60)
    av = _avatar_in(worlds[0])
    assert av is not None and not av.destroyed
    assert av.space is worlds[0]._test_space        # stayed home
    assert av.slot is not None and av._migrating is None
    # timers kept firing through the failed attempt
    hb = av.attrs.get("heartbeats") or 0
    time.sleep(0.3)
    assert (av.attrs.get("heartbeats") or 0) > hb
    # and the failed migration left no leaked bookkeeping
    assert not servers[0]._migrating_out


def test_create_on_game_and_online_games(two_game_cluster):
    """CreateEntityOnGame pins placement to a specific game (reference
    goworld.go:83) and GetOnlineGames-style views are seeded by the
    handshake and maintained by connect/disconnect notifies."""
    harness, worlds, servers = two_game_cluster
    # both games see the full cluster (game1 joined first, learns of
    # game2 via NOTIFY_GAME_CONNECTED; game2 is seeded by its ack)
    deadline = time.time() + 10
    while time.time() < deadline and not all(
        gs.online_games == {1, 2} for gs in servers
    ):
        time.sleep(0.05)
    assert servers[0].online_games == {1, 2}
    assert servers[1].online_games == {1, 2}

    # pin an entity onto game2 explicitly (the load heap would otherwise
    # prefer either)
    servers[0].create_entity_anywhere("Avatar", {"name": "pinned"},
                                      gameid=2)
    deadline = time.time() + 10
    placed = None
    while time.time() < deadline:
        for e in worlds[1].entities.values():
            if e.type_name == "Avatar" and \
                    e.attrs.get("name") == "pinned":
                placed = e
                break
        if placed is not None:
            break
        time.sleep(0.05)
    assert placed is not None, "pinned entity never appeared on game2"
    assert all(
        e.attrs.get("name") != "pinned"
        for e in worlds[0].entities.values() if e.type_name == "Avatar"
    )
