"""Multihost demo — ONE world, TWO controller processes, real cluster.

The capability the reference scales to with its dispatcher TCP star
(``engine/dispatchercluster``): multiple machines serving one game
world. Here the ENTITY plane is a single SPMD megaspace over a global
``jax.distributed`` mesh (each controller owns half the tiles; AOI
halos / tile migration ride XLA collectives, over DCN between hosts),
while the HOST plane is the same dispatcher/gate wire protocol as the
reference — one dispatcher, one GameServer per controller, one gate
per controller. Dispatcher-originated world mutations (client logins,
client RPCs, position syncs) replicate to every controller through the
per-tick mutation log (``net/game.py``), so any client on any gate
sees entities on any controller's tiles.

Run (one machine, two OS processes, 4 virtual CPU devices each):

    python examples/multihost_demo/run_cluster.py

It forms the cluster, logs a bot in through controller 0's gate, walks
an NPC on controller 1's half of the world, prints what the bot's
mirror sees, and shuts down. On real multi-host TPU deployments, start
one controller per host with the same script arguments (coordinator
address, process id) and point gates at the shared dispatcher.
"""

import asyncio
import json
import os
import subprocess
import sys
import socket
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TICKS = 500
TICK_SLEEP = 0.02


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def controller_main(pid: int, coord_port: int, disp_port: int) -> int:
    """One controller: half the mesh + a GameServer + its own gate."""
    from goworld_tpu.parallel.multihost import global_mesh, init_distributed
    init_distributed(f"127.0.0.1:{coord_port}", num_processes=2,
                     process_id=pid)

    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.entity.space import Space
    from goworld_tpu.net.botclient import BotClient
    from goworld_tpu.net.dispatcher import DispatcherService
    from goworld_tpu.net.game import GameServer
    from goworld_tpu.net.gate import GateService
    from goworld_tpu.ops.aoi import GridSpec

    n_dev, tile_w, radius = 8, 100.0, 12.0
    cfg = WorldConfig(
        capacity=64,
        grid=GridSpec(radius=radius, extent_x=tile_w + 2 * radius,
                      extent_z=100.0, k=16, cell_cap=32, row_block=64),
        npc_speed=0.0,
        enter_cap=512, leave_cap=512, sync_cap=512, input_cap=64,
    )
    world = World(cfg, n_spaces=n_dev, mesh=global_mesh(),
                  megaspace=True, halo_cap=16, migrate_cap=8)

    box = {}

    class Mega(Space):
        pass

    class Account(Entity):
        def Login_Client(self, name):
            # the avatar lands on tile 4+ — the OTHER controller's half
            avatar = self.world.create_entity(
                "Avatar", space=box["sp"], pos=(430.0, 0.0, 50.0),
            )
            avatar.attrs["name"] = name
            self.give_client_to(avatar)
            self.destroy()

    class Avatar(Entity):
        ATTRS = {"name": "allclients"}

    class Npc(Entity):
        pass

    world.registry.register("Mega", Mega, is_space=True, megaspace=True)
    world.register_entity("Account", Account)
    world.register_entity("Avatar", Avatar)
    world.register_entity("Npc", Npc)
    world.create_nil_space()
    box["sp"] = world.create_space("Mega")
    npc = world.create_entity("Npc", space=box["sp"],
                              pos=(433.0, 0.0, 50.0), eid="npc_demo_0000__x")

    ready = threading.Event()
    gate_port = {}
    loop_box = {}

    def services() -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        loop_box["loop"] = loop

        async def boot():
            if pid == 0:
                d = DispatcherService(1, "127.0.0.1", disp_port,
                                      desired_games=2, desired_gates=2)
                asyncio.ensure_future(d.serve())
                await d.started.wait()
            else:
                await asyncio.sleep(1.0)
            g = GateService(pid + 1, "127.0.0.1", 0,
                            [("127.0.0.1", disp_port)],
                            position_sync_interval_ms=20,
                            exit_on_dispatcher_loss=False)
            asyncio.ensure_future(g.serve())
            await g.started.wait()
            gate_port["p"] = g.bound_port

        loop.run_until_complete(boot())
        ready.set()
        loop.run_forever()

    threading.Thread(target=services, daemon=True).start()
    assert ready.wait(30)

    gs = GameServer(pid + 1, world, [("127.0.0.1", disp_port)],
                    boot_entity="Account")
    gs.start_network()

    bot = None
    if pid == 0:
        bot = BotClient("127.0.0.1", gate_port["p"], strict=True,
                        nosync=True)

        async def bot_script():
            while not gs.ready_event.is_set():
                await asyncio.sleep(0.1)
            await bot.connect()
            recv = asyncio.ensure_future(bot._recv_loop())
            try:
                await asyncio.wait_for(bot.player_ready.wait(), 90)
                bot.call_server("Login_Client", "demo-hero")
                t0 = time.time()
                while time.time() - t0 < 90:
                    me = bot.entities.get("npc_demo_0000__x")
                    if me is not None and bot.sync_count >= 3:
                        break
                    await asyncio.sleep(0.05)
            finally:
                recv.cancel()
        fut = asyncio.run_coroutine_threadsafe(bot_script(),
                                               loop_box["loop"])

    x = 433.0
    for _ in range(TICKS):
        gs.pump()
        if any(e.type_name == "Avatar" and not e.destroyed
               for e in world.entities.values()) and x < 440.0:
            x += 0.25
            npc.set_position((x, 0.0, 50.0))
        gs.tick()
        time.sleep(TICK_SLEEP)

    if pid == 0:
        fut.result(timeout=30)
        me = bot.entities.get("npc_demo_0000__x")
        print(json.dumps({
            "bot_player": bot.player.type_name if bot.player else None,
            "npc_mirrored": me is not None,
            "npc_mirror_x": me.pos[0] if me else None,
            "syncs": bot.sync_count,
            "strict_errors": bot.errors,
        }))
    return 0


def main() -> int:
    if len(sys.argv) > 1:                 # child controller
        return controller_main(int(sys.argv[1]), int(sys.argv[2]),
                               int(sys.argv[3]))
    coord, disp = _free_port(), _free_port()
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    flags = env.get("XLA_FLAGS", "")
    if "device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             str(pid), str(coord), str(disp)],
            cwd=REPO, env=env,
        )
        for pid in (0, 1)
    ]
    rc = 0
    for p in procs:
        rc |= p.wait(timeout=600)
    return rc


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    sys.exit(main())
