"""The benchmark's game script: one space of device-driven NPCs and an
Avatar per client. Runs inside the game process (``python -m goworld_tpu
start`` executes it from the server directory) and reads its sizes from
``bench_params.json`` beside it, which ``run.py`` writes from the
cell's configuration file.

Besides the world it gives the harness what only the process that owns
the chip can read (device memory as an RPC reply; the device's rows and
neighbour lists as a file beside it), and — for the tests
under ``benchmark/tests`` only — plants a fault under the timed path
when ``bench_params.json`` names one and the file ``plant.on`` exists.
"""
import json
import os

import numpy as np

import goworld_tpu as gw
from goworld_tpu.utils import opmon

with open("bench_params.json") as _f:
    P = json.load(_f)
EXTENT = float(P["extent"])
# parking: the i-th login enters on a grid wider than an AOI box, so a
# login wave never puts hundreds of avatars into one neighbourhood
PARK = 2.0 * float(P["aoi_radius"]) + 20.0
PARK_ROW = max(int(EXTENT // PARK) - 1, 1)
_logins = [0]


@gw.register_space("Arena")
class Arena(gw.Space):
    pass


@gw.register_entity("Npc")
class Npc(gw.Entity):
    pass


@gw.register_entity("Avatar")
class Avatar(gw.Entity):
    def OnClientConnected(self):
        arena = next(sp for sp in self.world.spaces.values()
                     if sp.type_name == "Arena")
        i = _logins[0]
        _logins[0] += 1
        x = PARK * (0.5 + i % PARK_ROW)
        z = PARK * (0.5 + (i // PARK_ROW) % PARK_ROW)
        self.enter_space(arena.id, (x, 0.0, z))

    def OnClientDisconnected(self):
        self.destroy()

    def Echo_Client(self, token):
        self.call_client("OnEcho", token)

    def Stats_Client(self):
        import jax

        peak = 0
        for d in jax.local_devices():
            ms = d.memory_stats() or {}
            peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
        self.call_client("OnStats", json.dumps(
            {"memory_peak_bytes": peak,
             "npcs": sum(1 for e in self.world.entities.values()
                         if e.type_name == "Npc")}))


    def Rows_Client(self, seed, sample):
        """For the check, once the world has settled: what the device
        holds, read in the one process that can read it. Every row's
        position and liveness, each avatar's row, and the neighbour
        lists of every avatar row and of ``sample`` NPC rows drawn from
        the seed go to ``rows.npz`` beside this script (program-prepared
        data: the benchmark compares the lists with its own brute force
        over these positions, and the avatars' rows with what it sent).
        Whole arrays come to the host as they are and are cut there, so
        nothing compiles for it."""
        st = self.world.state
        pos, alive, nbr = (np.asarray(x)[0]
                           for x in (st.pos, st.alive, st.nbr))
        avatars = sorted((e.id, int(e.slot))
                         for e in self.world.entities.values()
                         if e.type_name == "Avatar" and e.slot is not None)
        av_rows = np.array([r for _i, r in avatars], np.int64)
        npc_rows = np.setdiff1d(np.nonzero(alive)[0], av_rows)
        rng = np.random.default_rng([int(seed), 0x726F7773])
        rows = np.concatenate([av_rows, rng.choice(
            npc_rows, min(int(sample), len(npc_rows)), replace=False)])
        np.savez("rows.npz", pos=pos, alive=alive, rows=rows,
                 nbr=nbr[rows], avatar_rows=av_rows,
                 avatar_eids=np.array([i for i, _r in avatars]),
                 tick=int(self.world.tick_count))
        self.call_client("OnRows", "rows.npz")


class _LiveTick:
    """``/vars`` prints what it cannot encode with ``str``: this prints
    the world's tick count as it is when the page is asked for."""

    def __init__(self, world):
        self.world = world

    def __str__(self):
        return str(int(self.world.tick_count))


def _plant(world, kind: str) -> None:
    """Tests only: break the timed path underneath, once ``plant.on``
    exists. ``alter`` changes a position where it is staged, ``half``
    leaves out every second client's record, ``freeze`` stages nothing
    (the state stays as it was)."""
    cls = type(world)
    stage = cls.stage_pos_sync_batch

    def broken(self, eids, vals):
        if not os.path.exists("plant.on"):
            return stage(self, eids, vals)
        eids = np.asarray(eids, "S16")
        vals = np.array(vals, np.float32).reshape(-1, 4)
        if kind == "alter":
            vals[:, 0] += 1.0
        elif kind == "half":
            keep = np.array([sum(e) % 2 == 0 for e in eids], bool)
            eids, vals = eids[keep], vals[keep]
        elif kind == "freeze":
            return 0
        return stage(self, eids, vals)

    cls.stage_pos_sync_batch = broken


@gw.on_boot
def fill(world):
    arena = world.create_space("Arena")
    rng = np.random.default_rng(int(P["seed"]))
    xz = rng.uniform(0.0, EXTENT, (int(P["npcs"]), 2))
    for x, z in xz:
        world.create_entity("Npc", space=arena, pos=(x, 0.0, z),
                            moving=True)
    opmon.expose("bench_npcs", int(P["npcs"]))
    # the audit plane samples on the logic thread every so many ticks:
    # run.py opens every window at the same place against that cadence,
    # so every run holds as many samples
    opmon.expose("bench_tick", _LiveTick(world))
    aud = getattr(world, "audit", None)
    opmon.expose("bench_audit_every",
                 int(getattr(aud, "sample_every", 0) or 0))
    if P.get("plant"):
        _plant(world, P["plant"])


if __name__ == "__main__":
    gw.run()
