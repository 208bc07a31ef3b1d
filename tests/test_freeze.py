"""Freeze/restore (hot reload) tests.

Mirrors the reference's live-reload soak (``test_game.yml``: run bots,
``goworld reload``, run bots again) at unit scale, plus round-trip unit
tests in the spirit of ``engine/entity/migarte_test.go``."""

import threading
import time

import pytest

from goworld_tpu import freeze
from goworld_tpu.core import WorldConfig
from goworld_tpu.entity import Entity, GameClient, Space, World
from goworld_tpu.net.game import GameServer
from goworld_tpu.net.standalone import ClusterHarness
from goworld_tpu.ops.aoi import GridSpec
from goworld_tpu.utils import snapfiles


class Npc(Entity):
    ATTRS = {"hp": "allclients", "name": "client"}

    def __init__(self):
        super().__init__()
        self.heal_count = 0

    def Heal(self, amount):
        self.heal_count += 1
        self.attrs["hp"] = self.attrs.get("hp", 0) + amount


class Arena(Space):
    pass


def _cfg():
    return WorldConfig(
        capacity=64,
        grid=GridSpec(radius=30.0, extent_x=120.0, extent_z=120.0),
        input_cap=64,
    )


def _register(world):
    world.register_entity("Npc", Npc)
    world.register_space("Arena", Arena)


def _make_world():
    w = World(_cfg(), n_spaces=1)
    _register(w)
    w.create_nil_space()
    return w


class TestFreezeRoundtrip:
    def test_requires_nil_space(self):
        w = World(_cfg(), n_spaces=1)
        with pytest.raises(RuntimeError):
            freeze.freeze_world(w)

    def test_world_roundtrip(self):
        w = _make_world()
        arena = w.create_space("Arena", motd="welcome")
        a = w.create_entity("Npc", space=arena, pos=(10.0, 0.0, 10.0))
        a.attrs["hp"] = 70
        a.attrs["name"] = "alice"
        b = w.create_entity("Npc", space=arena, pos=(12.0, 0.0, 12.0))
        b.attrs["hp"] = 55
        b.set_yaw(1.5)
        # timer by method name: migration/freeze-safe like the reference
        b.add_timer(0.05, "Heal", 5)
        # client binding must survive quietly
        a.client = GameClient(2, "c" * 16, w)
        parked = w.create_entity("Npc", pos=(0.0, 0.0, 0.0))  # nil space
        for _ in range(3):
            w.tick()

        data = freeze.freeze_world(w)

        w2 = _make_world()
        freeze.restore_world(w2, data)
        assert set(w2.entities) == set(w.entities)
        arena2 = w2.spaces[arena.id]
        assert arena2.attrs.get("motd") == "welcome"
        a2, b2 = w2.entities[a.id], w2.entities[b.id]
        assert a2.attrs.get("hp") == 70
        assert a2.attrs.get("name") == "alice"
        assert a2.client is not None and a2.client.gate_id == 2
        assert a2.space is arena2
        assert w2.entities[parked.id].space is w2.nil_space
        # positions/yaw carried over (device state was snapshotted)
        for _ in range(3):
            w2.tick()
        assert tuple(w2.read_pos(0, a2.slot)) == pytest.approx(
            (10.0, 0.0, 10.0))
        assert w2.read_yaw(0, b2.slot) == pytest.approx(1.5)
        # AOI re-fires: a and b are within radius -> interest rebuilt
        assert b2.id in a2.interested_in
        # restored method-name timer still fires
        deadline = time.monotonic() + 2.0
        while b2.heal_count == 0 and time.monotonic() < deadline:
            w2.tick()
            time.sleep(0.01)
        assert b2.heal_count >= 1
        assert b2.attrs.get("hp") >= 60

    @pytest.mark.parametrize("enter_cap", [4096, 2])
    def test_reload_settles_what_a_connected_client_holds(self, enter_cap):
        """Every enter a client got still gets its leave across a
        reload: the freeze carries what the client was told, and the
        first restored tick destroys what is out of range by then and
        creates what is new — also when that tick's enter flood is cut
        at ``enter_cap`` (2: most of the avatar's pairs are dropped)."""
        def world():
            w = World(WorldConfig(
                capacity=64, input_cap=64, enter_cap=enter_cap,
                grid=GridSpec(radius=30.0, extent_x=120.0,
                              extent_z=120.0)), n_spaces=1)
            _register(w)
            w.create_nil_space()
            return w

        w = world()
        arena = w.create_space("Arena")
        av = w.create_entity("Npc", space=arena, pos=(60.0, 0.0, 60.0))
        # one at a time, so no tick's enters pass the cap before the
        # freeze: the client is told of every one of them
        stayers = []
        for i in range(5):
            stayers.append(w.create_entity(
                "Npc", space=arena, pos=(55.0 + 2 * i, 0.0, 62.0)))
            w.tick()
        leaver = w.create_entity("Npc", space=arena, pos=(70.0, 0.0, 70.0))
        w.tick()
        comer = w.create_entity("Npc", space=arena, pos=(5.0, 0.0, 5.0))
        w.tick()
        av.client = GameClient(2, "c" * 16, w)
        told = {e.id for e in stayers} | {leaver.id}
        assert av.interested_in == told

        data = freeze.freeze_world(w)
        assert freeze.freeze_world(w, run_hooks=False)["entities"][0] \
            .get("interest") is None       # a checkpoint carries none
        recs = {ed["id"]: ed for ed in data["entities"]}
        assert set(recs[av.id]["interest"]) == told
        assert "interest" not in recs[leaver.id]    # no client, no list
        # by the first restored tick one neighbour has walked out and
        # a stranger has walked in
        recs[leaver.id]["pos"] = [5.0, 0.0, 110.0]
        recs[comer.id]["pos"] = [58.0, 0.0, 58.0]

        w2 = world()
        sent = []
        w2.client_sink = lambda gate, cid, msg: sent.append(msg)
        freeze.restore_world(w2, data)
        w2.tick()
        av2 = w2.entities[av.id]
        now = {e.id for e in stayers} | {comer.id}
        assert av2.interested_in == now
        assert all(av.id in w2.entities[j].interested_by for j in now)
        destroyed = [m["eid"] for m in sent if m["type"] == "destroy_entity"]
        created = [m["eid"] for m in sent if m["type"] == "create_entity"]
        assert destroyed == [leaver.id]
        assert created.count(comer.id) == 1
        assert leaver.id not in created
        assert w2._restored_interest is None
        # ... and a mirror the client kept still gets its leave later
        sent.clear()
        w2.entities[stayers[0].id].set_position((5.0, 0.0, 5.0))
        w2.tick()
        w2.tick()
        assert [m["eid"] for m in sent
                if m["type"] == "destroy_entity"] == [stayers[0].id]

    def test_restored_poses_ride_the_spawn(self):
        """More restored entities than ``input_cap``: every pose lands
        with its spawn on the first tick — none waits in the pos-sync
        queue to rewind, ticks later, a row the device moved on from."""
        def world():
            w = World(WorldConfig(
                capacity=64, input_cap=4,
                grid=GridSpec(radius=30.0, extent_x=120.0,
                              extent_z=120.0)), n_spaces=1)
            _register(w)
            w.create_nil_space()
            return w

        w = world()
        arena = w.create_space("Arena")
        ents = [w.create_entity("Npc", space=arena,
                                pos=(5.0 + 7 * i, 0.0, 9.0 + 6 * i))
                for i in range(16)]
        w.tick()
        for i, e in enumerate(ents):
            e.set_yaw(0.1 * (i + 1))
        for _ in range(5):          # 16 yaws through input_cap = 4
            w.tick()
        data = freeze.freeze_world(w)

        w2 = world()
        freeze.restore_world(w2, data)
        w2.tick()
        assert not w2._staged_pos
        for i, e in enumerate(ents):
            e2 = w2.entities[e.id]
            assert e2._pending_pos is None and e2._pending_yaw is None
            assert w2.read_yaw(0, e2.slot) == pytest.approx(0.1 * (i + 1))
            assert tuple(w2.read_pos(0, e2.slot)) == pytest.approx(
                (5.0 + 7 * i, 0.0, 9.0 + 6 * i))

    def test_file_roundtrip(self, tmp_path):
        w = _make_world()
        arena = w.create_space("Arena")
        e = w.create_entity("Npc", space=arena, pos=(5.0, 0.0, 5.0))
        e.attrs["hp"] = 1
        path = freeze.freeze_to_file(w, str(tmp_path))
        assert path.endswith("game1_freezed.dat")
        w2 = _make_world()
        freeze.restore_from_file(w2, str(tmp_path))
        assert e.id in w2.entities

    def test_restore_rejects_populated_world(self):
        w = _make_world()
        data = freeze.freeze_world(w)
        w2 = _make_world()
        w2.create_space("Arena")
        with pytest.raises(RuntimeError):
            freeze.restore_world(w2, data)


def _drive(gs, stop):
    while not stop.is_set() and gs.run_state == "running":
        gs.pump()
        gs.tick()
        time.sleep(0.01)
    # freeze path: serve_forever would do this; emulate its tail
    if gs.run_state == "freezing":
        gs._do_freeze()


def test_cluster_freeze_then_restore(tmp_path):
    """Full protocol: game asks dispatchers to block, snapshots, exits;
    a new game process restores and traffic resumes (SURVEY.md#3.6)."""
    harness = ClusterHarness(n_dispatchers=2, n_gates=0, desired_games=1)
    harness.start()
    try:
        w = _make_world()
        arena = w.create_space("Arena")
        npc = w.create_entity("Npc", space=arena, pos=(1.0, 0.0, 1.0))
        npc.attrs["hp"] = 9

        gs = GameServer(1, w, list(harness.dispatcher_addrs),
                        freeze_dir=str(tmp_path))
        gs.start_network()
        stop = threading.Event()
        t = threading.Thread(target=_drive, args=(gs, stop), daemon=True)
        t.start()
        assert gs.ready_event.wait(20)

        gs.request_freeze()
        deadline = time.monotonic() + 15
        while gs.run_state != "frozen" and time.monotonic() < deadline:
            time.sleep(0.05)
        assert gs.run_state == "frozen"
        stop.set()
        t.join(timeout=5)

        # dispatcher kept the game blocked: queue an RPC while "down"
        from goworld_tpu.net import proto as P
        d = harness.dispatchers[0]
        pkt = P.pack_call_entity_method(npc.id, "Heal", (3,))
        harness.submit(_inject(d, npc.id, pkt)).result(timeout=5)

        # new process, same game id, -restore
        w2 = _make_world()
        gs2 = GameServer(1, w2, list(harness.dispatcher_addrs),
                         freeze_dir=str(tmp_path), restore=True)
        assert npc.id in w2.entities
        gs2.start_network()
        stop2 = threading.Event()
        t2 = threading.Thread(target=_drive, args=(gs2, stop2), daemon=True)
        t2.start()
        try:
            npc2 = w2.entities[npc.id]
            deadline = time.monotonic() + 15
            while npc2.heal_count == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert npc2.heal_count == 1, \
                "queued RPC was not delivered after restore"
            assert npc2.attrs.get("hp") == 12
        finally:
            stop2.set()
            t2.join(timeout=5)
            gs2.stop()
    finally:
        harness.stop()


async def _inject(dispatcher, eid, pkt):
    """Route a packet through the dispatcher's entity table as if it came
    from another game."""
    dispatcher._dispatch_to_entity(eid, pkt)


class TestAsyncCheckpoint:
    def test_checkpoint_while_running_restores_capture_point(self, tmp_path):
        """checkpoint_async captures the tick boundary it was called at;
        the world keeps ticking and mutating afterwards, and restoring
        the file reproduces the CAPTURED state, not the later one."""
        import numpy as np

        from goworld_tpu import freeze as fz
        from goworld_tpu.core.state import WorldConfig
        from goworld_tpu.entity.manager import World
        from goworld_tpu.ops.aoi import GridSpec

        def build():
            cfg = WorldConfig(
                capacity=64,
                grid=GridSpec(radius=20.0, extent_x=200.0, extent_z=200.0,
                              k=8, cell_cap=16, row_block=64),
                npc_speed=6.0,
                enter_cap=256, leave_cap=256, sync_cap=256,
                attr_sync_cap=64, input_cap=8,
            )
            w = World(cfg)
            w.register_entity("Npc", type("Npc", (Entity,), {}))
            w.register_space("Arena", type("Arena", (Space,), {}))
            w.create_nil_space()
            return w

        w = build()
        arena = w.create_space("Arena")
        rng = np.random.default_rng(0)
        for i in range(20):
            e = w.create_entity(
                "Npc", space=arena,
                pos=(rng.uniform(0, 200), 0, rng.uniform(0, 200)),
                moving=True,
            )
            e.attrs["hp"] = 100 + i
        for _ in range(3):
            w.tick()

        handle = fz.checkpoint_async(w, str(tmp_path))
        # the world keeps running + mutating while the worker transfers
        captured_pos = {
            e.id: tuple(e.position) for e in w.entities.values()
            if not e.is_space
        }
        for _ in range(5):
            w.tick()
        for e in list(w.entities.values()):
            if not e.is_space:
                e.attrs["hp"] = 1          # post-capture mutation
        handle.join(30)
        assert handle.path is not None

        w2 = build()
        fz.restore_world(w2, fz.read_freeze_file(handle.path))
        w2.tick()
        npcs = [e for e in w2.entities.values()
                if not e.is_space and e.type_name == "Npc"]
        assert len(npcs) == 20
        for e in npcs:
            assert e.attrs["hp"] >= 100    # captured value, not the 1
            ref = captured_pos[e.id]
            got = e.position
            # captured positions (one tick of drift allowed: capture is
            # the state AFTER the last tick; restore re-integrates)
            d = max(abs(got[0] - ref[0]), abs(got[2] - ref[2]))
            assert d < 1.0, (e.id, got, ref)

    def test_checkpoint_contains_no_slot_refs(self, tmp_path):
        """The written file is plain freeze format: every deferred
        (shard, slot) placeholder must have been patched out."""
        import numpy as np

        from goworld_tpu import freeze as fz
        from goworld_tpu.core.state import WorldConfig
        from goworld_tpu.entity.manager import World
        from goworld_tpu.ops.aoi import GridSpec

        cfg = WorldConfig(
            capacity=16,
            grid=GridSpec(radius=20.0, extent_x=100.0, extent_z=100.0,
                          k=8, cell_cap=16, row_block=16),
            enter_cap=64, leave_cap=64, sync_cap=64,
            attr_sync_cap=16, input_cap=4,
        )
        w = World(cfg)
        w.register_entity("Npc", type("Npc", (Entity,), {}))
        w.register_space("Arena", type("Arena", (Space,), {}))
        w.create_nil_space()
        sp = w.create_space("Arena")
        w.create_entity("Npc", space=sp, pos=(50.0, 0.0, 50.0))
        w.tick()
        h = fz.checkpoint_async(w, str(tmp_path)).join(30)
        data = fz.read_freeze_file(h.path)
        assert all("_slot" not in rec for rec in data["entities"])
        pos = data["entities"][0]["pos"]
        assert abs(pos[0] - 50.0) < 1e-3 and abs(pos[2] - 50.0) < 1e-3


class TestSnapshotCorruption:
    """A partial/corrupt snapshot must be REJECTED whole — restore falls
    back to the next-freshest candidate or fails loudly, never
    half-loads (ISSUE 3 recovery invariant)."""

    def _frozen(self):
        w = _make_world()
        arena = w.create_space("Arena")
        e = w.create_entity("Npc", space=arena, pos=(5.0, 0.0, 5.0))
        e.attrs["hp"] = 3
        return e, freeze.freeze_world(w)

    def test_truncated_freeze_falls_back_to_checkpoint(self, tmp_path):
        import msgpack

        e, data = self._frozen()
        # older but VALID checkpoint...
        freeze.write_freeze_file(
            str(tmp_path / snapfiles.checkpoint_filename(1)), data)
        # ...shadowed by a newer TRUNCATED freeze file (simulated crash
        # of a non-atomic writer / disk fault)
        blob = msgpack.packb(data, use_bin_type=True)
        fz = tmp_path / snapfiles.freeze_filename(1)
        fz.write_bytes(blob[: len(blob) // 2])
        later = time.time() + 5
        import os
        os.utime(str(fz), (later, later))

        assert snapfiles.latest_snapshot_path(1, str(tmp_path)) \
            == str(fz)                      # mtime says the corrupt one
        w2 = _make_world()
        freeze.restore_from_file(w2, str(tmp_path))   # ...but it falls back
        assert e.id in w2.entities
        assert w2.entities[e.id].attrs.get("hp") == 3
        assert freeze.has_restorable_snapshot(1, str(tmp_path))

    def test_all_corrupt_rejected_not_half_loaded(self, tmp_path):
        import msgpack

        _e, data = self._frozen()
        blob = msgpack.packb(data, use_bin_type=True)
        (tmp_path / snapfiles.freeze_filename(1)).write_bytes(blob[:40])
        assert not freeze.has_restorable_snapshot(1, str(tmp_path))
        w2 = _make_world()
        with pytest.raises(freeze.CorruptSnapshotError):
            freeze.restore_from_file(w2, str(tmp_path))
        # nothing was half-loaded: the world still holds only nil space
        assert list(w2.entities) == [w2.nil_space.id]

    def test_parseable_but_wrong_shape_rejected(self, tmp_path):
        import msgpack

        (tmp_path / snapfiles.freeze_filename(1)).write_bytes(
            msgpack.packb(["not", "a", "freeze"], use_bin_type=True))
        with pytest.raises(freeze.CorruptSnapshotError):
            freeze.read_freeze_file(
                str(tmp_path / snapfiles.freeze_filename(1)))

    def test_crash_mid_freeze_leaves_only_tmp(self, tmp_path):
        """Injected crash between the tmp write and the atomic rename
        (`crash:freeze.write`): the snapshot path must hold only the
        .tmp — a later -restore boot sees no (partial) freeze file at
        all, exactly the no-half-load guarantee."""
        import os
        import subprocess
        import sys

        from goworld_tpu.utils import faults as faults_mod

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        target = str(tmp_path / snapfiles.freeze_filename(1))
        env = dict(os.environ)
        env["PYTHONPATH"] = repo
        env["JAX_PLATFORMS"] = "cpu"
        env["GOWORLD_FAULTS"] = "crash:freeze.write:1.0"
        r = subprocess.run(
            [sys.executable, "-c",
             "from goworld_tpu.utils import faults; "
             "faults.install('freezer'); "
             "from goworld_tpu import freeze; "
             f"freeze.write_freeze_file({target!r}, "
             "{'version': 1, 'entities': []})"],
            env=env, capture_output=True, text=True, timeout=240,
        )
        assert r.returncode == faults_mod.KILL_EXIT_CODE, \
            r.stdout + r.stderr
        assert not os.path.exists(target)          # no partial snapshot
        assert os.path.exists(target + ".tmp")     # the crash artifact
        w2 = _make_world()
        with pytest.raises(FileNotFoundError):
            freeze.restore_from_file(w2, str(tmp_path))


@pytest.mark.precision
class TestSnapshotChain:
    """Quantized + delta-compressed snapshot chain (ISSUE 12,
    freeze.SnapshotChain): keyframe cadence, bit-exact roundtrip in
    the lattice domain, and corrupt/mismatched deltas falling back to
    the keyframe through the existing CorruptSnapshotError path."""

    def _world_with_npcs(self, n=8):
        w = _make_world()
        sp = w.create_space("Arena")
        ents = [w.create_entity("Npc", space=sp,
                                pos=(3.0 * i, 0.0, 5.0 * i))
                for i in range(n)]
        w.tick()
        return w, sp, ents

    def test_keyframe_cadence_honored(self, tmp_path):
        w, _sp, _es = self._world_with_npcs()
        chain = freeze.SnapshotChain(w, str(tmp_path), keyframe_every=3)
        kinds = []
        for _ in range(7):
            kinds.append("K" if chain.write().endswith("_ckpt_key.dat")
                         else "D")
        assert kinds == ["K", "D", "D", "K", "D", "D", "K"]

    def test_roundtrip_bit_exact_on_restore(self, tmp_path):
        import msgpack

        w, _sp, ents = self._world_with_npcs()
        chain = freeze.SnapshotChain(w, str(tmp_path), keyframe_every=4)
        pk = chain.write()
        pd = chain.write()
        data = freeze.read_freeze_file(pd)   # delta resolves via key
        assert data["version"] == 1
        w2 = _make_world()
        freeze.restore_world(w2, data)
        assert len([e for e in w2.entities.values()
                    if isinstance(e, Npc)]) == len(ents)
        # restored positions are lattice points; a SECOND chain write
        # of the restored world produces BYTE-IDENTICAL planes
        # (lattice points re-quantize to themselves)
        w2.tick()
        chain2 = freeze.SnapshotChain(w2, str(tmp_path / "b"),
                                      keyframe_every=4)
        import os as _os

        _os.makedirs(tmp_path / "b", exist_ok=True)
        pk2 = chain2.write()
        a = msgpack.unpackb(open(pk, "rb").read(), raw=False)
        b = msgpack.unpackb(open(pk2, "rb").read(), raw=False)
        for nm in ("pos_xz", "pos_y", "yaw", "moving"):
            assert a["planes"][nm] == b["planes"][nm], nm

    def test_delta_ships_only_changed_rows(self, tmp_path):
        import msgpack
        import numpy as np

        w, _sp, ents = self._world_with_npcs()
        chain = freeze.SnapshotChain(w, str(tmp_path), keyframe_every=8)
        chain.write()
        # move ONE entity by a super-lattice amount
        ents[3].set_position((100.0, 0.0, 100.0))
        w.tick()
        pd = chain.write()
        rec = msgpack.unpackb(open(pd, "rb").read(), raw=False)
        rows = np.frombuffer(rec["rows"], np.int32)
        assert (rows < 0).sum() <= 2     # the mover (+jitter slack)
        data = freeze.read_freeze_file(pd)
        by_id = {e["id"]: e for e in data["entities"]}
        got = by_id[ents[3].id]["pos"]
        step = freeze.snapshot_quant_step(w)
        assert abs(got[0] - 100.0) <= step
        assert abs(got[2] - 100.0) <= step

    def test_corrupt_delta_falls_back_to_keyframe(self, tmp_path):
        w, _sp, ents = self._world_with_npcs()
        chain = freeze.SnapshotChain(w, str(tmp_path), keyframe_every=4)
        chain.write()
        pd = chain.write()
        with open(pd, "r+b") as f:
            f.seek(24)
            f.write(b"\xff" * 16)
        with pytest.raises(freeze.CorruptSnapshotError):
            freeze.read_freeze_file(pd)
        # the candidate walk lands on the keyframe instead
        w2 = _make_world()
        freeze.restore_from_file(w2, str(tmp_path))
        assert len([e for e in w2.entities.values()
                    if isinstance(e, Npc)]) == len(ents)

    def test_rewritten_keyframe_fails_delta_crc(self, tmp_path):
        """A delta whose keyframe was REPLACED (CRCs mismatch) must be
        rejected whole — merging planes across two worlds' keyframes
        would silently mix states."""
        w, _sp, ents = self._world_with_npcs()
        chain = freeze.SnapshotChain(w, str(tmp_path), keyframe_every=4)
        chain.write()
        pd = chain.write()
        # a different world rewrites the keyframe under the delta
        w3 = _make_world()
        sp3 = w3.create_space("Arena")
        w3.create_entity("Npc", space=sp3, pos=(99.0, 0.0, 99.0))
        w3.tick()
        freeze.SnapshotChain(w3, str(tmp_path), keyframe_every=4).write()
        with pytest.raises(freeze.CorruptSnapshotError,
                           match="CRC mismatch"):
            freeze.read_freeze_file(pd)
        # ...and recovery still restores (the fresh keyframe parses)
        w2 = _make_world()
        freeze.restore_from_file(w2, str(tmp_path))
