"""Multi-host (multi-controller) megaspace: two OS processes, one global
8-device mesh, entity migration and AOI ghost interest across the PROCESS
boundary (SURVEY.md §5.8 — the reference scales across machines via its
dispatcher TCP star; here the data plane rides XLA collectives whose
cross-process legs run over the distributed runtime: Gloo/gRPC on this
CPU rig, ICI+DCN on real hardware)."""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _drain(procs, timeout):
    """communicate() every worker, KILLING all of them on a timeout —
    a leaked worker pair keeps burning CPU (and its jax.distributed
    rendezvous) long after the test fails."""
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=timeout))
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.communicate()
        raise
    return outs


@pytest.mark.slow
def test_two_process_megaspace_migration_and_ghosts():
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "tests._mh_worker", str(pid), str(port)],
            cwd=REPO, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for pid in (0, 1)
    ]
    results = {}
    for p, (out, err) in zip(procs, _drain(procs, 300)):
        assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        r = json.loads(line)
        results[r["process"]] = r

    # each controller owns its half of the mesh
    assert results[0]["local_shards"] == [0, 1, 2, 3]
    assert results[1]["local_shards"] == [4, 5, 6, 7]
    # both controllers agree on the global population (psum over DCN)
    assert results[0]["global_alive"] == 2
    assert results[1]["global_alive"] == 2
    # the walker crossed the process boundary: process 1 saw the arrival
    # on its shard 4 (process 0 can never see it — not addressable there)
    assert results[1]["migrated_tick"] >= 0, (
        f"no cross-process migration: {results[1]}"
    )
    # the tile-4 watcher (process 1) saw an AOI enter BEFORE the walker
    # migrated — ghost-zone interest across the process boundary
    shard4_enters = [
        e for e in results[1]["enters"] if e[0] == 4 and e[1] == 0
    ]
    assert shard4_enters, (
        f"tile-4 watcher never saw the cross-border ghost: {results[1]}"
    )


@pytest.mark.slow
def test_world_api_multihost():
    """The full World (entity API + megaspace + host bookkeeping) running
    SPMD on two controllers: slot bookkeeping stays identical everywhere,
    while AOI event fan-out is owner-local — the watcher's interest set
    updates on the controller owning its tile."""
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "tests._mh_world_worker",
             str(pid), str(port)],
            cwd=REPO, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for pid in (0, 1)
    ]
    results = {}
    for p, (out, err) in zip(procs, _drain(procs, 300)):
        assert p.returncode == 0, f"worker failed:\n{err[-2500:]}"
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        r = json.loads(line)
        results[r["process"]] = r

    r0, r1 = results[0], results[1]
    # slot/shard bookkeeping is SPMD-identical on both controllers
    assert r0["walker_shard"] == r1["walker_shard"] == 4, (r0, r1)
    assert r0["watcher_shard"] == r1["watcher_shard"] == 4
    assert r0["walker_alive"] and r1["walker_alive"]
    # both controllers read the same committed device position
    assert abs(r0["walker_pos_x"] - r1["walker_pos_x"]) < 1e-4
    assert r0["walker_pos_x"] > 400.0
    # event fan-out is owner-local: tile 4 belongs to process 1, so ONLY
    # process 1 fired the watcher's OnEnterAOI / updated its interest set
    assert "walker_walker_00" in r1["watcher_interested_in"]
    assert ("watcher_sees", "walker_walker_00") in [
        tuple(e) for e in r1["events"]
    ]
    assert "walker_walker_00" not in r0["watcher_interested_in"]


@pytest.mark.slow
def test_cross_controller_client_visibility():
    """The reference's any-client-sees-any-entity contract
    (``components/gate/GateService.go:258-306``) across CONTROLLERS: a
    strict-mirror bot on controller 0's gate logs in, its Avatar lands on
    a tile owned by controller 1, and a Walker moving on that remote tile
    must appear and position-sync in the bot's mirror — controller 1
    decodes the events and the dispatcher wire carries them to gate 1 by
    gate id. Exercises the multihost mutation log (client connect + Login
    RPC arrive on one controller, applied on both) and the per-entity
    client-send ownership dedup."""
    coord = _free_port()
    disp = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "tests._mh_cluster_worker",
             str(pid), str(coord), str(disp)],
            cwd=REPO, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for pid in (0, 1)
    ]
    results = {}
    for p, (out, err) in zip(procs, _drain(procs, 700)):
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        r = json.loads(line)
        results[r["process"]] = r

    r0, r1 = results[0], results[1]
    assert "bot_script_error" not in r0, r0
    assert r0["bot_errors"] == [], r0["bot_errors"]
    # SPMD bookkeeping: both controllers agree the Avatar sits on tile 4
    # (controller 1's side) and owned the gate-1 client
    assert r0["avatar_shard"] == r1["avatar_shard"] == 4, (r0, r1)
    assert r0["avatar_had_client"] and r1["avatar_had_client"]
    assert r0["avatar_gate"] == r1["avatar_gate"] == 1
    # the bot's hang-up propagated through the mutation log: BOTH
    # controllers unbound the avatar's client
    assert r0["disconnect_propagated"] and r1["disconnect_propagated"], \
        (r0.get("extra_ticks"), r1.get("extra_ticks"))
    # the bot completed the Account -> Avatar handoff
    assert r0["bot_player_type"] == "Avatar", r0
    assert r0["bot_player_name"] == "bob", r0
    # the remote tile's walker reached the bot's mirror and kept syncing
    assert "walker_walker_00" in r0["bot_mirrors"], r0["bot_mirrors"]
    assert r0["walker_mirror_x"] is not None \
        and r0["walker_mirror_x"] > 420.5, r0
    assert r0["bot_sync_count"] >= 3, r0
    # and the traffic was emitted by CONTROLLER 1 (the tile owner), not 0
    assert r1["sent"]["create_entity"] >= 1, r1["sent"]
    assert r1["sent"]["sync_records"] >= 3, r1["sent"]


@pytest.mark.slow
def test_two_process_stress_consistency():
    """40 churny ticks with 60 movers over the 2-controller mesh: both
    controllers agree on the global population every tick, nobody is
    lost or duplicated (the union of local occupancies is exactly the
    population), and cross-process migrations actually happened."""
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "tests._mh_worker",
             str(pid), str(port), "stress"],
            cwd=REPO, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for pid in (0, 1)
    ]
    results = {}
    for p, (out, err) in zip(procs, _drain(procs, 420)):
        assert p.returncode == 0, f"worker failed:\n{err[-2500:]}"
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        r = json.loads(line)
        results[r["process"]] = r
    r0, r1 = results[0], results[1]
    assert r0["global_alive"] == r1["global_alive"] == [60] * 40
    total = sum(r0["occupancy"].values()) + sum(r1["occupancy"].values())
    assert total == 60, (r0["occupancy"], r1["occupancy"])
    assert r0["dropped"] == 0 and r1["dropped"] == 0
    # churn actually crossed tiles (and with 4x2... 8 tiles over 2
    # processes, some hops crossed the process boundary)
    assert r0["migrations"] + r1["migrations"] > 0


@pytest.mark.slow
def test_multihost_checkpoint_restore():
    """§5.4 checkpoint/resume EXTENDED across controllers: every
    controller calls freeze_world at the same point (the device snapshot
    is an allgather — itself a lockstep point), gets the identical
    global snapshot, and restore_world rebuilds a fresh World over the
    same mesh with positions, attrs, tile ownership, and (after one
    sweep) interest sets intact. The reference can only freeze a single
    game process (GameService.go:220-313)."""
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "tests._mh_freeze_worker",
             str(pid), str(port)],
            cwd=REPO, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for pid in (0, 1)
    ]
    results = {}
    for p, (out, err) in zip(procs, _drain(procs, 420)):
        assert p.returncode == 0, f"worker failed:\n{err[-2500:]}"
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        r = json.loads(line)
        results[r["process"]] = r

    r0, r1 = results[0], results[1]
    # the walker had crossed onto controller 1's tile pre-freeze, and the
    # restored world agrees on every controller
    assert r0["pre"]["walker_shard"] == 4
    assert r0["restored_walker_shard"] == r1["restored_walker_shard"] == 4
    for r in (r0, r1):
        assert abs(r["restored_walker_x"] - r0["pre"]["walker_x"]) < 1e-3
        assert r["restored_hp"] == 7
        assert r["restored_alive"] == 2
    # interest was re-derived from restored positions; fan-out stays
    # owner-local, so the watcher's set updates on controller 1
    assert r1["restored_watcher_sees"] == r1["pre"]["watcher_sees"] \
        == ["walker_walker_00"]


@pytest.mark.slow
def test_multihost_services():
    """Sharded singleton services on a multi-controller world: kvreg
    updates replicate through the mutation log, the group claims shards
    under one token, reconciles run on the allgathered-ready tick
    cadence — both controllers create the SAME service entities with
    the SAME deterministic ids, and a service RPC from SPMD logic
    executes on both (reference service.go:106-238 kvreg race,
    single-process-per-claim)."""
    coord = _free_port()
    disp = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "tests._mh_service_worker",
             str(pid), str(coord), str(disp)],
            cwd=REPO, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for pid in (0, 1)
    ]
    results = {}
    for p, (out, err) in zip(procs, _drain(procs, 420)):
        assert p.returncode == 0, f"worker failed:\n{err[-2500:]}"
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        r = json.loads(line)
        results[r["process"]] = r

    r0, r1 = results[0], results[1]
    assert r0["claim"] == r1["claim"] == "mh:1"
    # both shards placed, identical ids on both controllers, and the
    # entities EXIST locally on both (SPMD host replication)
    assert all(r0["service_eids"]), r0
    assert r0["service_eids"] == r1["service_eids"]
    assert r0["local_entities"] == r1["local_entities"] \
        == sorted(r0["service_eids"])
    # the SPMD service RPC executed exactly once on each controller
    assert r0["called"] and r1["called"]
    assert r0["incr_calls"] == r1["incr_calls"] == [5]
