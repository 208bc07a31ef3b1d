"""Live serving telemetry (ISSUE 11): the device-resident lane carry
on the PRODUCTION World tick (zero host syncs asserted under
``jax.transfer_guard``), the drained-lane -> metrics/signature
plumbing, one-trace-per-config stability, the megaspace lane set, and
the end-to-end acceptance: a live (non-bench) GameServer serves a
workload signature at /workload and an induced SLO breach yields a
correlated bundle at /incidents."""

import json
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from goworld_tpu.core.state import WorldConfig
from goworld_tpu.entity.entity import Entity
from goworld_tpu.entity.manager import World
from goworld_tpu.entity.space import Space
from goworld_tpu.ops import telemetry
from goworld_tpu.ops.aoi import GridSpec
from goworld_tpu.utils import debug_http, flightrec, metrics

pytestmark = pytest.mark.flightrec


class Arena(Space):
    pass


class Npc(Entity):
    pass


def _world(skin=2.0, n=24, telemetry_live=True):
    w = World(
        WorldConfig(capacity=64, grid=GridSpec(
            radius=10.0, extent_x=40.0, extent_z=40.0, skin=skin)),
        n_spaces=1, telemetry_live=telemetry_live,
    )
    w.register_space("Arena", Arena, use_aoi=True)
    w.register_entity("Npc", Npc)
    w.create_nil_space()
    sp = w.create_space("Arena")
    for i in range(n):
        w.create_entity("Npc", space=sp,
                        pos=(2.0 * (i % 5), 0.0, 2.0 * (i // 5)),
                        moving=True)
    return w


# =======================================================================
# zero added host syncs on the live path
# =======================================================================
def test_live_fold_zero_sync_under_transfer_guard():
    """The per-tick accumulation (compiled step + telemetry fold) must
    run with NO host transfers — the ISSUE 11 acceptance bound. The
    staging flush and the drain are host work by design and sit
    outside the guard."""
    w = _world()
    for _ in range(3):
        w.tick()  # trace both executables first
    inputs = w._flush_staging()  # host->device, outside the guard
    # the resident fold DONATES its accumulator (the carry is deleted
    # by the call): read the before-count first, outside the guard
    reb_before = int(np.asarray(w._telem_acc["rebuilt"]).sum())
    with jax.transfer_guard("disallow"):
        st2, outs = w._step(w.state, inputs, w.policy)
        acc2 = w._telem_fn(w._telem_acc, outs)
        jax.block_until_ready(acc2)
    # sanity: the guarded fold really accumulated a tick
    assert int(np.asarray(acc2["rebuilt"]).sum()) == reb_before + 1


def test_one_trace_per_config_and_signature_stability():
    """TRACE_COUNTS: the live fold compiles ONCE per World config, and
    the signature classes are stable across further ticks (no
    per-tick or per-signature retrace)."""
    w = _world()
    w.tick()
    traces0 = telemetry.TRACE_COUNTS.get("telemetry_update_live", 0)
    for _ in range(10):
        w.tick()
    sig1 = w.workload_signature()
    for _ in range(10):
        w.tick()
    sig2 = w.workload_signature()
    assert telemetry.TRACE_COUNTS["telemetry_update_live"] == traces0
    assert sig1["sig"] == sig2["sig"]
    assert sig1["config"] == sig2["config"]


# =======================================================================
# drained lanes: parity, metrics feed, occupancy
# =======================================================================
def test_drained_lanes_track_the_live_world():
    w = _world(n=24)
    ticks = 12
    for _ in range(ticks):
        w.tick()
    lanes = w._telem_lanes
    # every tick contributed exactly one rebuilt sample
    assert sum(lanes["rebuilt"]["counts"]) == ticks
    # skin on: the slack lane exists and carries a sample per tick
    assert sum(lanes["skin_slack"]["counts"]) == ticks
    # occupancy: one sample per shard per tick; per_tile mirrors the
    # true device population (24 NPCs alive in the one shard)
    assert sum(lanes["occupancy"]["counts"]) == ticks
    assert lanes["occupancy"]["per_tile"] == [24]
    # quiet world: the oracle gauges stayed silent
    assert lanes["over_cap_cells"]["counts"][0] == ticks
    sig = w.workload_signature()
    assert sig["density"] == "exact"
    assert sig["ticks"] == ticks

    # vmapped S>1 worlds clear the skin: the lane set follows
    w2 = World(
        WorldConfig(capacity=32, grid=GridSpec(
            radius=10.0, extent_x=40.0, extent_z=40.0, skin=2.0)),
        n_spaces=2,
    )
    w2.create_nil_space()
    w2.tick()
    assert "skin_slack" not in w2._telem_lanes
    assert w2._telem_lanes["occupancy"]["per_tile"] == [0, 0]
    assert w2.workload_signature()["churn"] == "skinless"


def test_pipelined_world_drains_one_tick_behind():
    """pipeline_decode: the drained accumulator is swapped one tick
    back like the outputs — fetching the CURRENT tick's acc would
    depend on the in-flight step and re-serialize exactly the
    host/device overlap the mode exists to buy."""
    w = World(
        WorldConfig(capacity=32, grid=GridSpec(
            radius=10.0, extent_x=40.0, extent_z=40.0)),
        n_spaces=1, pipeline_decode=True,
    )
    w.create_nil_space()
    ticks = 5
    for _ in range(ticks):
        w.tick()
    # folded every tick, drained one behind
    assert sum(w._telem_lanes["rebuilt"]["counts"]) == ticks - 1
    assert int(np.asarray(w._telem_acc["rebuilt"]).sum()) == ticks


def test_lanes_feed_metrics_registry():
    w = _world(n=10)
    for _ in range(3):
        w.tick()
    text = metrics.REGISTRY.expose_text()
    # drained lanes land as shared-ladder histograms + per-tile gauges
    assert "telemetry_rebuilt_count" in text
    assert "telemetry_over_cap_cells_bucket" in text
    assert 'telemetry_tile_occupancy{tile="0"} 10' in text
    snap = metrics.REGISTRY.histogram_snapshot("telemetry_rebuilt")
    assert snap and snap[0][1]["count"] >= 1


def test_telemetry_live_off_is_really_off():
    w = _world(telemetry_live=False)
    for _ in range(3):
        w.tick()
    assert w._telem_fn is None and w._telem_lanes is None
    assert w.workload_signature() is None


def test_histogram_add_counts_rejects_mismatch():
    h = metrics.Histogram(buckets=(1.0, 2.0))
    h.add_counts([1, 2, 3])
    assert h.count == 6
    with pytest.raises(ValueError, match="buckets"):
        h.add_counts([1, 2])


# =======================================================================
# megaspace: comms lanes + per-tile occupancy
# =======================================================================
@pytest.mark.multichip
def test_mega_live_lanes_and_tile_skew():
    from goworld_tpu.parallel.mesh import make_mesh

    n_dev = 4
    radius, tile_w = 10.0, 50.0
    cfg = WorldConfig(
        capacity=64,
        grid=GridSpec(radius=radius, extent_x=tile_w + 2 * radius,
                      extent_z=60.0),
    )
    mesh = make_mesh(n_dev)
    w = World(cfg, n_spaces=n_dev, mesh=mesh, megaspace=True,
              halo_cap=32, migrate_cap=16)
    w.register_space("Mega", Space, megaspace=True)
    w.register_entity("Npc", Npc)
    w.create_nil_space()
    sp = w.create_space("Mega")
    # a deliberate hotspot: every NPC on tile 0
    for i in range(12):
        w.create_entity("Npc", space=sp,
                        pos=(2.0 + (i % 4), 0.0, 5.0 + i // 4),
                        moving=False)
    for _ in range(4):
        w.tick()
    lanes = w._telem_lanes
    # the mega comms lanes ride the live carry
    for nm in ("halo_demand", "migrate_demand", "migrate_dropped"):
        assert sum(lanes[nm]["counts"]) == 4
    assert lanes["occupancy"]["per_tile"] == [12, 0, 0, 0]
    sig = w.workload_signature()
    assert sig["tiles"] == n_dev
    assert sig["skew"] == "hotspot"
    assert "skew=hotspot" in sig["sig"]


# =======================================================================
# acceptance: live GameServer -> /workload + /incidents
# =======================================================================
def test_live_game_serves_workload_and_incidents():
    """ISSUE 11 acceptance: a live (non-bench) GameServer accumulates
    device telemetry per tick, serves its workload signature at
    /workload, and an induced SLO breach (a tick budget far below a
    real tick) freezes a correlated bundle retrievable at
    /incidents."""
    from goworld_tpu.net.game import GameServer
    from goworld_tpu.net.standalone import ClusterHarness

    flightrec.reset()
    harness = ClusterHarness(n_dispatchers=1, n_gates=1,
                             desired_games=1)
    harness.start()
    world = _world(n=16)
    # budget ~0.05 ms/tick: every real tick (ms-scale on CPU) breaches
    gs = GameServer(1, world, list(harness.dispatcher_addrs),
                    tick_interval=5e-5, gc_freeze_on_boot=False,
                    flightrec_cooldown_secs=0.2)
    gs.start_network()
    t = threading.Thread(target=gs.serve_forever, daemon=True)
    t.start()
    srv = debug_http.start(0, process_name="game1")
    try:
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if gs.flightrec is not None \
                    and gs.flightrec.snapshot()["incident_count"] >= 1 \
                    and world.tick_count >= 65:
                break
            time.sleep(0.05)
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/workload") as resp:
            wl = json.loads(resp.read().decode())
        assert wl["game_id"] == 1
        assert wl["density"] == "exact"
        assert "recommendation" in wl and "sig" in wl
        assert wl["ticks"] > 0

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/incidents") as resp:
            inc = json.loads(resp.read().decode())
        rec = inc["game1"]
        assert rec["incident_count"] >= 1
        triggers = {b["trigger"] for b in rec["incidents"]}
        assert "slo_breach" in triggers
        bundle = next(b for b in rec["incidents"]
                      if b["trigger"] == "slo_breach")
        # the bundle is CORRELATED: per-tick frames around the breach
        # + freeze-time context with the resolved kernel config
        assert bundle["frames"]
        last = bundle["frames"][-1]
        assert last["tick_ms"] > last["budget_ms"]
        assert "sweep_impl=" in bundle["context"]["kernel_config"]
        assert "stage" in last and "over_cap" in last
        # the signature refresh cadence stamped signature marks into
        # the frame stream (tick 64+ reached above)
        snap = gs.flightrec.snapshot(frames=True)
        assert any("signature" in f for f in snap["live_frames"]) \
            or any("signature" in f for b in rec["incidents"]
                   for f in b["frames"])
    finally:
        srv.shutdown()
        srv.server_close()
        gs.stop()
        t.join(timeout=5)
        harness.stop()
        flightrec.reset()
