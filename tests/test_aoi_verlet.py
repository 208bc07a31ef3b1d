"""Verlet skin reuse (GridSpec.skin > 0): EXACT front-half skipping.

The acceptance bar is zero interest-set divergence vs a per-tick
rebuild — the skin is a cadence optimization, never an approximation.
These tests drive multi-tick random walks through the cached path and
assert bit-parity with the stateless sweep every tick, plus every
rebuild trigger: displacement past skin/2, alive-set changes
(spawn/despawn), watch-radius changes, the rebuild_every_max backstop,
and the candidate-cap overflow gauge.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from goworld_tpu.core.state import WorldConfig, create_state, despawn, \
    spawn
from goworld_tpu.core.step import TickInputs, make_tick
from goworld_tpu.ops.aoi import (
    GridSpec,
    grid_neighbors_flags,
    grid_neighbors_verlet,
    init_verlet_cache,
)

N = 500
EXTENT = 300.0


def _spec(skin, **kw):
    base = dict(radius=25.0, extent_x=EXTENT, extent_z=EXTENT, k=48,
                cell_cap=48, row_block=128, verlet_cap=96)
    base.update(kw)
    return GridSpec(**base, skin=skin)


def _world(seed=0):
    rng = np.random.default_rng(seed)
    pos = np.zeros((N, 3), np.float32)
    pos[:, 0] = rng.random(N) * EXTENT
    pos[:, 2] = rng.random(N) * EXTENT
    alive = rng.random(N) < 0.9
    fb = rng.integers(0, 4, N).astype(np.int32)
    return rng, pos, alive, fb


def _both(spec, spec0, pos, alive, fb, cache):
    out = grid_neighbors_verlet(
        spec, jnp.asarray(pos), jnp.asarray(alive), cache,
        flag_bits=jnp.asarray(fb), with_stats=True,
    )
    ref = grid_neighbors_flags(
        spec0, jnp.asarray(pos), jnp.asarray(alive),
        flag_bits=jnp.asarray(fb), with_stats=True,
    )
    return out, ref


def test_random_walk_zero_divergence_with_reuse():
    """30 small-step ticks: every tick's lists/counts/flags identical
    to the per-tick rebuild, while most ticks actually skip."""
    rng, pos, alive, fb = _world(1)
    spec, spec0 = _spec(6.0), _spec(0.0)
    cache = init_verlet_cache(spec, N)
    rebuilds = 0
    for t in range(30):
        out, ref = _both(spec, spec0, pos, alive, fb, cache)
        nbr, cnt, fl, stats, cache, reb, slack = out
        rebuilds += int(reb)
        assert np.array_equal(np.asarray(nbr), np.asarray(ref[0])), t
        assert np.array_equal(np.asarray(cnt), np.asarray(ref[1])), t
        assert np.array_equal(np.asarray(fl), np.asarray(ref[2])), t
        step = rng.normal(0, 0.35, (N, 2)).astype(np.float32)
        pos[:, 0] = np.clip(pos[:, 0] + step[:, 0], 0, EXTENT - 1e-3)
        pos[:, 2] = np.clip(pos[:, 2] + step[:, 1], 0, EXTENT - 1e-3)
        fb = rng.integers(0, 4, N).astype(np.int32)
    assert rebuilds >= 1                      # cold cache built once
    assert rebuilds < 15, f"reuse never kicked in ({rebuilds}/30)"


def test_teleport_forces_rebuild_and_stays_exact():
    rng, pos, alive, fb = _world(2)
    spec, spec0 = _spec(6.0), _spec(0.0)
    cache = init_verlet_cache(spec, N)
    (nbr, _c, _f, _s, cache, reb, _sl), _ = _both(
        spec, spec0, pos, alive, fb, cache)
    assert int(reb) == 1
    # one entity jumps across the world (>> skin/2)
    pos[7, 0] = (pos[7, 0] + EXTENT / 2) % EXTENT
    out, ref = _both(spec, spec0, pos, alive, fb, cache)
    nbr, cnt, fl, _s, cache, reb, slack = out
    assert int(reb) == 1 and float(slack) < 0
    assert np.array_equal(np.asarray(nbr), np.asarray(ref[0]))


def test_alive_change_forces_rebuild_and_stays_exact():
    rng, pos, alive, fb = _world(3)
    spec, spec0 = _spec(6.0), _spec(0.0)
    cache = init_verlet_cache(spec, N)
    (_n, _c, _f, _s, cache, _r, _sl), _ = _both(
        spec, spec0, pos, alive, fb, cache)
    dead = np.nonzero(alive)[0][3]
    born = np.nonzero(~alive)[0][0]
    alive = alive.copy()
    alive[dead] = False                       # despawn
    alive[born] = True                        # spawn into a free slot
    out, ref = _both(spec, spec0, pos, alive, fb, cache)
    nbr, cnt, _f, _s, cache, reb, _sl = out
    assert int(reb) == 1
    assert np.array_equal(np.asarray(nbr), np.asarray(ref[0]))
    assert np.array_equal(np.asarray(cnt), np.asarray(ref[1]))
    # the new entity is visible, the dead one is gone, everywhere
    assert not np.any(np.asarray(nbr) == dead)


def test_watch_radius_change_forces_rebuild():
    rng, pos, alive, fb = _world(4)
    spec, spec0 = _spec(6.0), _spec(0.0)
    wr = np.full(N, np.inf, np.float32)
    cache = init_verlet_cache(spec, N)
    out = grid_neighbors_verlet(
        spec, jnp.asarray(pos), jnp.asarray(alive), cache,
        watch_radius=jnp.asarray(wr), flag_bits=jnp.asarray(fb))
    cache = out[4]
    wr2 = wr.copy()
    watcher = np.nonzero(alive)[0][0]
    wr2[watcher] = 5.0                        # shrink one view distance
    out = grid_neighbors_verlet(
        spec, jnp.asarray(pos), jnp.asarray(alive), cache,
        watch_radius=jnp.asarray(wr2), flag_bits=jnp.asarray(fb))
    nbr, cnt, _f, _s, cache, reb, _sl = out
    assert int(reb) == 1
    ref = grid_neighbors_flags(
        spec0, jnp.asarray(pos), jnp.asarray(alive),
        watch_radius=jnp.asarray(wr2), flag_bits=jnp.asarray(fb))
    assert np.array_equal(np.asarray(nbr), np.asarray(ref[0]))


def test_rebuild_every_max_backstop():
    rng, pos, alive, fb = _world(5)
    spec = _spec(50.0, rebuild_every_max=4)   # huge skin: displacement
    cache = init_verlet_cache(spec, N)        # never triggers
    pattern = []
    for t in range(9):
        out = grid_neighbors_verlet(
            spec, jnp.asarray(pos), jnp.asarray(alive), cache,
            flag_bits=jnp.asarray(fb))
        cache = out[4]
        pattern.append(int(out[5]))
    assert pattern == [1, 0, 0, 0, 1, 0, 0, 0, 1]


def test_candidate_overflow_fires_over_k_gauge():
    """verlet_cap too small for the density: the stats must say so
    (the only regime where the skin may diverge is gauged, mirroring
    the k/cell_cap contract)."""
    rng = np.random.default_rng(6)
    m = 64
    pos = np.zeros((m, 3), np.float32)
    pos[:, 0] = 50.0 + rng.random(m) * 4.0    # one dense blob
    pos[:, 2] = 50.0 + rng.random(m) * 4.0
    alive = np.ones(m, bool)
    spec = GridSpec(radius=25.0, extent_x=100.0, extent_z=100.0,
                    k=8, cell_cap=64, row_block=64, skin=5.0,
                    verlet_cap=16)            # demand is ~63 per row
    cache = init_verlet_cache(spec, m)
    out = grid_neighbors_verlet(
        spec, jnp.asarray(pos), jnp.asarray(alive), cache,
        flag_bits=jnp.zeros(m, jnp.int32), with_stats=True)
    stats = out[3]
    assert int(stats[1]) > 0                  # over-cap rows reported


def test_tick_body_integration_bit_parity_and_gauges():
    """make_tick with skin vs without: identical neighbor state and
    event counts every tick (random_walk velocities don't read nbr, so
    the two configs' trajectories coincide), and the outputs carry the
    rebuild/slack gauges."""
    def run(skin):
        cfg = WorldConfig(
            capacity=256,
            grid=_spec(skin, row_block=256),
            npc_speed=5.0,
        )
        st = create_state(cfg, seed=9)
        rng = np.random.default_rng(8)
        for s in range(120):
            st = spawn(st, s, pos=(rng.random() * EXTENT, 0.0,
                                   rng.random() * EXTENT),
                       npc_moving=True)
        tick = make_tick(cfg)
        ins = TickInputs.empty(cfg)
        rebuilds, outs = 0, []
        for t in range(20):
            st, out = tick(st, ins, None)
            rebuilds += int(out.aoi_rebuilt)
            outs.append((
                np.asarray(st.nbr), np.asarray(st.nbr_cnt),
                int(out.enter_n), int(out.leave_n), int(out.sync_n),
            ))
            if t == 9:
                st = despawn(st, 3)           # mid-run alive change
        return rebuilds, outs

    reb0, a = run(0.0)
    reb1, b = run(5.0)
    assert reb0 == 20                         # skinless: every tick
    assert 2 <= reb1 < 20                     # cold + despawn, then reuse
    for t, (oa, ob) in enumerate(zip(a, b)):
        assert np.array_equal(oa[0], ob[0]), f"nbr diverged @ tick {t}"
        assert np.array_equal(oa[1], ob[1]), f"cnt diverged @ tick {t}"
        assert oa[2:] == ob[2:], f"event counts diverged @ tick {t}"


@pytest.mark.scenarios
def test_scenario_teleport_flips_rebuild_cond_on_exact_tick():
    """ISSUE 7 regression: under the teleport scenario kernel a jump
    (>> skin/2: uniform over the world) must flip the in-graph rebuild
    cond ON THAT TICK, while the walk drift between jumps stays under
    skin/2 and correctly does NOT rebuild. The jump ticks are
    CONSTRUCTED, not drawn: slot 0 is the one teleport member
    (teleport_prob = 1, so it jumps on exactly the ticks it is
    switched to moving), every other slot random-walks. The cond is
    also predicted tick-by-tick by mirroring the cache contract
    host-side (max Chebyshev displacement since the last rebuild vs
    skin/2), and every tick stays bit-identical to the skinless
    sweep."""
    from goworld_tpu.scenarios.spec import ScenarioSpec

    cap, live, ext, skin = 64, 48, 150.0, 8.0
    jump_ticks = {4, 9, 10, 17, 22}
    spec = ScenarioSpec(name="tp_exact_tick",
                        mix=(("teleport", 0.5), ("random_walk", 0.5)),
                        teleport_prob=1.0)

    def mk(skin_v):
        return WorldConfig(
            capacity=cap,
            grid=GridSpec(radius=20.0, extent_x=ext, extent_z=ext,
                          k=16, cell_cap=48, row_block=cap,
                          verlet_cap=63, skin=skin_v),
            npc_speed=1.0,       # drift/tick = dt << skin/2
            scenario=spec,
        )

    cfg, cfg0 = mk(skin), mk(0.0)
    # slot 0 teleports (mix index 0), everyone else walks (index 1)
    lanes = jnp.ones((cap,), jnp.int32).at[0].set(0)
    st = create_state(cfg, seed=21).replace(behavior_id=lanes)
    st0 = create_state(cfg0, seed=21).replace(behavior_id=lanes)
    rng = np.random.default_rng(21)
    for s in range(live):
        p = (rng.random() * ext, 0.0, rng.random() * ext)
        st = spawn(st, s, pos=p, npc_moving=s > 0)
        st0 = spawn(st0, s, pos=p, npc_moving=s > 0)
    tick, tick0 = make_tick(cfg), make_tick(cfg0)
    ins = TickInputs.empty(cfg)

    ref = None                    # pos snapshot at the last rebuild
    for t in range(25):
        jump = t in jump_ticks
        st = st.replace(npc_moving=st.npc_moving.at[0].set(jump))
        st0 = st0.replace(npc_moving=st0.npc_moving.at[0].set(jump))
        st, out = tick(st, ins, None)
        st0, _ = tick0(st0, ins, None)
        pos = np.asarray(st.pos)[:live, ::2]
        if ref is None:
            expect = 1            # cold cache: first tick rebuilds
        else:
            disp = np.max(np.abs(pos - ref))
            expect = int(disp > skin / 2.0)
            # the construction holds: a jump tick moves slot 0 past the
            # bound, a still tick moves nobody past it
            assert expect == int(jump), (
                f"tick {t}: displacement {disp:.3f} vs skin/2 "
                f"{skin / 2.0} contradicts the schedule (jump={jump})"
            )
        assert int(out.aoi_rebuilt) == expect, (
            f"tick {t}: rebuild={int(out.aoi_rebuilt)} but the "
            f"displacement bound says {expect}"
        )
        if expect:
            ref = pos
        # the skin is exact through the churn (same rng stream -> the
        # two configs' populations coincide; teleports don't read nbr)
        assert np.array_equal(np.asarray(st.nbr), np.asarray(st0.nbr)), t
        assert np.array_equal(np.asarray(st.nbr_cnt),
                              np.asarray(st0.nbr_cnt)), t


def test_world_manager_exports_rebuild_gauges():
    """Single-space World with a skin: ticks run through the direct
    (un-vmapped) local step so the rebuild cond stays a real branch,
    and op_stats exports the cadence gauges."""
    from goworld_tpu.entity import Entity, Space, World

    cfg = WorldConfig(
        capacity=64,
        grid=GridSpec(radius=10.0, extent_x=100.0, extent_z=100.0,
                      k=8, cell_cap=32, row_block=64, skin=3.0),
    )
    w = World(cfg, n_spaces=1)
    w.register_entity("Mob", type("Mob", (Entity,), {}))
    w.register_space("Sp", type("Sp", (Space,), {}))
    w.create_nil_space()
    sp = w.create_space("Sp")
    for i in range(5):
        sp.create_entity("Mob", pos=(50 + i, 0, 50))
    for _ in range(3):
        w.tick()
    assert "aoi_rebuild_last" in w.op_stats
    assert "aoi_skin_slack" in w.op_stats
    assert w.op_stats["aoi_rebuild_last"] in (0, 1)
