"""End-to-end single-Space tick: spawn, move, AOI enter/leave, sync records.

Covers the minimal slice of the reference's game loop semantics
(GameService.go:77-190 + Entity.go AOI callbacks + CollectEntitySyncInfos)."""

import jax
import jax.numpy as jnp
import numpy as np

from goworld_tpu.core import (
    SpaceState, TickInputs, WorldConfig, create_state, make_tick,
)
from goworld_tpu.core.state import despawn, spawn
from goworld_tpu.models.npc_policy import init_policy
from goworld_tpu.ops.aoi import GridSpec


def small_cfg(**kw):
    base = dict(
        capacity=64,
        grid=GridSpec(radius=10.0, extent_x=100.0, extent_z=100.0,
                      k=16, cell_cap=32, row_block=64),
        npc_speed=5.0,
    )
    base.update(kw)
    return WorldConfig(**base)


def test_spawn_enter_leave_cycle():
    cfg = small_cfg()
    tick = make_tick(cfg)
    st = create_state(cfg)
    # two entities in AOI range, one out of range
    st = spawn(st, 0, pos=(50.0, 0, 50.0), has_client=True, client_gate=1)
    st = spawn(st, 1, pos=(55.0, 0, 52.0))
    st = spawn(st, 2, pos=(90.0, 0, 90.0))
    st, out = tick(st, TickInputs.empty(cfg), None)
    enters = {(int(w), int(j)) for w, j in
              zip(np.asarray(out.enter_w)[: int(out.enter_n)],
                  np.asarray(out.enter_j)[: int(out.enter_n)])}
    assert (0, 1) in enters and (1, 0) in enters
    assert not any(2 in p for p in enters)
    assert int(out.leave_n) == 0
    assert int(out.alive_count) == 3

    # teleport entity 1 far away via client input -> leave events
    inp = TickInputs.empty(cfg)
    inp = inp.replace(
        pos_sync_idx=inp.pos_sync_idx.at[0].set(1),
        pos_sync_vals=inp.pos_sync_vals.at[0].set(
            jnp.array([5.0, 0.0, 5.0, 1.0])),
        pos_sync_n=jnp.asarray(1, jnp.int32),
    )
    st, out = tick(st, inp, None)
    leaves = {(int(w), int(j)) for w, j in
              zip(np.asarray(out.leave_w)[: int(out.leave_n)],
                  np.asarray(out.leave_j)[: int(out.leave_n)])}
    assert (0, 1) in leaves and (1, 0) in leaves


def test_sync_records_only_for_clients_watching_dirty():
    cfg = small_cfg()
    tick = make_tick(cfg)
    st = create_state(cfg)
    st = spawn(st, 0, pos=(50.0, 0, 50.0), has_client=True)
    st = spawn(st, 1, pos=(52.0, 0, 50.0), npc_moving=True)  # NPC walks
    st = spawn(st, 2, pos=(54.0, 0, 50.0))                   # static, no client
    st, out = tick(st, TickInputs.empty(cfg), None)  # neighbors established
    st, out = tick(st, TickInputs.empty(cfg), None)
    w = np.asarray(out.sync_w)[: int(out.sync_n)]
    j = np.asarray(out.sync_j)[: int(out.sync_n)]
    assert int(out.sync_n) >= 1
    assert set(w.tolist()) == {0}          # only the client-owner watches
    assert set(j.tolist()) == {1}          # only the mover is reported
    # record carries the mover's fresh position
    vals = np.asarray(out.sync_vals)[0]
    assert np.allclose(vals[:3], np.asarray(st.pos)[1], atol=1e-5)


def test_despawn_removes_from_aoi():
    cfg = small_cfg()
    tick = make_tick(cfg)
    st = create_state(cfg)
    st = spawn(st, 0, pos=(50.0, 0, 50.0))
    st = spawn(st, 1, pos=(52.0, 0, 50.0))
    st, out = tick(st, TickInputs.empty(cfg), None)
    st = despawn(st, 1)
    st, out = tick(st, TickInputs.empty(cfg), None)
    leaves = {(int(w), int(j)) for w, j in
              zip(np.asarray(out.leave_w)[: int(out.leave_n)],
                  np.asarray(out.leave_j)[: int(out.leave_n)])}
    assert (0, 1) in leaves
    assert int(out.alive_count) == 1


def test_attr_dirty_flushed():
    cfg = small_cfg()
    tick = make_tick(cfg)
    st = create_state(cfg)
    st = spawn(st, 0, pos=(10.0, 0, 10.0))
    st = st.replace(
        hot_attrs=st.hot_attrs.at[0, 3].set(99.0),
        attr_dirty=st.attr_dirty.at[0].set(np.uint32(1 << 3)),
    )
    st, out = tick(st, TickInputs.empty(cfg), None)
    assert int(out.attr_n) == 1
    assert int(np.asarray(out.attr_e)[0]) == 0
    assert int(np.asarray(out.attr_i)[0]) == 3
    assert float(np.asarray(out.attr_v)[0]) == 99.0
    assert int(st.attr_dirty[0]) == 0  # cleared after flush


def test_mlp_behavior_compiles_and_moves():
    cfg = small_cfg(behavior="mlp")
    tick = make_tick(cfg)
    st = create_state(cfg)
    for s in range(8):
        st = spawn(st, s, pos=(40.0 + s, 0, 40.0), npc_moving=True)
    policy = init_policy(jax.random.PRNGKey(0))
    p0 = np.asarray(st.pos[:8]).copy()
    for _ in range(20):
        st, out = tick(st, TickInputs.empty(cfg), policy)
    assert not np.allclose(np.asarray(st.pos[:8]), p0)


def test_random_walk_stays_in_bounds():
    cfg = small_cfg()
    tick = make_tick(cfg)
    st = create_state(cfg)
    for s in range(16):
        st = spawn(st, s, pos=(50.0, 0, 50.0), npc_moving=True)
    for _ in range(100):
        st, _ = tick(st, TickInputs.empty(cfg), None)
    pos = np.asarray(st.pos[:16])
    assert (pos[:, 0] >= 0).all() and (pos[:, 0] <= 100.0).all()
    assert (pos[:, 2] >= 0).all() and (pos[:, 2] <= 100.0).all()


# first words of the stream a seed draws (threefry2x32, partitionable —
# core/state.seed_key). chip_smoke.py checks the same words on the chip.
SEED7_SPLIT1 = (195045567, 4062205631)
SEED7_BITS = (2899676959, 3548400998, 1692160380, 1822441453)


def test_seed_stream_is_pinned():
    """``--seed`` must mean one world: the key derivation is one
    helper and its stream is pinned, so a JAX default that changes it
    (as jax_threefry_partitionable did between 0.4 and 0.5) fails HERE
    instead of shifting every seeded world quietly."""
    from goworld_tpu.core.state import seed_key

    _, k = jax.random.split(seed_key(7))
    assert tuple(np.asarray(k).tolist()) == SEED7_SPLIT1
    assert tuple(np.asarray(
        jax.random.bits(k, (4,))).tolist()) == SEED7_BITS
    cfg = small_cfg()
    assert np.array_equal(np.asarray(create_state(cfg, seed=7).rng),
                          np.asarray(seed_key(7)))
