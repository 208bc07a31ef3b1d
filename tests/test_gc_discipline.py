"""The gc.freeze boot discipline must not leak destroyed entities.

The game logic loop freezes boot-time objects out of the cyclic GC
(net/game.py serve_forever, ini gc_freeze) so gen-2 collections stop
walking the whole world (~100 ms at a 131K shard, host clock). Frozen objects can then ONLY be reclaimed by
refcounting, so a destroyed entity must not sit in a reference cycle:
destroy_entity severs the attr tree's back-references (attrs.sever_tree
— the root journal closure holds the entity, and every nested node
holds its parent)."""

import gc
import weakref

import pytest

from goworld_tpu.core.state import WorldConfig
from goworld_tpu.entity.attrs import MapAttr, sever_tree
from goworld_tpu.entity.entity import Entity
from goworld_tpu.entity.manager import World
from goworld_tpu.entity.space import Space
from goworld_tpu.ops.aoi import GridSpec


class Npc(Entity):
    ATTRS = {"bag": "client persistent", "hp": "client hot:0"}


class Arena(Space):
    pass


def _world(n=64):
    cfg = WorldConfig(
        capacity=n,
        grid=GridSpec(radius=10.0, extent_x=100.0, extent_z=100.0,
                      k=8, cell_cap=16, row_block=n),
        enter_cap=256, leave_cap=256, sync_cap=256,
        attr_sync_cap=16, input_cap=n, delta_rows_cap=n,
    )
    world = World(cfg, n_spaces=1)
    world.register_space("Arena", Arena)
    world.register_entity("Npc", Npc)
    world.create_nil_space()
    return world, world.create_space("Arena")


def test_destroyed_frozen_entity_is_refcount_reclaimable():
    world, arena = _world()
    e = world.create_entity("Npc", space=arena, pos=(5.0, 0.0, 5.0))
    # nested attr tree: parent<->child pointer cycles inside the tree
    e.attrs["bag"] = {"slots": [1, 2, 3], "gold": {"amount": 9}}
    eid = e.id

    # simulate the logic loop's boot discipline: everything alive now
    # (including e) becomes permanent — only refcounting can free it
    gc.collect()
    gc.freeze()
    try:
        ref = weakref.ref(e)
        world.destroy_entity(e)
        # tick twice: the slot-release quarantine holds the host object
        # until its leave events have decoded
        world.tick()
        world.tick()
        assert eid not in world.entities
        del e
        # NO gc.collect() here — frozen objects wouldn't get one. If
        # the cycle weren't severed, the weakref would still be alive.
        assert ref() is None, "destroyed frozen entity leaked (cycle)"
    finally:
        gc.unfreeze()


def test_sever_tree_breaks_all_back_references():
    deltas = []
    from goworld_tpu.entity.attrs import make_root
    root = make_root(deltas.append)
    root["m"] = {"a": [1, {"b": 2}]}
    m = root["m"]
    lst = m["a"]
    inner = lst[1]
    sever_tree(root)
    assert root._root_cb is None
    assert m.parent is None and lst.parent is None \
        and inner.parent is None
    # reads still work; mutations no longer journal
    assert m.to_dict() == {"a": [1, {"b": 2}]}
    n0 = len(deltas)
    m["c"] = 1
    assert len(deltas) == n0


def test_class_patched_aoi_hook_after_registration_fires():
    """Patching the hook on the CLASS after register_entity must also
    fire (the decode's per-class override cache is rebuilt every tick,
    not at registration)."""
    world, arena = _world()

    class Patched(Npc):
        pass

    world.register_entity("Patched", Patched)
    a = world.create_entity("Patched", space=arena, pos=(5.0, 0.0, 5.0))
    b = world.create_entity("Patched", space=arena, pos=(6.0, 0.0, 6.0))
    seen = []
    Patched.OnEnterAOI = lambda self, other: seen.append(
        (self.id, other.id))
    try:
        world.tick()
        world.tick()
    finally:
        del Patched.OnEnterAOI
    assert (a.id, b.id) in seen and (b.id, a.id) in seen


def test_instance_assigned_aoi_hook_still_fires():
    """The per-type has_enter_hook fast path must not skip hooks bound
    on an INSTANCE (walker.OnEnterAOI = fn — the multihost worker
    pattern)."""
    world, arena = _world()
    a = world.create_entity("Npc", space=arena, pos=(5.0, 0.0, 5.0))
    b = world.create_entity("Npc", space=arena, pos=(6.0, 0.0, 6.0))
    seen = []
    a.OnEnterAOI = lambda other: seen.append(other.id)
    world.tick()
    world.tick()
    assert b.id in seen
