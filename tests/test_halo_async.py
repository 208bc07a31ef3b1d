"""halo_impl parity: the async (Pallas make_async_remote_copy, packed
dirty-only payload) halo exchange must be BIT-identical to the ppermute
impl — ghost blocks AND demand gauges — for 1D strips and 2D tiles,
across dirty/visible permutations and halo_cap overflow (ISSUE 10).

Off-TPU the async kernel runs in interpret mode behind
ops/pallas_compat.resolve_interpret (one-time warning, never a CPU
default) — exactly the configuration tier-1 exercises here.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from goworld_tpu.parallel.halo import (  # noqa: E402
    exchange_halo,
    exchange_halo_2d,
    meta_gid_bound,
)
from goworld_tpu.parallel.mesh import (  # noqa: E402
    SPACE_AXIS,
    make_mesh,
    shard_map_norep,
)

pytestmark = [pytest.mark.pallas, pytest.mark.multichip]

N_DEV = 8
N = 64
TILE_W = 100.0
TILE_D = 100.0
RADIUS = 25.0  # wide strips: plenty of rows to permute and overflow


def _world(seed: int, dirty_frac: float, alive_frac: float,
           two_d: bool):
    """Random per-shard world arrays in GLOBAL coordinates, leading
    [n_dev] axis."""
    rng = np.random.default_rng(seed)
    tx, tz = (4, 2) if two_d else (N_DEV, 1)
    pos = np.zeros((N_DEV, N, 3), np.float32)
    for d in range(N_DEV):
        ix, iz = d // tz, d % tz
        pos[d, :, 0] = ix * TILE_W + rng.uniform(0, TILE_W, N)
        pos[d, :, 2] = (iz * TILE_D + rng.uniform(0, TILE_D, N)
                        if two_d else rng.uniform(0, TILE_D, N))
    yaw = rng.uniform(-np.pi, np.pi, (N_DEV, N)).astype(np.float32)
    dirty = rng.uniform(size=(N_DEV, N)) < dirty_frac
    alive = rng.uniform(size=(N_DEV, N)) < alive_frac
    return (jnp.asarray(pos), jnp.asarray(yaw), jnp.asarray(dirty),
            jnp.asarray(alive))


def _exchange(impl: str, two_d: bool, halo_cap: int, world):
    mesh = make_mesh(N_DEV)

    def fn(pos, yaw, dirty, alive):
        pos, yaw, dirty, alive = pos[0], yaw[0], dirty[0], alive[0]
        if two_d:
            out = exchange_halo_2d(
                SPACE_AXIS, (4, 2), N, pos, yaw, dirty, alive,
                TILE_W, TILE_D, RADIUS, halo_cap, impl=impl,
            )
        else:
            out = exchange_halo(
                SPACE_AXIS, N_DEV, pos, yaw, dirty, alive,
                TILE_W, RADIUS, halo_cap, impl=impl,
            )
        return jax.tree.map(lambda x: x[None], out)

    mapped = shard_map_norep(
        fn, mesh=mesh, in_specs=(P(SPACE_AXIS),) * 4,
        out_specs=P(SPACE_AXIS),
    )
    return [np.asarray(x) for x in jax.jit(mapped)(*world)]


NAMES = ("gpos", "gyaw", "gdirty", "gvalid", "ggid", "strip_demand")


@pytest.mark.parametrize("two_d", [False, True], ids=["1d", "2d"])
@pytest.mark.parametrize("dirty_frac,alive_frac", [
    (0.0, 1.0),    # nobody dirty
    (1.0, 1.0),    # everybody dirty
    (0.4, 0.7),    # mixed dirty + dead rows (visibility filter)
], ids=["clean", "all-dirty", "mixed"])
def test_async_bit_identical(two_d, dirty_frac, alive_frac):
    world = _world(3, dirty_frac, alive_frac, two_d)
    ref = _exchange("ppermute", two_d, 32, world)
    got = _exchange("async", two_d, 32, world)
    for name, r, g in zip(NAMES, ref, got):
        assert r.dtype == g.dtype, name
        assert np.array_equal(r, g), (
            f"{name} diverges between impls "
            f"({(r != g).sum()} of {r.size} lanes)"
        )


@pytest.mark.parametrize("two_d", [False, True], ids=["1d", "2d"])
def test_async_bit_identical_under_overflow(two_d):
    """halo_cap far below the strip occupancy: the overflow rows must
    drop IDENTICALLY (bounded_extract slot order is shared) and the
    demand gauge must report the same true occupancy."""
    world = _world(7, 0.5, 1.0, two_d)
    cap = 4   # RADIUS/TILE_W = 25% of 64 rows per strip >> 4
    ref = _exchange("ppermute", two_d, cap, world)
    got = _exchange("async", two_d, cap, world)
    for name, r, g in zip(NAMES, ref, got):
        assert np.array_equal(r, g), f"{name} diverges under overflow"
    demand = ref[-1]
    assert (demand > cap).any(), (
        "overflow case never exceeded halo_cap — the test shape is "
        "not exercising the drop path"
    )


def test_async_ghosts_nonempty():
    """The parity cases must actually ship ghosts (an all-empty
    exchange would pass parity vacuously)."""
    world = _world(3, 0.4, 0.7, False)
    got = _exchange("async", False, 32, world)
    gvalid = got[3]
    assert gvalid.any(), "no ghosts shipped at 25% strip width"
    # interior shards receive from both sides
    assert gvalid[3].any() and gvalid[4].any()


def test_meta_gid_bound_guard():
    """MegaConfig refuses async when gids overflow the packed meta
    word (the 29-bit bound halo._pack_strip documents)."""
    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.ops.aoi import GridSpec
    from goworld_tpu.parallel.megaspace import MegaConfig

    cap = (meta_gid_bound() // 2) + 1  # 2 devices -> gids past bound
    cfg = WorldConfig(
        capacity=cap,
        grid=GridSpec(radius=10.0, extent_x=120.0, extent_z=100.0,
                      k=8, cell_cap=16, row_block=1024),
    )
    with pytest.raises(ValueError, match="29-bit"):
        MegaConfig(cfg=cfg, n_dev=2, tile_w=100.0, halo_impl="async")
    with pytest.raises(ValueError, match="halo_impl"):
        MegaConfig(cfg=WorldConfig(
            capacity=64,
            grid=GridSpec(radius=10.0, extent_x=120.0, extent_z=100.0,
                          k=8, cell_cap=16, row_block=64),
        ), n_dev=2, tile_w=100.0, halo_impl="bogus")
