"""Ask the chip's compiler, without the chip.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
v5e that is described, not attached (on-chip-measurement guide §2.3).
These tests hold what interpret mode cannot show: that the served tick
and every Pallas kernel in the tree either COMPILE for a TPU v5e at the
real shard (n = 131,072; the tick at the library defaults k/cell_cap =
64/32, the kernels also at the bench shapes 32/12), or are REFUSED on a
TPU backend by their option's validation. A compile that passes here is not a chip run —
``chip_smoke.py`` is.

Rules this file keeps (the driver runs tier-1 under pytest-xdist, and
only one process may hold libtpu):

* every compile lives in THIS file, so one worker loads the library;
* the topology is described inside a module-scoped fixture that skips
  when it cannot be — never at import, in a ``skipif``, in
  ``parametrize`` arguments or in conftest; no ``autouse``; no child
  process;
* ``pallas_compat.on_tpu`` is steered from here (monkeypatch) — the
  program gets no option for it;
* the persistent compile cache is off around the compiles: an entry
  written for a described device cannot be read back without one.
"""

import contextlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from goworld_tpu.core.state import WorldConfig
from goworld_tpu.ops import pallas_compat
from goworld_tpu.ops.aoi import GridSpec
from goworld_tpu.utils.devprof import device_peaks

N = 131072
RADIUS = 50.0
EXTENT = 10451.0          # ~12 Chebyshev neighbours at radius 50
HBM_BYTES = int(device_peaks("TPU v5 lite")["hbm_gb"] * 10**9)
# (k, cell_cap) the kernels are asked at: bench.py's shard shapes, then
# the library defaults
SHAPES = [(32, 12), (64, 32)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    from goworld_tpu.parallel.mesh import SPACE_AXIS

    return Mesh(np.asarray(topo.devices), (SPACE_AXIS,))


@contextlib.contextmanager
def _as_the_chip(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pallas_compat, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture()
def for_tpu(monkeypatch):
    """Compile as the chip would: hardware lowering for every Pallas
    kernel, persistent cache off."""
    with _as_the_chip(monkeypatch):
        yield


def _shaped(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)


@pytest.fixture(scope="module")
def served_tick(one_chip):
    """The main path: the step ``World.tick()`` dispatches (one space,
    every kernel choice at its library default, the carry donated), at
    the real shard. Compiled once for the tests that read it: it is
    most of this file's time."""
    from goworld_tpu.core.step import TickInputs
    from goworld_tpu.entity.manager import _make_local_tick
    from goworld_tpu.parallel.mesh import create_multi_state

    cfg = WorldConfig(capacity=N, grid=GridSpec(
        radius=RADIUS, extent_x=EXTENT, extent_z=EXTENT))
    state = _shaped(jax.eval_shape(lambda: create_multi_state(cfg, 1)),
                    one_chip)
    inputs = _shaped(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x[None], TickInputs.empty(cfg))), one_chip)
    with pytest.MonkeyPatch.context() as mp, _as_the_chip(mp):
        compiled = _make_local_tick(cfg, 1, donate=True) \
            .lower(state, inputs, None).compile()
    return cfg, compiled


def test_served_tick_compiles_for_v5e(served_tick):
    cfg, compiled = served_tick
    m = compiled.memory_analysis()
    print(f"\nserved tick n={N} k={cfg.grid.k} "
          f"cell_cap={cfg.grid.cell_cap}: {m}")
    assert m.alias_size_in_bytes > 0, "the donated carry did not alias"
    assert _device_bytes(compiled) < HBM_BYTES


def test_served_tick_window_fetch_is_no_loop(served_tick):
    """The default sweep's window fetch stays one row gather per query
    (ISSUE 26): under ``ranges``/``table`` the v5e compiler expands the
    per-query windowed ``dynamic_slice`` into a ``while`` of ~1.2
    million tiny slices a tick, named ``.../gw.aoi.gather/vmap(vmap())/
    gather`` — 91% of the served tick's device time on the chip
    (PERF.md, PR 25). A change that brings it back fails here, on the
    CPU host, before a chip is asked."""
    cfg, compiled = served_tick
    loops = []
    for line in compiled.as_text().splitlines():
        if re.search(r"\bwhile\(", line):
            name = re.search(r'op_name="([^"]*)"', line)
            loops.append(name.group(1) if name else "")
    assert loops, "no while at all: the row blocks' lax.map is one"
    under = [n for n in loops if "gw.aoi.gather" in n.split("/")]
    assert not under, f"the window fetch is a loop again: {under}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"\nserved tick sweep_impl={cfg.grid.sweep_impl}: "
          f"temporaries {temp:,} bytes, loops {loops}")
    # the premerged block and one row block's window, and no more than
    # the expanded gather's temporaries were (657 MB)
    assert temp < 512 * 10**6


def test_counting_sort_pallas_compiles_for_v5e(one_chip, for_tpu):
    """sort_impl='pallas' at the shard's bin space (the cell edge is
    the radius, so both shape sets share it)."""
    from goworld_tpu.ops.sort import counting_sort_cells_pallas

    spec = GridSpec(radius=RADIUS, extent_x=EXTENT, extent_z=EXTENT)
    n_rows = (spec.cells_x + 2) * (spec.cells_z + 2)
    compiled = jax.jit(
        lambda srow: counting_sort_cells_pallas(srow, n_rows)
    ).lower(jax.ShapeDtypeStruct((N,), jnp.int32,
                                 sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,cell_cap", SHAPES)
def test_fused_sweep_is_refused_on_tpu(one_chip, for_tpu, monkeypatch,
                                       k, cell_cap):
    """sweep_impl='fused': the v5e compiler rejects the kernel, so the
    option is refused on a TPU backend with the compiler's reason — and
    the reason is still true (if this compile starts passing, lift the
    refusal in ops/pallas_compat.py instead of editing this test)."""
    kw = dict(radius=RADIUS, extent_x=EXTENT, extent_z=EXTENT, k=k,
              cell_cap=cell_cap, sweep_impl="fused")
    with pytest.raises(ValueError, match="multiple of 128"):
        GridSpec(**kw)

    from goworld_tpu.ops.aoi import _sweep_fused

    # build the spec as a CPU process would, then compile its kernel
    # as the chip would
    with monkeypatch.context() as mp:
        mp.setattr(pallas_compat, "on_tpu", lambda: False)
        spec = GridSpec(**kw)

    def sweep(pos, alive, radius, flags):
        return _sweep_fused(spec, pos, alive, None, radius, flags, True)

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
              for s, d in (((N, 3), jnp.float32), ((N,), jnp.bool_),
                           ((N,), jnp.float32), ((N,), jnp.int32))]
    with pytest.raises(Exception, match="128"):
        jax.jit(sweep).lower(*shapes).compile()


def _mega_config(halo_impl: str):
    from goworld_tpu.parallel.megaspace import MegaConfig

    cfg = WorldConfig(capacity=N, grid=GridSpec(
        radius=RADIUS, extent_x=EXTENT + 2 * RADIUS,
        extent_z=EXTENT + 2 * RADIUS))
    return MegaConfig(cfg=cfg, n_dev=4, tile_w=EXTENT, tile_d=EXTENT,
                      mesh_shape=(2, 2), halo_cap=4096,
                      migrate_cap=1024, halo_impl=halo_impl)


def test_mega_tick_async_halo_compiles_on_v5e_2x2(mesh4, for_tpu):
    """The megaspace tick over the described 2x2 mesh with the Pallas
    remote-DMA halo inside it: 131,072 per tile, 524,288 entities."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from goworld_tpu.parallel.megaspace import (
        create_mega_state,
        make_mega_tick,
    )
    from goworld_tpu.parallel.mesh import SPACE_AXIS
    from goworld_tpu.parallel.step import MultiTickInputs

    mc = _mega_config("async")
    sh = NamedSharding(mesh4, P(SPACE_AXIS))
    state = _shaped(jax.eval_shape(lambda: create_mega_state(mc)), sh)
    inputs = _shaped(jax.eval_shape(
        lambda: MultiTickInputs.empty(mc.cfg, 4)), sh)
    compiled = make_mega_tick(mc, mesh4, donate=True) \
        .lower(state, inputs, None).compile()
    text = compiled.as_text()
    print(f"\nmega tick 2x2 n/tile={N}: {compiled.memory_analysis()}")
    assert "tpu_custom_call" in text      # the async halo DMAs
    assert "all-to-all" in text           # tile migration
    assert "collective-permute" not in text
    assert _device_bytes(compiled) < HBM_BYTES


def test_ppermute_halo_compiles_on_v5e_2x2(mesh4, for_tpu):
    """The default halo beside it: the same 2D exchange as barriered
    collectives (the whole ppermute mega tick is what
    ``chip_smoke.py --chips 4`` runs on the chips)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from goworld_tpu.parallel.halo import exchange_halo_2d
    from goworld_tpu.parallel.mesh import SPACE_AXIS, shard_map

    mc = _mega_config("ppermute")

    def shard_fn(pos, yaw, dirty, alive):
        out = exchange_halo_2d(
            SPACE_AXIS, (2, 2), N, pos[0], yaw[0], dirty[0], alive[0],
            mc.tile_w, mc.tile_d, RADIUS, mc.halo_cap,
            impl="ppermute")
        return jax.tree.map(lambda x: x[None], out)

    mapped = shard_map(shard_fn, mesh=mesh4,
                       in_specs=(P(SPACE_AXIS),) * 4,
                       out_specs=P(SPACE_AXIS))
    sh = NamedSharding(mesh4, P(SPACE_AXIS))
    shapes = [jax.ShapeDtypeStruct((4,) + s, d, sharding=sh)
              for s, d in (((N, 3), jnp.float32), ((N,), jnp.float32),
                           ((N,), jnp.bool_), ((N,), jnp.bool_))]
    text = jax.jit(mapped).lower(*shapes).compile().as_text()
    assert "collective-permute" in text
    assert "tpu_custom_call" not in text
