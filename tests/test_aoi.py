"""Grid AOI kernel vs the NumPy oracle (reference semantics: Chebyshev XZ
interest within per-space radius, go-aoi XZList — Space.go:91-106)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from goworld_tpu.ops.aoi import GridSpec, grid_neighbors, neighbors_oracle


def random_world(n, seed, extent=200.0, alive_frac=1.0):
    rng = np.random.default_rng(seed)
    pos = np.zeros((n, 3), np.float32)
    pos[:, 0] = rng.uniform(0, extent, n)
    pos[:, 1] = rng.uniform(0, 10, n)
    pos[:, 2] = rng.uniform(0, extent, n)
    alive = rng.uniform(size=n) < alive_frac
    return pos, alive


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("alive_frac", [1.0, 0.7])
def test_grid_matches_oracle(seed, alive_frac):
    n = 300
    radius = 25.0
    pos, alive = random_world(n, seed, alive_frac=alive_frac)
    # caps chosen large enough for exactness at this density
    spec = GridSpec(
        radius=radius, extent_x=200.0, extent_z=200.0,
        k=128, cell_cap=128, row_block=128,
    )
    nbr, cnt = jax.jit(grid_neighbors, static_argnums=0)(
        spec, jnp.asarray(pos), jnp.asarray(alive)
    )
    nbr, cnt = np.asarray(nbr), np.asarray(cnt)
    oracle = neighbors_oracle(pos, alive, radius)
    for i in range(n):
        got = set(nbr[i][nbr[i] < n].tolist())
        assert len(got) == cnt[i]
        assert got == oracle[i], f"row {i}"


def test_sorted_and_sentinel_padded():
    n = 200
    pos, alive = random_world(n, 3)
    spec = GridSpec(radius=30.0, extent_x=200.0, extent_z=200.0,
                    k=64, cell_cap=64, row_block=64)
    nbr, cnt = grid_neighbors(spec, jnp.asarray(pos), jnp.asarray(alive))
    nbr = np.asarray(nbr)
    assert (np.diff(nbr, axis=1) >= 0).all()
    for i in range(n):
        assert (nbr[i, cnt[i]:] == n).all()
        assert (nbr[i, :cnt[i]] < n).all()


def test_k_cap_keeps_nearest():
    # 10 entities in one spot, k=4 -> keep 4 nearest (all dist 0 ties ok)
    pos = np.zeros((10, 3), np.float32)
    pos[:, 0] = np.arange(10) * 0.1
    alive = np.ones(10, bool)
    spec = GridSpec(radius=50.0, extent_x=64.0, extent_z=64.0,
                    k=4, cell_cap=16, row_block=16)
    nbr, cnt = grid_neighbors(spec, jnp.asarray(pos), jnp.asarray(alive))
    assert (np.asarray(cnt) == 4).all()


def test_dead_entities_invisible():
    pos = np.zeros((4, 3), np.float32)
    alive = np.array([True, False, True, True])
    spec = GridSpec(radius=10.0, extent_x=32.0, extent_z=32.0,
                    k=8, cell_cap=8, row_block=4)
    nbr, cnt = grid_neighbors(spec, jnp.asarray(pos), jnp.asarray(alive))
    nbr, cnt = np.asarray(nbr), np.asarray(cnt)
    assert cnt[1] == 0
    for i in (0, 2, 3):
        assert 1 not in set(nbr[i][nbr[i] < 4].tolist())
        assert cnt[i] == 2


def test_row_blocking_consistent():
    n = 500
    pos, alive = random_world(n, 7)
    a = GridSpec(radius=20.0, extent_x=200.0, extent_z=200.0,
                 k=64, cell_cap=64, row_block=500)
    b = GridSpec(radius=20.0, extent_x=200.0, extent_z=200.0,
                 k=64, cell_cap=64, row_block=100)
    nbr_a, cnt_a = grid_neighbors(a, jnp.asarray(pos), jnp.asarray(alive))
    nbr_b, cnt_b = grid_neighbors(b, jnp.asarray(pos), jnp.asarray(alive))
    assert (np.asarray(nbr_a) == np.asarray(nbr_b)).all()
    assert (np.asarray(cnt_a) == np.asarray(cnt_b)).all()


def test_approx_topk_matches_oracle():
    """topk_impl='approx' (lax.approx_min_k over f32-bitcast packed keys)
    plumbing check: same neighbor sets as the oracle, flags aligned. On
    CPU the lowering is exact so this proves the bit packing, NOT TPU
    recall — on TPU approx may miss a true neighbor with ~2% per-call
    probability (see the GridSpec.topk_impl caveat; knob is opt-in)."""
    from goworld_tpu.ops.aoi import grid_neighbors_flags, neighbors_oracle

    n = 400
    pos, alive = random_world(n, 13)
    oracle = neighbors_oracle(pos, alive, 25.0)
    spec = GridSpec(radius=25.0, extent_x=200.0, extent_z=200.0,
                    k=64, cell_cap=64, row_block=128, topk_impl="approx")
    rng = np.random.default_rng(13)
    fb = rng.integers(0, 4, n).astype(np.int32)
    nbr, cnt, fl = grid_neighbors_flags(
        spec, jnp.asarray(pos), jnp.asarray(alive),
        flag_bits=jnp.asarray(fb),
    )
    nbr, cnt, fl = np.asarray(nbr), np.asarray(cnt), np.asarray(fl)
    for i in range(n):
        got = set(nbr[i][nbr[i] < n].tolist())
        want = oracle[i] if alive[i] else set()
        assert got == want, (i, got, want)
        for j in range(spec.k):
            if nbr[i, j] < n:
                assert fl[i, j] == (fb[nbr[i, j]] & 3)


def test_ranges_sweep_matches_table_and_oracle():
    """sweep_impl='ranges' (tableless: candidates sliced straight from
    the cell-sorted array) must equal the table impl bit-for-bit while
    no cell overflows cell_cap, and equal the oracle."""
    from goworld_tpu.ops.aoi import grid_neighbors_flags, neighbors_oracle

    n = 500
    pos, alive = random_world(n, 21)
    oracle = neighbors_oracle(pos, alive, 25.0)
    rng = np.random.default_rng(21)
    fb = rng.integers(0, 4, n).astype(np.int32)
    base = dict(radius=25.0, extent_x=200.0, extent_z=200.0,
                k=64, cell_cap=64, row_block=128)
    outs = {}
    for impl in ("table", "ranges"):
        spec = GridSpec(**base, sweep_impl=impl)
        nbr, cnt, fl = grid_neighbors_flags(
            spec, jnp.asarray(pos), jnp.asarray(alive),
            flag_bits=jnp.asarray(fb),
        )
        outs[impl] = (np.asarray(nbr), np.asarray(cnt), np.asarray(fl))
    for a, b in zip(outs["table"], outs["ranges"]):
        assert (a == b).all()
    nbr, cnt, fl = outs["ranges"]
    for i in range(n):
        got = set(nbr[i][nbr[i] < n].tolist())
        assert got == (oracle[i] if alive[i] else set()), i
        for j in range(64):
            if nbr[i, j] < n:
                assert fl[i, j] == (fb[nbr[i, j]] & 3)


def test_ranges_sweep_pools_cell_cap():
    """The ranges impl's cap is pooled per z-triple (3*cell_cap): a cell
    overflowing cell_cap keeps strictly more true neighbors than the
    per-cell table cap — never fewer."""
    m = 40
    pos = np.zeros((m, 3), np.float32)
    rng = np.random.default_rng(4)
    pos[:30, 0] = 5.0 + rng.random(30)   # 30 entities in ONE cell
    pos[:30, 2] = 5.0 + rng.random(30)
    pos[30:, 0] = pos[30:, 2] = 100.0
    alive = np.ones(m, bool)
    base = dict(radius=10.0, extent_x=120.0, extent_z=120.0,
                k=64, cell_cap=8, row_block=m)
    cnt = {}
    for impl in ("table", "ranges"):
        spec = GridSpec(**base, sweep_impl=impl)
        _, c = grid_neighbors(spec, jnp.asarray(pos), jnp.asarray(alive))
        cnt[impl] = int(np.asarray(c)[0])
    assert cnt["ranges"] >= cnt["table"]
    assert cnt["ranges"] >= 20          # pooled cap 24 admits most of 29


def test_big_grid_argsort_path_matches_oracle():
    """Worlds with >= 2^10 padded cell rows take the argsort path (the
    packed single-array sort can't encode the row id); it must agree
    with the oracle exactly like the packed path does."""
    n = 400
    pos, alive = random_world(n, 31)
    spec = GridSpec(radius=2.0, extent_x=200.0, extent_z=200.0,
                    k=32, cell_cap=16, row_block=128)
    assert (spec.cells_x + 2) * (spec.cells_z + 2) >= (1 << 10)
    nbr, cnt = grid_neighbors(spec, jnp.asarray(pos), jnp.asarray(alive))
    nbr = np.asarray(nbr)
    oracle = neighbors_oracle(pos, alive, 2.0)
    for i in range(n):
        got = set(nbr[i][nbr[i] < n].tolist())
        assert got == (oracle[i] if alive[i] else set()), i


def test_candidate_planes_are_int32():
    """The sorted view and the cell table carry positions as f32 BITS
    inside int32 planes, never slot words as int bits inside f32
    planes: a small int viewed as f32 is a subnormal, and the TPU
    flushes subnormals to zero (PR 21's first chip run: every
    neighbour list collapsed to slot 0 — invisible on the CPU, which
    keeps the bits; chip_smoke.py holds the result on the chip)."""
    import jax
    import jax.numpy as jnp

    from goworld_tpu.ops import aoi

    n = 64
    spec = GridSpec(radius=10.0, extent_x=100.0, extent_z=100.0,
                    k=8, cell_cap=4)
    rng = np.random.default_rng(3)
    pos = jnp.asarray(rng.uniform(0, 100, (n, 3)).astype(np.float32))
    alive = jnp.ones((n,), bool)
    _cx, _cz, srow, _al, _czp, n_rows = aoi._cell_rows(
        spec, pos, alive, None)
    order, sorted_row = aoi._sort_cells(n, n_rows, srow)
    src, _sentinel, empty = aoi._sorted_src(
        spec, pos, jnp.zeros((n,), jnp.int32), order)
    _rs, s_t = aoi._build_ranges(4, n_rows, srow, src, empty)
    table = aoi._build_table(4, n_rows, sorted_row, src, empty)
    assert src.dtype == s_t.dtype == table.dtype == jnp.int32
    # the coordinates survive the round trip bit for bit
    back = np.asarray(jax.lax.bitcast_convert_type(src[:, 0],
                                                   jnp.float32))
    assert np.array_equal(back, np.asarray(pos[:, 0])[np.asarray(order)])
    # ... and the words are the slot ids (flags 0 here), as plain ints
    assert np.array_equal(np.asarray(src[:, 2]) >> 2, np.asarray(order))
