"""Cross-impl AOI parity: {table, ranges, cellrow, shift, fused} x
{argsort, counting sort} x {skin off, skin on} must produce IDENTICAL
neighbor sets (vs the NumPy oracle) in non-overflow regimes, and the
front-half checksums (sweep_phase_checksum) must agree across sort
lowerings — the counting sort is stable, so it is a pure lowering
choice, and the Verlet skin is exact by the standard bound. The fused
Pallas back half (r6) must additionally be BIT-identical to its split
sibling "ranges" (same candidates, same packed keys, unique valid keys
→ the same top-k) — asserted on raw arrays, not just sets. Structure
follows tests/test_aoi_shift.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from goworld_tpu.ops.aoi import (
    GridSpec,
    grid_neighbors_flags,
    grid_neighbors_verlet,
    init_verlet_cache,
    neighbors_oracle,
    sweep_phase_checksum,
)

# the fused rows run the Pallas kernel in interpret mode on CPU — part
# of the kernel-parity set the `pallas` marker selects
FUSED = pytest.param("fused", marks=pytest.mark.pallas)

N = 600
EXTENT = 300.0
RADIUS = 25.0
SKIN = 7.5


def _world(seed=5):
    rng = np.random.default_rng(seed)
    pos = np.zeros((N, 3), np.float32)
    pos[:, 0] = rng.random(N) * EXTENT
    pos[:, 2] = rng.random(N) * EXTENT
    alive = rng.random(N) < 0.92
    fb = rng.integers(0, 4, N).astype(np.int32)
    # a second position set, every entity moved < SKIN/2 (reuse-legal)
    pos2 = pos.copy()
    step = rng.normal(0.0, 1.0, (N, 2)).astype(np.float32)
    step = np.clip(step, -SKIN / 2 + 0.1, SKIN / 2 - 0.1)
    pos2[:, 0] = np.clip(pos[:, 0] + step[:, 0], 0, EXTENT - 1e-3)
    pos2[:, 2] = np.clip(pos[:, 2] + step[:, 1], 0, EXTENT - 1e-3)
    return pos, pos2, alive, fb


POS, POS2, ALIVE, FB = _world()
ORACLE = neighbors_oracle(POS, ALIVE, RADIUS)
ORACLE2 = neighbors_oracle(POS2, ALIVE, RADIUS)


def _spec(sweep_impl, sort_impl, skin):
    # generous caps: no k/cell_cap/verlet_cap overflow at this density,
    # so every combo must be EXACT
    return GridSpec(
        radius=RADIUS, extent_x=EXTENT, extent_z=EXTENT,
        k=64, cell_cap=64, row_block=256,
        sweep_impl=sweep_impl, sort_impl=sort_impl, skin=skin,
        verlet_cap=128,
    )


def _sets(nbr):
    nbr = np.asarray(nbr)
    return [set(r[r < N].tolist()) for r in nbr]


def _check_flags(nbr, fl, fb):
    nbr, fl = np.asarray(nbr), np.asarray(fl)
    valid = nbr < N
    assert np.array_equal(fl[valid], fb[np.minimum(nbr, N - 1)][valid] & 3)


@pytest.mark.parametrize("sort_impl", ["argsort", "counting"])
@pytest.mark.parametrize("sweep_impl", ["table", "ranges", "cellrow",
                                        "shift", FUSED])
def test_skinless_matrix_matches_oracle(sweep_impl, sort_impl):
    spec = _spec(sweep_impl, sort_impl, 0.0)
    nbr, cnt, fl = grid_neighbors_flags(
        spec, jnp.asarray(POS), jnp.asarray(ALIVE),
        flag_bits=jnp.asarray(FB),
    )
    got = _sets(nbr)
    for i in range(N):
        want = ORACLE[i] if ALIVE[i] else set()
        assert got[i] == want, (sweep_impl, sort_impl, i)
    _check_flags(nbr, fl, FB)


@pytest.mark.parametrize("sort_impl", ["argsort", "counting"])
@pytest.mark.parametrize("sweep_impl", ["table", "ranges", "cellrow",
                                        "shift", FUSED])
def test_skin_matrix_matches_oracle_rebuild_and_reuse(sweep_impl,
                                                      sort_impl):
    """Verlet path through every (sweep, sort) front half: the rebuild
    tick AND a moved reuse tick must both be oracle-exact."""
    spec = _spec(sweep_impl, sort_impl, SKIN)
    cache = init_verlet_cache(spec, N)
    nbr, cnt, fl, _s, cache, reb, _sl = grid_neighbors_verlet(
        spec, jnp.asarray(POS), jnp.asarray(ALIVE), cache,
        flag_bits=jnp.asarray(FB),
    )
    assert int(reb) == 1          # cold cache: the front half ran
    got = _sets(nbr)
    for i in range(N):
        want = ORACLE[i] if ALIVE[i] else set()
        assert got[i] == want, ("rebuild", sweep_impl, sort_impl, i)
    _check_flags(nbr, fl, FB)

    nbr2, cnt2, fl2, _s, cache, reb2, _sl = grid_neighbors_verlet(
        spec, jnp.asarray(POS2), jnp.asarray(ALIVE), cache,
        flag_bits=jnp.asarray(FB),
    )
    assert int(reb2) == 0         # under skin/2: the front half skipped
    got2 = _sets(nbr2)
    for i in range(N):
        want = ORACLE2[i] if ALIVE[i] else set()
        assert got2[i] == want, ("reuse", sweep_impl, sort_impl, i)
    _check_flags(nbr2, fl2, FB)


@pytest.mark.parametrize("sweep_impl", ["table", "ranges"])
def test_sweep_phase_checksums_agree_across_sort_impls(sweep_impl):
    """The bench sub-phase probes time the real helpers; the counting
    sort's (order, sorted_row) is bit-identical to argsort's, so the
    'sort' and 'build' checksums must agree exactly."""
    outs = {}
    for sort_impl in ("argsort", "counting"):
        spec = _spec(sweep_impl, sort_impl, 0.0)
        outs[sort_impl] = [
            float(sweep_phase_checksum(
                spec, jnp.asarray(POS), jnp.asarray(ALIVE), phase
            ))
            for phase in ("sort", "build")
        ]
    assert outs["argsort"] == outs["counting"]


@pytest.mark.pallas
@pytest.mark.parametrize("topk_impl", ["exact", "sort", "f32"])
def test_fused_bit_identical_to_ranges(topk_impl):
    """Stronger than the oracle matrix: the fused kernel shares the
    ranges front half and the _pack_keys encoder, and valid keys are
    unique, so its (nbr, cnt, flags) arrays must equal the split
    "ranges" sweep's BIT-FOR-BIT under every exact ranking. argsort
    front half and k=32 keep the interpret-mode cost down — the
    counting front half's bit-parity is proven by the oracle matrix
    above plus test_sort.py, and k only sizes the unrolled
    min-extract."""
    outs = {}
    for sweep_impl in ("ranges", "fused"):
        spec = GridSpec(
            radius=RADIUS, extent_x=EXTENT, extent_z=EXTENT,
            k=32, cell_cap=64, row_block=256,
            sweep_impl=sweep_impl, topk_impl=topk_impl,
        )
        nbr, cnt, fl = grid_neighbors_flags(
            spec, jnp.asarray(POS), jnp.asarray(ALIVE),
            flag_bits=jnp.asarray(FB),
        )
        outs[sweep_impl] = (np.asarray(nbr), np.asarray(cnt),
                            np.asarray(fl))
    for a, b in zip(outs["ranges"], outs["fused"]):
        assert np.array_equal(a, b)


@pytest.mark.pallas
def test_fused_phase_checksums_follow_ranges():
    """The front-half checksums ("sort"/"build") of a fused spec go
    through the shared `sweep_impl in ("ranges", "fused")` build
    branch — a real equality check that the fused front half IS the
    ranges front half. The back-half probes ("gather"/"pack"/"rank")
    are DEFINED to run the split sibling (sweep_phase_checksum maps
    fused -> ranges before calling _sweep), so equality there is the
    contract, not evidence — this leg only guards that a fused config
    can evaluate every bench sub-phase probe without tracing the
    Pallas kernel (finite scalar out, no crash)."""
    for phase in ("sort", "build"):
        a = float(sweep_phase_checksum(
            _spec("ranges", "argsort", 0.0),
            jnp.asarray(POS), jnp.asarray(ALIVE), phase))
        b = float(sweep_phase_checksum(
            _spec("fused", "argsort", 0.0),
            jnp.asarray(POS), jnp.asarray(ALIVE), phase))
        assert a == b, phase
    for phase in ("gather", "pack", "rank"):
        v = float(sweep_phase_checksum(
            _spec("fused", "argsort", 0.0),
            jnp.asarray(POS), jnp.asarray(ALIVE), phase))
        assert np.isfinite(v), phase


@pytest.mark.pallas
def test_pallas_impls_fall_back_to_interpret_off_tpu(monkeypatch,
                                                     caplog):
    """Regression (ISSUE 6 satellite): selecting a Pallas impl on a
    non-TPU backend must fall back to interpret mode with a ONE-TIME
    warning — never fail at trace time, never warn per re-trace."""
    import logging

    import jax

    from goworld_tpu.ops import pallas_compat

    if jax.default_backend() == "tpu":
        pytest.skip("fallback path is for non-TPU backends")
    monkeypatch.setattr(pallas_compat, "_WARNED", set())
    with caplog.at_level(logging.WARNING,
                         logger="goworld_tpu.ops.pallas"):
        for _ in range(2):   # second call: cached, no second warning
            nbr, _cnt, _fl = grid_neighbors_flags(
                _spec("fused", "pallas", 0.0),
                jnp.asarray(POS), jnp.asarray(ALIVE),
                flag_bits=jnp.asarray(FB),
            )
        got = [set(r[r < N].tolist()) for r in np.asarray(nbr)]
        for i in range(N):
            assert got[i] == (ORACLE[i] if ALIVE[i] else set()), i
    warns = [r.message for r in caplog.records
             if "interpret mode" in r.message]
    assert sorted(warns.count(m) for m in set(warns)) == [1, 1], warns
    assert any("aoi_fused_sweep" in m for m in warns)
    assert any("counting_sort_fill" in m for m in warns)


@pytest.mark.precision
@pytest.mark.parametrize("sweep_impl", ["table", "ranges", "cellrow",
                                        "shift"])
def test_precision_q16_matrix_matches_snapped_oracle(sweep_impl):
    """precision=q16 rows of the parity matrix (ISSUE 12): every impl
    sweeps the SNAPPED lattice world, so the oracle over the snapped
    positions must hold exactly, and the packed-int16 "ranges" fast
    path must match the f32 impls bit-for-bit (deep coverage incl.
    Verlet reuse lives in tests/test_precision.py)."""
    from goworld_tpu.ops.aoi import quantize_positions

    spec = _spec(sweep_impl, "argsort", 0.0)
    import dataclasses as _dc

    spec = _dc.replace(spec, precision="q16")
    spos = np.asarray(quantize_positions(spec, jnp.asarray(POS)))
    oracle_q = neighbors_oracle(spos, ALIVE, RADIUS)
    nbr, cnt, fl = grid_neighbors_flags(
        spec, jnp.asarray(POS), jnp.asarray(ALIVE),
        flag_bits=jnp.asarray(FB),
    )
    got = _sets(nbr)
    for i in range(N):
        want = oracle_q[i] if ALIVE[i] else set()
        assert got[i] == want, (sweep_impl, i)
    _check_flags(nbr, fl, FB)


def test_new_knob_validation_mirrors_existing_messages():
    """GridSpec.__post_init__ rejects bad values for the r5 knobs with
    the same shape as the topk_impl/sweep_impl errors: the named
    allowed set plus the repr of the offending value."""
    base = dict(radius=10.0)
    with pytest.raises(ValueError, match=r"argsort\|counting\|pallas"):
        GridSpec(**base, sort_impl="quicksort")
    with pytest.raises(ValueError, match=r"'quicksort'"):
        GridSpec(**base, sort_impl="quicksort")
    with pytest.raises(ValueError, match=r"skin must be >= 0.*-1\.5"):
        GridSpec(**base, skin=-1.5)
    with pytest.raises(ValueError, match=r"skin must be >= 0"):
        GridSpec(**base, skin=float("nan"))
    with pytest.raises(ValueError, match=r"verlet_cap must be 0.*-3"):
        GridSpec(**base, verlet_cap=-3)
    # in (0, k): _rank_candidates would ask _rank_packed for k of
    # fewer-than-k cached lanes — reject at construction, not deep in
    # the trace
    with pytest.raises(ValueError, match=r"verlet_cap must be 0.*or >= k"):
        GridSpec(**base, k=8, verlet_cap=4)
    GridSpec(**base, k=8, verlet_cap=8)  # == k is legal
    # effective cap past the 3x3 window's 9*cell_cap lanes: the
    # rebuild sweep could never fill it (cond branch shape mismatch
    # deep in the trace) — reject at construction
    with pytest.raises(ValueError, match=r"9\*cell_cap"):
        GridSpec(**base, k=32, cell_cap=3, skin=2.0)
    GridSpec(**base, k=32, cell_cap=3)  # fine while skin is off
    with pytest.raises(ValueError,
                       match=r"rebuild_every_max must be >= 0.*-7"):
        GridSpec(**base, rebuild_every_max=-7)
    # the existing knobs keep their messages (pinned here so the new
    # branches can't have reordered them away)
    with pytest.raises(ValueError,
                       match=r"table\|ranges\|cellrow\|shift\|fused"):
        GridSpec(**base, sweep_impl="bogus")
    with pytest.raises(ValueError, match=r"exact\|sort\|f32\|approx"):
        GridSpec(**base, topk_impl="bogus")


# =======================================================================
# the served path: the default sweep against "ranges" through World.tick
# =======================================================================
def _served_world(sweep_kw: dict, n_spaces: int, precision: str):
    from goworld_tpu.core import WorldConfig
    from goworld_tpu.entity import Entity, GameClient, Space, World

    class _Mob(Entity):
        pass

    w = World(WorldConfig(
        capacity=256,
        grid=GridSpec(radius=RADIUS, extent_x=EXTENT, extent_z=EXTENT,
                      k=32, cell_cap=32, row_block=64,
                      precision=precision, **sweep_kw),
        input_cap=64), n_spaces=n_spaces, seed=11)
    w.register_entity("Mob", _Mob)
    w.register_space("Arena", Space)
    w.create_nil_space()
    sent = []
    w.sync_sink = lambda gate, cids, eids, vals: sent.append(
        (gate, list(cids), list(eids), np.asarray(vals).tobytes()))
    rng = np.random.default_rng(3)
    for s in range(n_spaces):
        sp = w.create_space("Arena")
        for i in range(150):
            w.create_entity(
                "Mob", space=sp, eid=f"s{s}mob{i:010d}", moving=True,
                pos=(float(rng.uniform(5, EXTENT - 5)), 0.0,
                     float(rng.uniform(5, EXTENT - 5))),
                client=(GameClient(1, f"CID{s}{i:012d}", w)
                        if i < 6 else None))
    return w, sent


@pytest.mark.parametrize(
    "n_spaces,precision", [(1, "off"), (2, "off"), (1, "q16")],
    ids=["one_space", "vmapped_s2", "q16"])
def test_served_default_sweep_matches_ranges(n_spaces, precision):
    """ISSUE 26: the library default (``cellrow``, drawn from the one
    constant — no ``sweep_impl`` is named) and ``ranges`` serve the
    same world while occupancy <= cell_cap: equal neighbour rows,
    enter/leave events, sync records and gauges out of ``World.tick``,
    equal interest sets and client sends on the host. Under q16 the
    default sweeps the snapped positions through the f32-bits table
    and ``ranges`` its packed int16-pair view."""
    from goworld_tpu.utils import consts

    wa, sent_a = _served_world({}, n_spaces, precision)
    wb, sent_b = _served_world({"sweep_impl": "ranges"}, n_spaces,
                               precision)
    assert wa.cfg.grid.sweep_impl == consts.DEFAULT_SWEEP_IMPL != "ranges"
    events = 0
    for _ in range(8):
        wa.tick()
        wb.tick()
        oa, ob = wa.last_outputs, wb.last_outputs
        assert int(np.max(oa.aoi_over_cap_cells)) == 0
        assert int(np.max(oa.aoi_over_k_rows)) == 0
        for x, y in zip(jax.tree.leaves(oa), jax.tree.leaves(ob)):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        assert np.array_equal(np.asarray(wa.state.nbr),
                              np.asarray(wb.state.nbr))
        assert np.array_equal(np.asarray(wa.state.nbr_cnt),
                              np.asarray(wb.state.nbr_cnt))
        events += int(np.sum(oa.enter_n)) + int(np.sum(oa.leave_n))
    assert events > 0 and int(np.sum(wa.last_outputs.sync_n)) > 0
    assert int(np.max(np.asarray(wa.state.nbr_cnt))) > 0
    assert sent_a and sent_a == sent_b
    mobs = [eid for eid in wa.entities if "mob" in eid]   # not the spaces
    assert len(mobs) == 150 * n_spaces
    for eid in mobs:
        assert wa.entities[eid].interested_in \
            == wb.entities[eid].interested_in
    assert any(wa.entities[eid].interested_in for eid in mobs)
