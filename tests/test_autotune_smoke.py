"""Compile-only smoke over EVERY bench autotune candidate at tiny N —
including the BENCH_AUTOTUNE_DIAG set — so kernel variants cannot rot
unseen between chip runs (a candidate that stops compiling would
otherwise only be discovered mid-bench on chip time; the autotune
sweep no longer hides it — a failing candidate stops the bench child).
What the chip's compiler says to the kernels is asked in
tests/test_tpu_compile.py.
"""

import importlib.util
import os

import numpy as np
import pytest

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_smoke_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


BENCH = _load_bench()
N = 256
EXTENT = float(int((N * 10000 / 12) ** 0.5))


def _ids():
    return [
        ",".join(f"{k}={v}" for k, v in ov.items()) or "default"
        for _sel, ov in BENCH.AUTOTUNE_CANDIDATES
    ]


@pytest.mark.parametrize(
    "selectable,overrides", BENCH.AUTOTUNE_CANDIDATES, ids=_ids()
)
def test_autotune_candidate_builds_and_runs(selectable, overrides,
                                            monkeypatch):
    for var in BENCH.GRID_ENV.values():
        monkeypatch.delenv(var, raising=False)
    from goworld_tpu.ops.aoi import (
        GridSpec,
        grid_neighbors_flags,
        grid_neighbors_verlet,
        init_verlet_cache,
    )

    gk = BENCH._grid_kw_from_env(N, overrides)
    spec = GridSpec(radius=50.0, extent_x=EXTENT, extent_z=EXTENT, **gk)
    rng = np.random.default_rng(1)
    pos = np.zeros((N, 3), np.float32)
    pos[:, 0] = rng.random(N) * EXTENT
    pos[:, 2] = rng.random(N) * EXTENT
    alive = jnp.ones(N, bool)
    flags = jnp.asarray(rng.integers(0, 4, N).astype(np.int32))
    if spec.skin > 0:
        # the bench autotune harness exercises this exact path
        cache = init_verlet_cache(spec, N)
        nbr, cnt, fl, _s, cache, _rb, _sl = grid_neighbors_verlet(
            spec, jnp.asarray(pos), alive, cache, flag_bits=flags)
    else:
        nbr, cnt, fl = grid_neighbors_flags(
            spec, jnp.asarray(pos), alive, flag_bits=flags)
    assert nbr.shape == (N, spec.k)
    assert int(cnt.sum()) >= 0  # forces execution, not just tracing


def test_diag_set_is_covered():
    """The parametrization above must include the diagnostics (the
    BENCH_AUTOTUNE_DIAG=1 set), not just the selectable pool."""
    assert any(not sel for sel, _ in BENCH.AUTOTUNE_CANDIDATES)


def test_fused_rows_are_candidates():
    """The r6 fused back half must stay in the candidate pool — both
    the fused-over-argsort row and the full-Pallas pipeline (fused over
    the counting-sort front half) — so the parametrized smoke above
    keeps compiling them every tier-1 run."""
    impls = [(ov.get("sweep_impl"), ov.get("sort_impl"))
             for _sel, ov in BENCH.AUTOTUNE_CANDIDATES]
    assert ("fused", None) in impls
    assert ("fused", "counting") in impls


@pytest.mark.pallas
def test_lowered_counting_sort_compiles_at_bench_shape():
    """The serial kernel body — the TPU lowering of the counting-sort
    fill pass (SMEM bins walked by the scalar core, no vector gathers)
    — must keep building at the autotune smoke shape, under interpret on
    CPU (the same body lowers on hardware). The autotune candidates
    only reach the "vector" interpret body off-TPU, so this is the
    tier-1 guard on the lowering itself."""
    from goworld_tpu.ops.sort import counting_sort_cells_pallas

    rng = np.random.default_rng(6)
    n_rows = 37
    srow = rng.integers(0, n_rows, N).astype(np.int32)
    ref = np.argsort(srow, kind="stable").astype(np.int32)
    order, sorted_row = counting_sort_cells_pallas(
        jnp.asarray(srow), n_rows, chunk=64, interpret=True,
        lowering="serial",
    )
    assert np.array_equal(np.asarray(order), ref)
    assert np.array_equal(np.asarray(sorted_row), srow[ref])
