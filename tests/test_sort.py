"""Counting-sort front half (ops/sort.py): bit-parity with stable
argsort — the contract that makes GridSpec.sort_impl a pure lowering
choice (docs/ROOFLINE.md replaces the bitonic-network traffic term with
this kernel). The Pallas form is validated in interpret mode for BOTH
kernel bodies: the "vector" gather form (the interpret default) and the
"serial" body that IS the TPU lowering (SMEM bins + per-element fill
walk on the scalar core, no interpret flag on hardware; its v5e compile
is held by tests/test_tpu_compile.py) — so a chip run exercises a
CPU-validated algorithm.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from goworld_tpu.ops.sort import (
    counting_sort_cells,
    counting_sort_cells_pallas,
    row_starts,
)


CASES = [
    # (n, n_rows, chunk): dup-heavy, single-bin, chunk larger than n,
    # chunk not dividing n, many empty bins
    (1000, 37, 128),
    (4096, 1, 8192),
    (777, 500, 100),
    (64, 9, 2048),
    (2048, 2048, 512),
]


def _keys(rng, n, n_rows, dead_frac=0.1):
    """Cell-row keys incl. the dump bin n_rows (dead entities)."""
    srow = rng.integers(0, n_rows, n).astype(np.int32)
    srow[rng.random(n) < dead_frac] = n_rows
    return srow


@pytest.mark.parametrize("n,n_rows,chunk", CASES)
def test_counting_sort_matches_stable_argsort(n, n_rows, chunk):
    rng = np.random.default_rng(n + n_rows)
    srow = _keys(rng, n, n_rows)
    ref = np.argsort(srow, kind="stable").astype(np.int32)
    order, sorted_row = counting_sort_cells(
        jnp.asarray(srow), n_rows, chunk
    )
    assert np.array_equal(np.asarray(order), ref)
    assert np.array_equal(np.asarray(sorted_row), srow[ref])


@pytest.mark.pallas
@pytest.mark.parametrize("lowering", ["vector", "serial"])
@pytest.mark.parametrize("n,n_rows,chunk", CASES[:3])
def test_pallas_kernel_interpret_parity(n, n_rows, chunk, lowering):
    """Both kernel bodies — the vector-gather interpret form and the
    serial body that is the real TPU lowering — must match stable
    argsort bit-for-bit under interpret mode."""
    rng = np.random.default_rng(3 * n + n_rows)
    srow = _keys(rng, n, n_rows)
    ref = np.argsort(srow, kind="stable").astype(np.int32)
    order, sorted_row = counting_sort_cells_pallas(
        jnp.asarray(srow), n_rows, chunk, interpret=True,
        lowering=lowering,
    )
    assert np.array_equal(np.asarray(order), ref)
    assert np.array_equal(np.asarray(sorted_row), srow[ref])


@pytest.mark.pallas
def test_pallas_lowering_knob_validated():
    with pytest.raises(ValueError, match=r"auto\|serial\|vector"):
        counting_sort_cells_pallas(
            jnp.zeros(8, jnp.int32), 4, lowering="bogus"
        )


@pytest.mark.pallas
def test_serial_lowering_wide_bin_space():
    """A bin space wider than a chunk's worth of lanes and not a
    multiple of 128 (the SMEM tables are 1-D: any bin count works)."""
    rng = np.random.default_rng(77)
    n, n_rows = 3000, 1000
    srow = _keys(rng, n, n_rows)
    ref = np.argsort(srow, kind="stable").astype(np.int32)
    order, sorted_row = counting_sort_cells_pallas(
        jnp.asarray(srow), n_rows, 512, interpret=True,
        lowering="serial",
    )
    assert np.array_equal(np.asarray(order), ref)
    assert np.array_equal(np.asarray(sorted_row), srow[ref])


def test_chunk_size_is_pure_execution_knob():
    rng = np.random.default_rng(11)
    srow = _keys(rng, 1500, 64)
    outs = [
        np.asarray(counting_sort_cells(jnp.asarray(srow), 64, c)[0])
        for c in (1, 7, 256, 1500, 4096)
    ]
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)


def test_row_starts_exclusive_cumsum():
    srow = np.array([2, 0, 2, 5, 0, 2], np.int32)
    starts = np.asarray(row_starts(jnp.asarray(srow), 5))
    # bins: 0 -> 2 elems, 2 -> 3, 5(dump) -> 1
    assert starts.tolist() == [0, 2, 2, 5, 5, 5]


def test_all_same_and_degenerate_bins():
    srow = np.full(300, 7, np.int32)
    order, sorted_row = counting_sort_cells(jnp.asarray(srow), 20, 64)
    assert np.array_equal(np.asarray(order), np.arange(300))
    assert np.all(np.asarray(sorted_row) == 7)
