"""The plain reference: brute-force Chebyshev neighbourhoods over true
positions, in numpy. It imports nothing of the program.

An entity ``j`` is in ``i``'s interest set iff ``j != i`` and
``max(|x_i - x_j|, |z_i - z_j|) <= radius`` (the AOI the configuration
states: a square box of half-width ``radius`` in the XZ plane).
"""
from __future__ import annotations

import numpy as np


def chebyshev(xz_a: np.ndarray, xz_b: np.ndarray) -> np.ndarray:
    """Chebyshev distance of every row of ``xz_a`` to every row of
    ``xz_b``: f64[len(a), len(b)]."""
    a = np.asarray(xz_a, np.float64)[:, None, :]
    b = np.asarray(xz_b, np.float64)[None, :, :]
    return np.abs(a - b).max(axis=2)


def neighbours_of(xz: np.ndarray, rows, radius: float,
                  space=None) -> list[set[int]]:
    """The neighbours' row numbers of the given rows among all rows of
    ``xz``, one row at a time (a distance matrix of 4,096 rows against a
    world of 400,000 would take minutes; a test holds this equal to the
    brute force over every pair): of all rows, those whose x lies in ``[x_i - radius, x_i +
    radius]`` (found in the rows sorted by x: the same comparison, made
    once, and exact — the coordinates are f32 values held in f64, so
    the sums are), and of those the ones whose z does.

    ``space[j]`` (a world of many spaces; absent: one world, as ever) is
    the space row ``j`` lives in. Spaces share their coordinates and
    nothing else: a neighbourhood holds only rows of the same space, so
    the rows are sorted by space first and the search stays inside the
    row's own."""
    xz = np.asarray(xz, np.float64)
    rows = np.asarray(rows, np.int64)
    if space is None:
        order = np.argsort(xz[:, 0], kind="stable")
        first = last = None
    else:
        space = np.asarray(space, np.int64)
        order = np.lexsort((xz[:, 0], space))
        first = np.searchsorted(space[order], space, "left")
        last = np.searchsorted(space[order], space, "right")
    xs = xz[order, 0]
    out: list[set[int]] = []
    for i in rows:
        x, z = xz[i]
        a, b = (0, len(xs)) if first is None else (first[i], last[i])
        lo = a + np.searchsorted(xs[a:b], x - radius, "left")
        hi = a + np.searchsorted(xs[a:b], x + radius, "right")
        cand = order[lo:hi]
        near = cand[np.abs(xz[cand, 1] - z) <= radius]
        out.append({int(j) for j in near if j != i})
    return out


def neighbourhoods(xz: np.ndarray, radius: float,
                   space=None) -> list[set[int]]:
    """For every row its neighbours' row numbers."""
    return neighbours_of(xz, np.arange(len(xz)), radius, space)
