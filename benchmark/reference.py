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
                  block: int = 256) -> list[set[int]]:
    """The neighbours' row numbers of the given rows among all rows of
    ``xz``, by brute force, in blocks of rows so the distance matrix
    stays small."""
    xz = np.asarray(xz, np.float64)
    rows = np.asarray(rows, np.int64)
    out: list[set[int]] = []
    for lo in range(0, len(rows), block):
        part = rows[lo:lo + block]
        d = chebyshev(xz[part], xz)
        for r, i in enumerate(part):
            near = np.nonzero(d[r] <= radius)[0]
            out.append({int(j) for j in near if j != i})
    return out


def neighbourhoods(xz: np.ndarray, radius: float) -> list[set[int]]:
    """For every row its neighbours' row numbers."""
    return neighbours_of(xz, np.arange(len(xz)), radius)
