"""Kernels (ops/sync.py): device time per frame under ``gw.attrs`` —
collecting the hot-attr deltas into a space's output plane, which the
tick does whether any attr changed or not — read by name from the
capture (phase_reduce.py)."""
from phase_reduce import scope_ms


def read(scrapes, trace, cell):
    return scope_ms(cell, "gw.attrs")
