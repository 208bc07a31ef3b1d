"""Kernels (ops/aoi.py): device time per frame under the ``gw.aoi``
scope — the whole AOI sweep — read by name from the capture
(phase_reduce.py)."""
from phase_reduce import scope_ms


def read(scrapes, trace, cell):
    return scope_ms(cell, "gw.aoi")
