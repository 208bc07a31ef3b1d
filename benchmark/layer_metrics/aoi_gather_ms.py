"""Kernels (ops/aoi.py): device time per frame under ``gw.aoi.gather``
— the sweep's window fetch, whichever ``sweep_impl`` runs it — read by
name from the capture (phase_reduce.py)."""
from phase_reduce import scope_ms


def read(scrapes, trace, cell):
    return scope_ms(cell, "gw.aoi.gather")
