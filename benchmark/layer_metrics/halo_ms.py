"""Mesh (parallel/halo.py): device time per frame under ``gw.halo`` —
packing the border strips and shipping them to the neighbouring tiles
(``ppermute``) — on the busiest device plane, read by name from the
capture (phase_reduce.py). Nothing on one chip: no such scope runs."""
from phase_reduce import scope_ms


def read(scrapes, trace, cell):
    return scope_ms(cell, "gw.halo")
