"""Mesh: the busiest device plane's busy time per frame minus the least
busy plane's (phase_reduce.py): how unevenly the tiles' work falls on
the chips of one frame. Nothing on one chip."""
from phase_reduce import skew_ms


def read(scrapes, trace, cell):
    return skew_ms(cell)
