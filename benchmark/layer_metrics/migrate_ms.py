"""Mesh (parallel/migrate.py): device time per frame under
``gw.migrate`` — finding the rows that left their tile and moving them
to the tile that owns them now (``all_to_all``) — on the busiest device
plane, read by name from the capture (phase_reduce.py). Nothing on one
chip: no such scope runs."""
from phase_reduce import scope_ms


def read(scrapes, trace, cell):
    return scope_ms(cell, "gw.migrate")
