"""Game host (net/game.py ``_flush_sync_out``, checkpoint, replication):
the ``fan_out`` span per frame (``tick_phase_ms{phase="fan_out"}``,
window delta)."""
from phase_reduce import phase_ms


def read(scrapes, trace, cell):
    return phase_ms(scrapes, cell, "fan_out")
