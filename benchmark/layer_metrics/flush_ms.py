"""Game host (entity/manager.py ``_flush_staging``, timers, post queue):
the ``flush_staging`` span per frame
(``tick_phase_ms{phase="flush_staging"}``, window delta)."""
from phase_reduce import phase_ms


def read(scrapes, trace, cell):
    return phase_ms(scrapes, cell, "flush_staging")
