"""Game host (net/game.py pump): the serve loop's ``drain_inputs`` span
— handling every queued packet — per frame
(``tick_phase_ms{phase="drain_inputs"}``, window delta)."""
from phase_reduce import phase_ms


def read(scrapes, trace, cell):
    return phase_ms(scrapes, cell, "drain_inputs")
