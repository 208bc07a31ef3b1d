"""Device: idle time per frame under no ``gw.`` host span at all after
the clock shift (phase_reduce.py): the stretch of the serve loop that
is still nameless."""
from phase_reduce import UNLABELLED, idle_ms


def read(scrapes, trace, cell):
    return idle_ms(cell, lambda k: k == UNLABELLED)
