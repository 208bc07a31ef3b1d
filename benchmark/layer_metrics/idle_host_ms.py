"""Device: idle time per frame that falls under a ``gw.`` host span
other than the pacing sleep (after the clock shift, phase_reduce.py):
what the device actually waits for the host."""
from phase_reduce import PACING, UNLABELLED, idle_ms


def read(scrapes, trace, cell):
    return idle_ms(cell, lambda k: k not in (PACING, UNLABELLED))
