"""Game host: what a frame spends under NO timeline span
(``tick_phase_ms{phase="unspanned"}``: the tick's duration minus what
its spans cover), per frame over the window."""
from phase_reduce import phase_ms


def read(scrapes, trace, cell):
    return phase_ms(scrapes, cell, "unspanned")
