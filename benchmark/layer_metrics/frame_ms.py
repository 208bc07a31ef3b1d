"""Game host (net/game.py serve loop): wall time of a frame — pump plus
``tick()`` — as the game's tick histogram has it: sum over count, window
delta."""
from scrapes import frame_ms


def read(scrapes, trace, cell):
    return frame_ms(scrapes)
