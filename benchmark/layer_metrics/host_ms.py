"""Game host (entity/manager.py flush -> fetch -> decode, net/game.py
fan-out): the wall time of the frames served while the profiler
captured, minus the device's busy time per frame of that capture."""
from scrapes import busy_ms_per_frame, frame_ms_traced


def read(scrapes, trace, cell):
    f, b = frame_ms_traced(scrapes), busy_ms_per_frame(trace, cell)
    if f is None or b is None:
        return None
    return f - b
