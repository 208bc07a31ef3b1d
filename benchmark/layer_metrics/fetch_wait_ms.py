"""Device tick, seen from the host: the ``device_step`` span (dispatch)
plus the ``fetch_outputs`` span (the blocking fetch) per frame — how
long the logic thread waits for the device
(``tick_phase_ms``, window delta)."""
from phase_reduce import phase_ms


def read(scrapes, trace, cell):
    return phase_ms(scrapes, cell, "device_step", "fetch_outputs")
