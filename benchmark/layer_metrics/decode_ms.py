"""Game host (entity/manager.py ``_decode_outputs``): the
``decode_fanout`` span per frame
(``tick_phase_ms{phase="decode_fanout"}``, window delta)."""
from phase_reduce import phase_ms


def read(scrapes, trace, cell):
    return phase_ms(scrapes, cell, "decode_fanout")
