"""Device tick (core/step.py tick_body): union of the device's op
intervals over the captured span, per served frame."""
from scrapes import busy_ms_per_frame


def read(scrapes, trace, cell):
    return busy_ms_per_frame(trace, cell)
