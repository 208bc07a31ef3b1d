"""Dispatcher (net/dispatcher.py): the ``dispatcher`` hop lane of the
sync-age stamp (game send -> dispatcher forward), record-weighted mean
over the window, from the gate's /metrics."""
from scrapes import mean_ms


def read(scrapes, trace, cell):
    return mean_ms(scrapes, "gate", "sync_age_hop_ms",
                   'hop="dispatcher"')
