"""Gate (net/gate.py): the ``gate_flush`` hop lane of the sync-age
stamp (dispatcher forward -> per-client send), record-weighted mean
over the window, from the gate's /metrics."""
from scrapes import mean_ms


def read(scrapes, trace, cell):
    return mean_ms(scrapes, "gate", "sync_age_hop_ms",
                   'hop="gate_flush"')
