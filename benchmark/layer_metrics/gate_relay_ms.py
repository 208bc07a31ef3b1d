"""Gate (net/gate.py ``_relay_sync_records``): a sync batch's decode,
regroup and per-client sends, from the handler's entry to the last
send (``gate_relay_ms``), mean per batch over the window, from the
gate's /metrics. The rest of ``gate_flush_ms`` is the wire leg and the
wait for the gate's loop. A gate without the series reads nothing."""
from scrapes import mean_ms


def read(scrapes, trace, cell):
    return mean_ms(scrapes, "gate", "gate_relay_ms")
