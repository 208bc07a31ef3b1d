"""``gate_relay_ms`` on a synthetic scrape: the gate's /metrics text at
the two edges of a window, as its ``gate_relay_ms`` histogram prints
it (a Registry of its own, so nothing else in the process shows)."""
import os

import pytest

from goworld_tpu.utils import metrics
from run import load_module, parse_prom

LAYER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "layer_metrics")


def read(s):
    return load_module(os.path.join(LAYER, "gate_relay_ms.py"),
                       "reader_gate_relay_ms").read(s, None, {})


def scrape(reg):
    return {"gate": parse_prom(reg.expose_text()), "game": {}}


def test_the_mean_is_of_the_batches_relayed_inside_the_window():
    reg = metrics.Registry()
    h = reg.histogram("gate_relay_ms", help="sync batch relay")
    for ms in (40.0, 60.0):           # before the window opens
        h.observe(ms)
    s = {"open": scrape(reg)}
    for ms in (3.0, 5.0, 13.0):
        h.observe(ms)
    s["close"] = scrape(reg)
    assert read(s) == pytest.approx(7.0)


def test_a_gate_without_the_series_or_without_batches_reads_nothing():
    reg = metrics.Registry()
    reg.histogram("gate_packet_handle_ms").observe(1.0)
    s = {"open": scrape(reg), "close": scrape(reg)}
    assert read(s) is None                       # the parent's gate
    h = reg.histogram("gate_relay_ms")
    h.observe(2.0)
    s = {"open": scrape(reg), "close": scrape(reg)}
    assert read(s) is None                       # no batch in the window
