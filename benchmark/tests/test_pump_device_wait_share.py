"""``pump_device_wait_share`` on a recorded scrape: the game's /metrics at
two instants of a CPU rehearsal of ``tile.roam`` (PR 34; 4 Hz), cut to
the families the game-host readers take: ``game_pump_packets_total`` has
three places there. Its times are a CPU's and are compared with nothing
here: the counts are. The parent's scrape (PR 30's recording,
``data/pump_scrape``) has two places and reads nothing."""
import os

import pytest

from run import load_module, parse_prom

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER = os.path.join(os.path.dirname(HERE), "layer_metrics")
CELL = {"cell": {"name": "no.such.cell"}}
PLACES = {w: 'game_pump_packets_total{where="%s"}' % w
          for w in ("frame", "between", "device_wait")}


def scrapes(recording="pump_scrape_device_wait"):
    def edge(name):
        with open(os.path.join(HERE, "data", recording,
                               name + ".txt")) as f:
            return {"game": parse_prom(f.read())}
    return {"open": edge("open"), "close": edge("close")}


def read(metric, s):
    return load_module(os.path.join(LAYER, metric + ".py"),
                       "reader_" + metric).read(s, None, CELL)


def handled(s) -> dict:
    return {w: s["close"]["game"][k] - s["open"]["game"][k]
            for w, k in PLACES.items()}


def test_the_recorded_window_counts_three_places():
    s = scrapes()
    n = handled(s)
    assert n["frame"] == 0 and n["between"] > 0 and n["device_wait"] > 0
    share = read("pump_device_wait_share", s)
    assert share == pytest.approx(
        100.0 * n["device_wait"] / sum(n.values()))
    # the same packets, counted in a new place: the two shares are of
    # one total
    assert share + read("pump_between_share", s) == pytest.approx(100.0)


@pytest.mark.parametrize("in_frame", [0, 100, 1000])
def test_the_share_is_of_all_three_places_over_the_window(in_frame):
    s = scrapes()
    n = handled(s)
    s["close"]["game"][PLACES["frame"]] += in_frame
    assert read("pump_device_wait_share", s) == pytest.approx(
        100.0 * n["device_wait"] / (sum(n.values()) + in_frame))


def test_a_loop_that_never_engaged_reads_zero_and_not_nothing():
    """The series is there and nothing was handled in the wait: 0.0,
    which is what 'the loop never engaged' looks like on the chip."""
    s = scrapes()
    s["close"]["game"][PLACES["device_wait"]] = \
        s["open"]["game"][PLACES["device_wait"]]
    assert read("pump_device_wait_share", s) == 0.0


@pytest.mark.parametrize("recording", ["pump_scrape", "stripped"])
def test_a_program_without_the_place_reads_nothing(recording):
    """The parent of PR 34 counts two places (PR 30's recording), the
    parent of PR 30 none: no value, no error."""
    if recording == "stripped":
        s = scrapes()
        for e in s.values():
            for k in PLACES.values():
                del e["game"][k]
    else:
        s = scrapes(recording)
        assert PLACES["device_wait"] not in s["close"]["game"]
        assert read("pump_between_share", s) == 100.0
    assert read("pump_device_wait_share", s) is None
    s = scrapes()
    for k in PLACES.values():                      # nothing handled
        s["close"]["game"][k] = s["open"]["game"][k]
    assert read("pump_device_wait_share", s) is None


def test_the_wait_is_many_fetch_spans_and_pump_ms_holds_every_burst():
    """``fetch_wait_ms`` sums the ``fetch_outputs`` spans per frame: the
    waits on the queue and the fetch itself, the time the thread truly
    waited; the bursts in between are ``drain_inputs`` spans of the
    frame and stay in ``pump_ms``."""
    s = scrapes()
    g0, g1 = s["open"]["game"], s["close"]["game"]
    frames = g1["tick_latency_ms_count"] - g0["tick_latency_ms_count"]
    waits = 'tick_phase_ms_count{phase="fetch_outputs"}'
    assert g1[waits] - g0[waits] > frames
    total = {p: g1[k] - g0[k] for p in ("device_step", "fetch_outputs",
                                        "drain_inputs")
             for k in ['tick_phase_ms_sum{phase="%s"}' % p]}
    assert read("fetch_wait_ms", s) == pytest.approx(
        (total["device_step"] + total["fetch_outputs"]) / frames)
    assert read("pump_ms", s) == pytest.approx(
        total["drain_inputs"] / frames)
    bursts = 'tick_phase_ms_count{phase="drain_inputs"}'
    assert g1[bursts] - g0[bursts] >= frames + sum(handled(s).values()) / 4
