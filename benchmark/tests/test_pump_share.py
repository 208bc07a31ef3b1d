"""``pump_between_share`` on a recorded scrape: the game's /metrics at
two instants of a CPU rehearsal of ``tile.roam`` (PR 30; 4 Hz), cut to the families the game-host readers take. Its times are
a CPU's and are compared with nothing here: the counts are."""
import os

import pytest

from run import load_module, parse_prom

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "pump_scrape")
LAYER = os.path.join(os.path.dirname(HERE), "layer_metrics")
CELL = {"cell": {"name": "no.such.cell"}}
FRAME = 'game_pump_packets_total{where="frame"}'
BETWEEN = 'game_pump_packets_total{where="between"}'


def scrapes():
    def edge(name):
        with open(os.path.join(DATA, name + ".txt")) as f:
            return {"game": parse_prom(f.read())}
    return {"open": edge("open"), "close": edge("close")}


def read(metric, s):
    return load_module(os.path.join(LAYER, metric + ".py"),
                       "reader_" + metric).read(s, None, CELL)


def test_every_packet_of_the_recorded_window_was_handled_between_ticks():
    s = scrapes()
    assert s["close"]["game"][BETWEEN] - s["open"]["game"][BETWEEN] == 453
    assert s["close"]["game"][FRAME] == s["open"]["game"][FRAME] == 0
    assert read("pump_between_share", s) == 100.0


@pytest.mark.parametrize("in_frame, share", [(151, 75.0), (453, 50.0)])
def test_the_share_is_of_both_places_over_the_window(in_frame, share):
    s = scrapes()
    s["close"]["game"][FRAME] += in_frame
    assert read("pump_between_share", s) == pytest.approx(share)


def test_a_program_without_the_counter_reads_nothing():
    """The parent of PR 30 exports no such series: no value, no error."""
    s = scrapes()
    for e in s.values():
        for k in (FRAME, BETWEEN):
            del e["game"][k]
    assert read("pump_between_share", s) is None
    s = scrapes()
    s["close"]["game"][BETWEEN] = s["open"]["game"][BETWEEN]
    assert read("pump_between_share", s) is None       # nothing handled


def test_bursts_between_ticks_count_in_pump_ms_per_frame():
    """A burst between ticks is a lone ``drain_inputs`` span: it is in
    ``pump_ms`` (all pump work over the frames served) and in no
    frame's duration."""
    s = scrapes()
    g0, g1 = s["open"]["game"], s["close"]["game"]
    frames = g1["tick_latency_ms_count"] - g0["tick_latency_ms_count"]
    spans = 'tick_phase_ms_count{phase="drain_inputs"}'
    assert g1[spans] - g0[spans] > 2 * frames
    total = 'tick_phase_ms_sum{phase="drain_inputs"}'
    assert read("pump_ms", s) == pytest.approx(
        (g1[total] - g0[total]) / frames)
    in_frames = sum(
        g1[k] - g0[k] for k in g1 if k.startswith("tick_phase_ms_sum")
        and "pacing_sleep" not in k and "overload_observe" not in k)
    frame_sum = g1["tick_latency_ms_sum"] - g0["tick_latency_ms_sum"]
    # the phases add up to the frames plus what ran between them
    assert in_frames > frame_sum
    assert in_frames - frame_sum <= g1[total] - g0[total]
    assert read("queue_wait_ms", s) is not None
