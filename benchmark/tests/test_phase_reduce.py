"""The reducer by name, on the small SCOPED capture recorded on a TPU
v5e (``make_scoped_trace.py``: per frame one run of a program whose
``gw.aoi`` scope does 8 matrix products, 6 of them under
``gw.aoi.gather``, and whose ``gw.sync`` scope does 2; then 30 ms of
sleep under ``gw.decode_fanout`` inside ``gw.frame`` and 50 ms under
``gw.pacing_sleep`` outside it). Hand-built planes are in
``tests/test_trace_scopes.py`` (tier-1)."""
import os

import pytest

import phase_reduce as P
import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
CAPTURE = os.path.join(HERE, "data", "scoped",
                       "small_scoped_tpu_v5e.xplane.pb")


@pytest.fixture(scope="module")
def res():
    out = P.reduce_file(CAPTURE)
    assert out is not None
    return out


def test_both_scopes_are_found_by_name_from_tf_op(res):
    s = res["scopes"]
    assert set(s) == {"gw.aoi", "gw.aoi.gather", "gw.sync"}
    # known relative cost: 8 products against 2, 6 of the 8 in the gather
    assert s["gw.sync"] / s["gw.aoi"] == pytest.approx(0.25, abs=0.04)
    assert s["gw.aoi.gather"] / s["gw.aoi"] == pytest.approx(0.75, abs=0.04)
    assert res["unscoped_ms"] < 0.01 * res["busy_ms"]
    assert s["gw.aoi"] + s["gw.sync"] + res["unscoped_ms"] \
        == pytest.approx(res["busy_ms"], rel=1e-6)


def test_the_window_and_busy_time_are_trace_reduces(res):
    planes = T.read_xplane(CAPTURE)
    ref = T.reduce_planes(planes, frame_s=0.08)
    assert res["frames"] == ref["frames"] == 3
    assert res["window_s"] == pytest.approx(ref["window_s"], rel=1e-9)
    assert res["busy_ms"] * res["frames"] / 1e3 \
        == pytest.approx(ref["busy_s"], rel=1e-3)


def test_the_gaps_are_labelled_by_the_annotations(res):
    idle = res["idle"]
    assert res["host_line"] is not None
    assert idle["gw.decode_fanout"] == pytest.approx(30.0, abs=1.5)
    assert idle["gw.pacing_sleep"] == pytest.approx(50.0, abs=1.5)
    assert idle.get(P.UNLABELLED, 0.0) < 0.5
    assert sum(idle.values()) == pytest.approx(res["idle_ms"], rel=1e-6)


def test_the_clock_shift_is_about_a_millisecond(res):
    assert 0.5 <= res["clock_shift_ms"] <= 5.0
    assert res["clock_shift_by"] == "enqueue"
