"""The trace reducer: on hand-made planes, and on the small capture
recorded on a TPU v5e (``make_small_trace.py``: three bursts of four
matrix products, 50 ms of sleep after each)."""
import glob
import os

import pytest

import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6


def test_union_counts_overlap_once():
    assert T.union([(0, 10), (5, 12), (20, 30), (30, 31)]) \
        == [(0, 12), (20, 31)]


def _tick(at):
    return [("%while.1 = (s32[]) while(...)", at, 500 * MS),  # holds the next
            ("%fusion.7 = f32[8]{0} fusion(...)", at + 50 * MS, 200 * MS),
            ("%sort.2 = f32[8]{0} sort(...)", at + 520 * MS, 80 * MS)]


def test_reduce_planes_by_hand():
    starts = [100 * MS, 1100 * MS, 2100 * MS]
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("pump", 0.0, 4000 * MS)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_tick", s, 600 * MS) for s in starts] + [
                ("jit_tick", 0.0, 30 * MS),      # cut by the capture's edge
                ("jit_scatter", 90 * MS, 1 * MS)]},
            {"name": "XLA Ops", "events": [
                e for s in starts for e in _tick(s)]}]},
    ]
    r = T.reduce_planes(planes, frame_s=1.0)
    # two whole frames, from the first start of the tick to the last
    assert r["frames"] == 2
    assert r["window_s"] == pytest.approx(2.0)
    assert r["busy_s"] == pytest.approx(2 * 0.58)     # 500 + 80, not 780
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["while.1"] == pytest.approx(1.0)
    assert ops["fusion.7"] == pytest.approx(0.4)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 400 ms after a tick's last op: the frame's remainder; 20 ms
    # between two ops: host work
    assert gaps["pacing sleep (frame remainder)"] == pytest.approx(0.8)
    assert gaps["between ops (host: flush, fetch, decode, pump)"] \
        == pytest.approx(0.04)
    assert r["longest_gap_s"] == pytest.approx(0.4)


def test_a_first_run_cut_at_its_beginning_marks_no_frame():
    """The capture began inside a tick: that run is two thirds there and
    starts where the capture does. Read as whole (the rule of one half)
    it would open a frame that lacks the operations before the cut."""
    starts = [1000 * MS, 2000 * MS, 3000 * MS]
    cut = [("%sort.2 = f32[8]{0} sort(...)", 320 * MS, 80 * MS)]
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_tick", s, 600 * MS) for s in starts] + [
            ("jit_tick", 0.0, 400 * MS)]},
        {"name": "XLA Ops", "events": cut + [
            e for s in starts for e in _tick(s)]}]}]
    r = T.reduce_planes(planes, frame_s=1.0)
    assert r["frames"] == 2 and r["window_s"] == pytest.approx(2.0)
    assert r["busy_s"] == pytest.approx(2 * 0.58)
    # the same rule where the scopes are read
    import phase_reduce as P
    mods = [("jit_tick", 0.0, 400 * MS, {})] + [
        ("jit_tick", s, 600 * MS, {}) for s in starts]
    lo, hi, frames, runs = P.frame_window(mods)
    assert (lo, hi, frames, len(runs)) == (starts[0], starts[-1], 2, 3)
    # a first run that is all there opens the first frame, as ever
    mods[0] = ("jit_tick", 0.0, 598 * MS, {})
    assert P.frame_window(mods)[:3] == (0.0, starts[-1], 3)


def test_no_device_plane_reads_nothing():
    r = T.reduce_planes([{"name": "/host:CPU", "lines": [
        {"name": "t", "events": [("x", 0.0, 5.0)]}]}], 1.0)
    assert r["busy_s"] is None and r["breakdown"] is None
    assert T.reduce_planes([], 1.0)["window_s"] is None


def test_recorded_tpu_trace():
    pb = glob.glob(os.path.join(HERE, "data", "*.xplane.pb"))
    if not pb:
        pytest.skip("no recorded capture under benchmark/tests/data")
    r = T.reduce_planes(T.read_xplane(pb[0]), frame_s=0.05)
    # twelve runs of the program, two sleeps of 50 ms between the first
    # and the last: the span is over 100 ms and the device is idle for
    # most of it, busy for some of it. The first run reads 13.4 us
    # against the others' 19.1-19.4: not all there by the rule for a
    # first run, so it opens no frame (one frame fewer, none bent)
    assert r["frames"] == 10
    assert r["window_s"] > 0.10
    assert 0.0 < r["busy_s"] < 0.5 * r["window_s"]
    assert r["breakdown"]["device_ops"][0][1] > 0
    assert r["longest_gap_s"] > 0.04
