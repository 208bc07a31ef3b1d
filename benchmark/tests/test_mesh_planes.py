"""A capture with one device plane per chip, on hand-made planes: every
per-layer number is the BUSIEST plane's (benchmark/README.md), the
planes are held against each other (skew, the wait inside the
exchange), and the breakdown names the collectives as the capture
prints them."""
import pytest

import phase_reduce as P
import trace_reduce as T
from scrapes import busy_ms_per_frame, plane_of

MS = 1e6
STARTS = [100 * MS, 1100 * MS, 2100 * MS]


def ops_of(at, sweep_ms, permute_ms, a2a_ms):
    """One tick on one chip: halo exchange, sweep, migration."""
    return [
        ("%collective-permute.3 = f32[1024,3] collective-permute(...)",
         at, permute_ms * MS, "jit(tick)/gw.halo/ppermute"),
        ("%fusion.9 = f32[8]{0} fusion(...)", at + permute_ms * MS,
         sweep_ms * MS, "jit(tick)/gw.aoi/gw.aoi.gather/gather"),
        ("%all-to-all.1 = (s32[256]) all-to-all(...)",
         at + (permute_ms + sweep_ms) * MS, a2a_ms * MS,
         "jit(tick)/gw.migrate/all_to_all"),
        ("%copy.2 = f32[8]{0} copy(...)",
         at + (permute_ms + sweep_ms + a2a_ms) * MS, 1 * MS, ""),
    ]


# chip 0 sweeps for 60 ms and waits 2 ms in each exchange; chip 1 sweeps
# for 40 ms, so it arrives early and waits: 12 ms in each exchange
CHIPS = {"/device:TPU:0": (60.0, 2.0, 2.0), "/device:TPU:1": (40.0, 12.0, 12.0)}


def planes(raw: bool):
    out = []
    for name, (sweep, perm, a2a) in CHIPS.items():
        ops = [e for s in STARTS for e in ops_of(s, sweep, perm, a2a)]
        mods = [("jit_tick", s, (sweep + perm + a2a + 1) * MS)
                for s in STARTS]
        if raw:         # phase_reduce's form: (name, start, dur, stats)
            ops = [(n, s, d, {"tf_op": tf}) for n, s, d, tf in ops]
            mods = [(n, s, d, {"run_id": i})
                    for i, (n, s, d) in enumerate(mods)]
        else:
            ops = [(n, s, d) for n, s, d, _tf in ops]
        out.append({"name": name, "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]})
    return out


def test_trace_reduce_reads_the_busiest_plane():
    r = T.reduce_planes(planes(raw=False), frame_s=1.0)
    assert r["device_planes"] == 2 and r["frames"] == 2
    per = {p["plane"]: p for p in r["per_plane"]}
    assert per["/device:TPU:0"]["busy_s"] == pytest.approx(2 * 0.065)
    assert per["/device:TPU:1"]["busy_s"] == pytest.approx(2 * 0.065)
    # a tie on busy time here (the early chip waits inside the exchange):
    # make chip 0 the busier by its sweep
    slow = planes(raw=False)
    slow[0]["lines"][1]["events"].append(
        ("%fusion.11 = f32[8]{0} fusion(...)", 300 * MS, 20 * MS))
    r = T.reduce_planes(slow, frame_s=1.0)
    assert r["busiest"]["plane"] == "/device:TPU:0"
    assert r["busiest"]["busy_s"] == pytest.approx(2 * 0.065 + 0.020)
    # the device block's numbers stay means over the planes
    assert r["busy_s"] == pytest.approx(2 * 0.065 + 0.010)
    # the readers take the busiest plane's, never the mean
    assert plane_of(r) is r["busiest"]
    assert busy_ms_per_frame(r, {}) == pytest.approx(75.0)
    # the breakdown is that plane's, and names its collectives
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.9"] == pytest.approx(0.120)       # not 0.100
    assert ops["collective-permute.3"] == pytest.approx(0.004)
    assert ops["all-to-all.1"] == pytest.approx(0.004)


def test_one_plane_reads_as_ever():
    one = planes(raw=False)[:1]
    r = T.reduce_planes(one, frame_s=1.0)
    assert r["busiest"]["busy_s"] == r["busy_s"] == pytest.approx(0.13)
    assert busy_ms_per_frame(r, {}) == pytest.approx(65.0)
    # a reduction written before the rule (no ``busiest``) still reads
    old = {"busy_s": 0.13, "window_s": 2.0, "frames": 2}
    assert busy_ms_per_frame(old, {}) == pytest.approx(65.0)


def test_the_breakdown_is_the_ten_operations_that_took_most_time():
    one = planes(raw=False)[:1]
    one[0]["lines"][1]["events"] += [
        (f"%fusion.{100 + i} = f32[8]{{0}} fusion(...)",
         (300 + 10 * i) * MS, 5 * MS) for i in range(12)]
    r = T.reduce_planes(one, frame_s=1.0)
    ops = r["breakdown"]["device_ops"]
    assert len(ops) == 10
    assert [s for _n, s in ops] == sorted((s for _n, s in ops), reverse=True)
    # no place is kept for an operation that took less than the tenth
    assert min(s for _n, s in ops) >= 0.005


def test_phase_reduce_holds_the_planes_against_each_other():
    raw = planes(raw=True)
    raw[0]["lines"][1]["events"].append(
        ("%fusion.11 = f32[8]{0} fusion(...)", 300 * MS, 20 * MS,
         {"tf_op": "jit(tick)/gw.delta/select"}))
    r = P.reduce_phases(raw)
    assert r["device_planes"] == 2 and r["busiest"] == "/device:TPU:0"
    assert r["frames"] == 2
    assert r["busy_ms"] == pytest.approx(75.0)
    # scopes are the busiest plane's: its sweep of 60, not the mean 50
    assert r["scopes"]["gw.aoi"] == pytest.approx(60.0)
    assert r["scopes"]["gw.halo"] == pytest.approx(2.0)
    assert r["scopes"]["gw.migrate"] == pytest.approx(2.0)
    assert r["scopes"]["gw.delta"] == pytest.approx(10.0)
    assert r["unscoped_ms"] == pytest.approx(1.0)
    assert sum(r["scopes"][s] for s in ("gw.aoi", "gw.halo", "gw.migrate",
                                        "gw.delta")) + r["unscoped_ms"] \
        == pytest.approx(r["busy_ms"])
    assert [q["plane"] for q in r["planes"]] == ["/device:TPU:0",
                                                 "/device:TPU:1"]
    assert r["skew_ms"] == pytest.approx(75.0 - 65.0)
    assert "mesh_wait_ms" not in r


def test_mesh_readers_report_nothing_on_one_chip(monkeypatch):
    one = P.reduce_phases(planes(raw=True)[:1])
    assert one["device_planes"] == 1 and one["skew_ms"] == 0.0
    cell = {"cell": {"name": "x.y"}}
    monkeypatch.setitem(P._CACHE, "x.y", one)
    assert P.skew_ms(cell) is None
    assert P.scope_ms(cell, "gw.halo") == pytest.approx(2.0)
    two = P.reduce_phases(planes(raw=True))
    monkeypatch.setitem(P._CACHE, "x.y", two)
    assert P.skew_ms(cell) == pytest.approx(0.0)


def test_collectives_in_flight_are_named_where_the_plane_has_the_line():
    one = planes(raw=False)[:1]
    one[0]["lines"].append({"name": "Async XLA Ops", "events": [
        ("%collective-permute-start.4 = (f32[8]) collective-permute-start"
         "(...)", s + 1 * MS, 30 * MS) for s in STARTS] + [
        ("%copy-start.7 = (f32[8]) copy-start(...)", s, 50 * MS)
        for s in STARTS]})
    r = T.reduce_planes(one, frame_s=1.0)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["collective-permute-start.4 (async, in flight)"] \
        == pytest.approx(0.060)
    assert not any(n.startswith("copy-start") for n in ops)
    # busy time is the ops line's: what is in flight beside it adds none
    assert r["busy_s"] == pytest.approx(0.13)
