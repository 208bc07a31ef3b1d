"""tools/bench_trend.py — the trajectory regression gate, in tier-1.

A healthy BENCH_r*/MULTICHIP_r* trajectory walked by directory must
PASS, an injected regression must FAIL, and a missing file is a usage
error, not a silent pass. The repository keeps no artifacts of its own
any more (the old ones were CPU numbers under device-metric names), so
every trajectory here is synthesised.
"""

import importlib.util
import json
import os

import pytest

pytestmark = pytest.mark.devprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TREND = _load("bench_trend")


def _bench_rec(value, entities=1000, platform="cpu", tick_ms=10.0,
               phase_ms=None, slo=None, scenarios=None):
    rec = {
        "metric": "entity_ticks_per_sec_per_chip", "value": value,
        "unit": "entity-ticks/s/chip", "vs_baseline": 0.0,
        "entities": entities, "tick_ms": tick_ms, "platform": platform,
        "stage": "full", "attempts": [],
        "phase_ms": phase_ms or {"aoi": 5.0, "move": 1.0},
    }
    if slo is not None:
        rec["slo"] = slo
    if scenarios is not None:
        rec["scenarios"] = scenarios
    return rec


def _write(tmp_path, name, rec):
    p = tmp_path / name
    p.write_text(json.dumps(rec))
    return str(p)


def test_real_checked_in_trajectory_passes(tmp_path):
    """--dir walks a whole trajectory (single-chip and mesh rounds
    side by side) and passes a healthy one."""
    _write(tmp_path, "BENCH_r01.json", _bench_rec(1000.0))
    _write(tmp_path, "BENCH_r02.json", _bench_rec(1400.0, tick_ms=7.0))
    _write(tmp_path, "MULTICHIP_r10.json", _multi_rec(100000.0))
    _write(tmp_path, "MULTICHIP_r11.json", _multi_rec(110000.0))
    assert TREND.main(["--dir", str(tmp_path)]) == 0


def test_missing_file_is_an_error(capsys):
    assert TREND.main([os.path.join(REPO, "BENCH_r99_missing.json")]) \
        == 1
    assert "missing file" in capsys.readouterr().err


def test_improvement_passes(tmp_path):
    f1 = _write(tmp_path, "BENCH_r01.json", _bench_rec(1000.0))
    f2 = _write(tmp_path, "BENCH_r02.json",
                _bench_rec(1500.0, tick_ms=7.0))
    assert TREND.main([f1, f2]) == 0


def test_injected_headline_regression_fails(tmp_path):
    f1 = _write(tmp_path, "BENCH_r01.json", _bench_rec(1000.0))
    f2 = _write(tmp_path, "BENCH_r02.json", _bench_rec(500.0))
    assert TREND.main([f1, f2]) == 2


def test_regression_vs_best_prior_not_just_previous(tmp_path):
    # r2 dipped (historic, not gated), r3 must still beat r1's best
    f1 = _write(tmp_path, "BENCH_r01.json", _bench_rec(1000.0))
    f2 = _write(tmp_path, "BENCH_r02.json", _bench_rec(100.0))
    f3 = _write(tmp_path, "BENCH_r03.json", _bench_rec(650.0))
    assert TREND.main([f1, f2, f3]) == 2  # 650 < 0.7 * 1000
    f3b = _write(tmp_path, "BENCH_r04.json", _bench_rec(900.0))
    assert TREND.main([f1, f2, f3b]) == 0


def test_phase_regression_fails(tmp_path):
    f1 = _write(tmp_path, "BENCH_r01.json",
                _bench_rec(1000.0, phase_ms={"aoi": 5.0}))
    f2 = _write(tmp_path, "BENCH_r02.json",
                _bench_rec(1000.0, phase_ms={"aoi": 9.0}))
    assert TREND.main([f1, f2]) == 2


def test_phase_regression_demoted_when_headline_improved(tmp_path):
    """The split gate catches a phase rotting UNDER a flat headline;
    when the headline itself improved past the threshold vs the same
    predecessor (r12 vs r05: different hardware, 1.9x faster headline,
    slower collect split), the split flags demote to NOTES — recorded,
    never gated. A flat headline keeps the hard gate (test above)."""
    f1 = _write(tmp_path, "BENCH_r01.json",
                _bench_rec(1000.0, phase_ms={"aoi": 5.0}))
    f2 = _write(tmp_path, "BENCH_r02.json",
                _bench_rec(1900.0, phase_ms={"aoi": 9.0}))
    assert TREND.main([f1, f2]) == 0
    # just-under-threshold improvement still gates the split
    f2b = _write(tmp_path, "BENCH_r03.json",
                 _bench_rec(1200.0, phase_ms={"aoi": 20.0}))
    assert TREND.main([f1, f2b]) == 2


def test_shape_change_is_not_compared(tmp_path):
    f1 = _write(tmp_path, "BENCH_r01.json",
                _bench_rec(1000.0, entities=1000))
    f2 = _write(tmp_path, "BENCH_r02.json",
                _bench_rec(10.0, entities=8))  # different shape
    assert TREND.main([f1, f2]) == 0


def test_slo_pass_to_fail_transition_fails(tmp_path):
    ok = {"target_ms": 16.0, "p99_ms": 8.0, "pass": True}
    bad = {"target_ms": 16.0, "p99_ms": 33.0, "pass": False}
    f1 = _write(tmp_path, "BENCH_r01.json", _bench_rec(1000.0, slo=ok))
    f2 = _write(tmp_path, "BENCH_r02.json",
                _bench_rec(1000.0, slo=bad))
    assert TREND.main([f1, f2]) == 2
    # fail -> fail is the recorded status quo, not a regression
    f1b = _write(tmp_path, "BENCH_r03.json",
                 _bench_rec(1000.0, slo=bad))
    f2b = _write(tmp_path, "BENCH_r04.json",
                 _bench_rec(1000.0, slo=bad))
    assert TREND.main([f1b, f2b]) == 0


def test_signature_drift_is_informational_not_gated(tmp_path):
    """A workload-signature class change between comparable rounds is
    surfaced as a NOTE but never fails the gate (ISSUE 11: the
    signature describes the workload, not the implementation)."""
    r1 = _bench_rec(1000.0)
    r1["workload_signature"] = {"sig": "churn=flock_like|density=exact"}
    r2 = _bench_rec(1100.0)
    r2["workload_signature"] = {
        "sig": "churn=teleport_like|density=over_k"}
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    problems: list = []
    notes: list = []
    TREND.check_bench([f1, f2], 0.30, problems, notes)
    assert problems == []
    assert any("workload signature drifted" in n for n in notes)
    assert TREND.main([f1, f2]) == 0
    # stable signature: just the informational stamp, no drift note
    r2["workload_signature"] = dict(r1["workload_signature"])
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    problems, notes = [], []
    TREND.check_bench([f1, f2], 0.30, problems, notes)
    assert problems == []
    assert not any("drifted" in n for n in notes)


def _gov_block(throughput, n=1024, schedule=("flock", "teleport",
                                             "hotspot")):
    return {"schedule": list(schedule), "n": n,
            "throughput": throughput,
            "phases": [], "static_wall_s": {"default": 1.0}}


def test_governor_mode_headline_is_its_own_anchor_series(tmp_path):
    """A headline stamped bench_mode=governor never gates against (or
    anchors) static rounds — the (entities, platform, mode) shape key
    (ISSUE 13): the governor number includes swap dynamics and a
    scenario schedule, a different experiment entirely."""
    f1 = _write(tmp_path, "BENCH_r01.json", _bench_rec(1000.0))
    gov_rec = _bench_rec(300.0)  # 70% "down" vs r1 — but governor-mode
    gov_rec["bench_mode"] = "governor"
    f2 = _write(tmp_path, "BENCH_r02.json", gov_rec)
    assert TREND.main([f1, f2]) == 0
    # and a static round after it gates against r1, not the governor
    f3 = _write(tmp_path, "BENCH_r03.json", _bench_rec(950.0))
    assert TREND.main([f1, f2, f3]) == 0
    f3b = _write(tmp_path, "BENCH_r03.json", _bench_rec(500.0))
    assert TREND.main([f1, f2, f3b]) == 2


def test_governor_block_series_gated_and_regression_fails(tmp_path):
    """The governor schedule block's throughput is its own series:
    same schedule shape gates vs the best prior; a skipped round
    neither gates nor anchors; an injected regression fails."""
    r1 = _bench_rec(1000.0)
    r1["governor"] = _gov_block(2000.0)
    r2 = _bench_rec(1000.0)
    r2["governor"] = _gov_block(1900.0)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 0
    # injected governor regression: static headline flat, governor
    # throughput down 60% -> gate fails
    r3 = _bench_rec(1000.0)
    r3["governor"] = _gov_block(800.0)
    f3 = _write(tmp_path, "BENCH_r03.json", r3)
    assert TREND.main([f1, f2, f3]) == 2
    # a skipped-governor round between them is not a gate or an anchor
    r3b = _bench_rec(1000.0)
    r3b["governor"] = {"skipped": "--governor not requested"}
    f3b = _write(tmp_path, "BENCH_r03.json", r3b)
    assert TREND.main([f1, f2, f3b]) == 0
    # a different schedule shape is a different series
    r3c = _bench_rec(1000.0)
    r3c["governor"] = _gov_block(800.0, schedule=("flock", "shrink"))
    f3c = _write(tmp_path, "BENCH_r03.json", r3c)
    assert TREND.main([f1, f2, f3c]) == 0


def test_governor_gate_survives_headline_shape_change(tmp_path):
    """The governor series is keyed by its OWN (n, platform, schedule)
    shape: a round that changes the HEADLINE entity count (so the
    headline has no prior and is not gated) must still gate its
    governor block against the prior rounds' — the early headline
    return must not swallow the governor comparison (review
    finding)."""
    r1 = _bench_rec(1000.0, entities=1000)
    r1["governor"] = _gov_block(2000.0)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    # headline shape changes (no prior -> headline ungated) while the
    # governor block regresses 60% at the SAME governor shape
    r2 = _bench_rec(5000.0, entities=4096)
    r2["governor"] = _gov_block(800.0)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    # same headline-shape change with a healthy governor block passes
    r2b = _bench_rec(5000.0, entities=4096)
    r2b["governor"] = _gov_block(1950.0)
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 0


def test_scenario_value_regression_fails(tmp_path):
    sc_ok = {"hotspot": {"value": 500.0, "entities": 512,
                         "tick_ms": 1.0}}
    sc_bad = {"hotspot": {"value": 100.0, "entities": 512,
                          "tick_ms": 5.0}}
    f1 = _write(tmp_path, "BENCH_r01.json",
                _bench_rec(1000.0, scenarios=sc_ok))
    f2 = _write(tmp_path, "BENCH_r02.json",
                _bench_rec(1000.0, scenarios=sc_bad))
    assert TREND.main([f1, f2]) == 2


def test_suspect_and_failed_rounds_are_skipped(tmp_path):
    f1 = _write(tmp_path, "BENCH_r01.json",
                {"cmd": "x", "rc": 1, "parsed": None, "tail": ""})
    rec = _bench_rec(1000.0)
    rec["timing_suspect"] = "2x scan took 1.1x"
    f2 = _write(tmp_path, "BENCH_r02.json", rec)
    f3 = _write(tmp_path, "BENCH_r03.json", _bench_rec(900.0))
    # only r3 has a trustworthy headline -> nothing to gate
    assert TREND.main([f1, f2, f3]) == 0


def test_multichip_ok_regression_fails(tmp_path):
    f1 = _write(tmp_path, "MULTICHIP_r01.json",
                {"n_devices": 8, "rc": 0, "ok": True, "tail": "",
                 "skipped": False})
    f2 = _write(tmp_path, "MULTICHIP_r02.json",
                {"n_devices": 8, "rc": 1, "ok": False, "tail": "",
                 "skipped": False})
    assert TREND.main([f1, f2]) == 2
    f2b = _write(tmp_path, "MULTICHIP_r03.json",
                 {"n_devices": 8, "rc": 0, "ok": True, "tail": "",
                  "skipped": False})
    assert TREND.main([f1, f2b]) == 0


def test_threshold_knob(tmp_path):
    f1 = _write(tmp_path, "BENCH_r01.json", _bench_rec(1000.0))
    f2 = _write(tmp_path, "BENCH_r02.json", _bench_rec(850.0))
    assert TREND.main([f1, f2]) == 0              # within default 30%
    assert TREND.main(["--threshold", "0.1", f1, f2]) == 2


def _multi_rec(value, eff=0.8, n=65536, n_dev=8, platform="cpu",
               **extra):
    rec = {
        "n_devices": n_dev, "rc": 0, "ok": True, "skipped": False,
        "tail": "",
        "headline": {
            "entity_ticks_per_sec_mesh": value,
            "per_chip_efficiency": eff,
            "n_entities": n, "platform": platform, "n_devices": n_dev,
        },
    }
    rec.update(extra)
    return rec


def test_multichip_headline_regression_fails(tmp_path):
    f1 = _write(tmp_path, "MULTICHIP_r10.json", _multi_rec(100000.0))
    f2 = _write(tmp_path, "MULTICHIP_r11.json", _multi_rec(60000.0))
    assert TREND.main([f1, f2]) == 2
    f2b = _write(tmp_path, "MULTICHIP_r12.json", _multi_rec(95000.0))
    assert TREND.main([f1, f2b]) == 0


def test_multichip_efficiency_drop_fails(tmp_path):
    """A mesh that keeps throughput but burns per-chip efficiency
    (>30% drop) regresses even with the headline flat."""
    f1 = _write(tmp_path, "MULTICHIP_r10.json",
                _multi_rec(100000.0, eff=0.8))
    f2 = _write(tmp_path, "MULTICHIP_r11.json",
                _multi_rec(100000.0, eff=0.5))
    assert TREND.main([f1, f2]) == 2
    f2b = _write(tmp_path, "MULTICHIP_r12.json",
                 _multi_rec(100000.0, eff=0.7))
    assert TREND.main([f1, f2b]) == 0


def test_multichip_shape_change_not_compared(tmp_path):
    """A different (entities, platform, n_devices) shape is a new
    baseline, not a regression."""
    f1 = _write(tmp_path, "MULTICHIP_r10.json",
                _multi_rec(100000.0, n=65536))
    f2 = _write(tmp_path, "MULTICHIP_r11.json",
                _multi_rec(20000.0, n=8192))
    assert TREND.main([f1, f2]) == 0
    f3 = _write(tmp_path, "MULTICHIP_r12.json",
                _multi_rec(20000.0, n_dev=16))
    assert TREND.main([f1, f3]) == 0


def test_multichip_dryrun_rounds_not_headline_gated(tmp_path):
    """Pre-r10 dryrun-only records neither gate nor anchor the mesh
    headline; the ok/rc invariants still apply."""
    f1 = _write(tmp_path, "MULTICHIP_r05.json",
                {"n_devices": 8, "rc": 0, "ok": True, "tail": "",
                 "skipped": False})
    f2 = _write(tmp_path, "MULTICHIP_r10.json", _multi_rec(100.0))
    assert TREND.main([f1, f2]) == 0


def _sa_block(p99, records=2048, clients=4, passed=None):
    return {"target_ms": 16.0, "records_per_tick": records,
            "clients": clients,
            "e2e": {"samples": 1000, "p50_ms": p99 / 3,
                    "p90_ms": p99 / 2, "p99_ms": p99},
            "hops": {}, "pass": (p99 <= 16.0 if passed is None
                                 else passed),
            "stamp_overhead_pct_of_budget": 0.05}


def test_sync_age_series_gated_and_regression_fails(tmp_path):
    """The sync-age loopback block's e2e p99 is its own
    lower-is-better series at the same (records, clients, platform)
    shape (ISSUE 15): a >30% p99 regression fails, skip/error rounds
    neither gate nor anchor, shape changes are new series."""
    r1 = _bench_rec(1000.0)
    r1["sync_age"] = _sa_block(10.0)
    r2 = _bench_rec(1000.0)
    r2["sync_age"] = _sa_block(11.0)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 0
    # injected regression: headline flat, e2e p99 up 3x -> gate fails
    r3 = _bench_rec(1000.0)
    r3["sync_age"] = _sa_block(30.0, passed=False)
    f3 = _write(tmp_path, "BENCH_r03.json", r3)
    assert TREND.main([f1, f2, f3]) == 2
    # an honest skip neither gates nor anchors
    r3b = _bench_rec(1000.0)
    r3b["sync_age"] = {"skipped": "BENCH_SYNC_AGE=0"}
    f3b = _write(tmp_path, "BENCH_r03.json", r3b)
    assert TREND.main([f1, f2, f3b]) == 0
    # a different harness shape is a different series
    r3c = _bench_rec(1000.0)
    r3c["sync_age"] = _sa_block(30.0, records=32768, passed=False)
    f3c = _write(tmp_path, "BENCH_r03.json", r3c)
    assert TREND.main([f1, f2, f3c]) == 0


def test_sync_age_pass_to_fail_transition_fails(tmp_path):
    """A verdict flip pass -> fail at the same shape always fails,
    even inside the 30% p99 band (the slo-flip rule)."""
    r1 = _bench_rec(1000.0)
    r1["sync_age"] = _sa_block(15.0)           # pass, close to target
    r2 = _bench_rec(1000.0)
    r2["sync_age"] = _sa_block(17.0, passed=False)  # +13%, but a flip
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    # fail -> fail within the band is the recorded status quo
    r1b = _bench_rec(1000.0)
    r1b["sync_age"] = _sa_block(20.0, passed=False)
    r2b = _bench_rec(1000.0)
    r2b["sync_age"] = _sa_block(22.0, passed=False)
    f1b = _write(tmp_path, "BENCH_r03.json", r1b)
    f2b = _write(tmp_path, "BENCH_r04.json", r2b)
    assert TREND.main([f1b, f2b]) == 0


def test_sync_age_gate_survives_headline_shape_change(tmp_path):
    """Like the governor series: a round that changes the headline
    entity count must still gate its sync_age block against prior
    rounds' — the early headline return must not swallow it."""
    r1 = _bench_rec(1000.0, entities=1000)
    r1["sync_age"] = _sa_block(10.0)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    r2 = _bench_rec(5000.0, entities=4096)
    r2["sync_age"] = _sa_block(30.0, passed=False)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    r2b = _bench_rec(5000.0, entities=4096)
    r2b["sync_age"] = _sa_block(10.5)
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 0


def _rs_block(p99, gap, entities=64, passed=None):
    return {
        "entities": entities,
        "bubble": {"samples": 90, "p50_ms": p99 / 4, "p90_ms": p99 / 2,
                   "p99_ms": p99},
        "tick": {"samples": 90, "p50_ms": 17.0, "p90_ms": 18.0,
                 "p99_ms": 20.0},
        "bubble_budget_ms": 4.0,
        "serve_gap": gap,
        "serve_gap_ref": "scan_marginal",
        "pass": (p99 <= 4.0 if passed is None else passed),
    }


def test_residency_series_gated_and_regression_fails(tmp_path):
    """The residency block's bubble p99 and serve_gap are their own
    lower-is-better series at the same (entities, platform) shape
    (ISSUE 16): an injected regression in either fails, skip/error
    rounds neither gate nor anchor, shape changes are new series."""
    r1 = _bench_rec(1000.0)
    r1["residency"] = _rs_block(2.0, 1.4)
    r2 = _bench_rec(1000.0)
    r2["residency"] = _rs_block(2.2, 1.5)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 0
    # injected bubble regression: headline flat, bubble p99 up 4x
    r3 = _bench_rec(1000.0)
    r3["residency"] = _rs_block(8.0, 1.4, passed=False)
    f3 = _write(tmp_path, "BENCH_r03.json", r3)
    assert TREND.main([f1, f2, f3]) == 2
    # injected serve_gap regression with a healthy bubble
    r3g = _bench_rec(1000.0)
    r3g["residency"] = _rs_block(2.0, 2.5)
    f3g = _write(tmp_path, "BENCH_r03.json", r3g)
    assert TREND.main([f1, f2, f3g]) == 2
    # an honest skip neither gates nor anchors
    r3b = _bench_rec(1000.0)
    r3b["residency"] = {"skipped": "BENCH_RESIDENCY=0"}
    f3b = _write(tmp_path, "BENCH_r03.json", r3b)
    assert TREND.main([f1, f2, f3b]) == 0
    # a different residency shape is a different series
    r3c = _bench_rec(1000.0)
    r3c["residency"] = _rs_block(8.0, 2.5, entities=192, passed=False)
    f3c = _write(tmp_path, "BENCH_r03.json", r3c)
    assert TREND.main([f1, f2, f3c]) == 0


def test_residency_pass_to_fail_and_inf_fail(tmp_path):
    """A verdict flip pass -> fail at the same shape always fails (the
    slo-flip rule), and a latest round whose bubble p99 lands past the
    last bucket ("inf", the ptiles convention) fails against any
    finite prior."""
    r1 = _bench_rec(1000.0)
    r1["residency"] = _rs_block(3.5, 1.4)            # pass, near budget
    r2 = _bench_rec(1000.0)
    r2["residency"] = _rs_block(4.2, 1.4, passed=False)  # flip
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    # "inf" latest vs finite prior: strongest regression, gated
    r2b = _bench_rec(1000.0)
    r2b["residency"] = _rs_block(3.5, 1.4, passed=False)
    r2b["residency"]["bubble"]["p99_ms"] = "inf"
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 2
    # zero-bubble prior + sub-slack latest: the 0.25 ms absolute slack
    # keeps timer noise from gating a healthy round
    r1c = _bench_rec(1000.0)
    r1c["residency"] = _rs_block(0.0, 1.4)
    r2c = _bench_rec(1000.0)
    r2c["residency"] = _rs_block(0.2, 1.4)
    f1c = _write(tmp_path, "BENCH_r03.json", r1c)
    f2c = _write(tmp_path, "BENCH_r04.json", r2c)
    assert TREND.main([f1c, f2c]) == 0


def test_residency_gate_survives_headline_shape_change(tmp_path):
    """Like the governor/sync_age series: a round that changes the
    headline entity count must still gate its residency block against
    prior rounds' — the early headline return must not swallow it."""
    r1 = _bench_rec(1000.0, entities=1000)
    r1["residency"] = _rs_block(2.0, 1.4)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    r2 = _bench_rec(5000.0, entities=4096)
    r2["residency"] = _rs_block(8.0, 1.4, passed=False)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    r2b = _bench_rec(5000.0, entities=4096)
    r2b["residency"] = _rs_block(2.1, 1.45)
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 0


def _fo_block(latency, lost=0, dup=0, rejected=0, entities=48,
              replay_ok=True, passed=None):
    return {
        "entities": entities,
        "replication_bytes_per_tick": 5163.3,
        "client_sync_bytes_per_tick": 1214.4,
        "standby_apply_ms_per_tick": 0.9,
        "promotion_latency_ticks": latency,
        "lag_budget_ticks": 16,
        "entities_lost": lost,
        "entities_duplicated": dup,
        "frames_applied": 20,
        "frames_rejected": rejected,
        "decision_log_replay_ok": replay_ok,
        "pass": ((lost == 0 and dup == 0 and latency <= 16)
                 if passed is None else passed),
    }


def test_failover_entity_loss_always_fails(tmp_path):
    """ISSUE 18: ANY lost or duplicated EntityID across promotion
    fails unconditionally — conservation needs no prior round (a lost
    entity is a bug, not a trend), and a flat headline must not hide
    it. Torn frames and a failed decision-log replay gate the same
    way."""
    r1 = _bench_rec(1000.0)  # prior round without a failover block
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    r2 = _bench_rec(1000.0)
    r2["failover"] = _fo_block(1, lost=2, passed=False)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    r2b = _bench_rec(1000.0)
    r2b["failover"] = _fo_block(1, dup=1, passed=False)
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 2
    r2c = _bench_rec(1000.0)
    r2c["failover"] = _fo_block(1, rejected=3, passed=False)
    f2c = _write(tmp_path, "BENCH_r02.json", r2c)
    assert TREND.main([f1, f2c]) == 2
    r2d = _bench_rec(1000.0)
    r2d["failover"] = _fo_block(1, replay_ok=False, passed=False)
    f2d = _write(tmp_path, "BENCH_r02.json", r2d)
    assert TREND.main([f1, f2d]) == 2
    # a clean block with no prior is a new anchor, not a gate
    r2e = _bench_rec(1000.0)
    r2e["failover"] = _fo_block(1)
    f2e = _write(tmp_path, "BENCH_r02.json", r2e)
    assert TREND.main([f1, f2e]) == 0


def test_failover_promotion_latency_lower_is_better(tmp_path):
    """The promotion latency gates against the best (lowest) prior at
    the same (entities, platform) shape with a 1-tick absolute slack;
    skip rounds neither gate nor anchor; shape changes are new
    series."""
    r1 = _bench_rec(1000.0)
    r1["failover"] = _fo_block(1)
    r2 = _bench_rec(1000.0)
    r2["failover"] = _fo_block(2)  # within 1.3x + 1 tick slack
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 0
    # injected latency regression: headline flat, promotion 5x slower
    r3 = _bench_rec(1000.0)
    r3["failover"] = _fo_block(5)
    f3 = _write(tmp_path, "BENCH_r03.json", r3)
    assert TREND.main([f1, f2, f3]) == 2
    # an honest skip neither gates nor anchors
    r3b = _bench_rec(1000.0)
    r3b["failover"] = {"skipped": "BENCH_FAILOVER=0"}
    f3b = _write(tmp_path, "BENCH_r03.json", r3b)
    assert TREND.main([f1, f2, f3b]) == 0
    # a different harness shape is a different series
    r3c = _bench_rec(1000.0)
    r3c["failover"] = _fo_block(5, entities=192)
    f3c = _write(tmp_path, "BENCH_r03.json", r3c)
    assert TREND.main([f1, f2, f3c]) == 0


def test_failover_pass_to_fail_transition_fails(tmp_path):
    """A verdict flip pass -> fail at the same shape always fails,
    even when every individual number stays inside its band (the
    slo-flip rule)."""
    r1 = _bench_rec(1000.0)
    r1["failover"] = _fo_block(1)
    r2 = _bench_rec(1000.0)
    r2["failover"] = _fo_block(2, passed=False)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    # fail -> fail is the recorded status quo, not a regression
    r1b = _bench_rec(1000.0)
    r1b["failover"] = _fo_block(2, passed=False)
    r2b = _bench_rec(1000.0)
    r2b["failover"] = _fo_block(2, passed=False)
    f1b = _write(tmp_path, "BENCH_r03.json", r1b)
    f2b = _write(tmp_path, "BENCH_r04.json", r2b)
    assert TREND.main([f1b, f2b]) == 0


def test_failover_gate_survives_headline_shape_change(tmp_path):
    """Like the governor/sync_age/residency series: a round that
    changes the headline entity count must still gate its failover
    block against prior rounds' — the early headline return must not
    swallow the conservation check."""
    r1 = _bench_rec(1000.0, entities=1000)
    r1["failover"] = _fo_block(1)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    r2 = _bench_rec(5000.0, entities=4096)
    r2["failover"] = _fo_block(1, lost=1, passed=False)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    r2b = _bench_rec(5000.0, entities=4096)
    r2b["failover"] = _fo_block(1)
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 0


def _rb_block(recovery, lost=0, dup=0, moved=24, replay_ok=True,
              passed=None):
    return {
        "entities": 96,
        "donor_p99_before_ms": 12.1,
        "donor_p99_after_ms": 10.4,
        "batch": 24,
        "entities_moved": moved,
        "aborts": 0,
        "donor_recovery_windows": recovery,
        "entities_lost": lost,
        "entities_duplicated": dup,
        "decision_log_replay_ok": replay_ok,
        "pass": ((lost == 0 and dup == 0 and recovery is not None)
                 if passed is None else passed),
    }


def test_rebalance_entity_loss_always_fails(tmp_path):
    """ISSUE 19: ANY lost or duplicated entity across the automated
    handoff fails unconditionally — conservation needs no prior round
    — and a failed DecisionLog byte replay gates the same way."""
    r1 = _bench_rec(1000.0)  # prior round without a rebalance block
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    r2 = _bench_rec(1000.0)
    r2["rebalance"] = _rb_block(2, lost=3, passed=False)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    r2b = _bench_rec(1000.0)
    r2b["rebalance"] = _rb_block(2, dup=1, passed=False)
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 2
    r2c = _bench_rec(1000.0)
    r2c["rebalance"] = _rb_block(2, replay_ok=False, passed=False)
    f2c = _write(tmp_path, "BENCH_r02.json", r2c)
    assert TREND.main([f1, f2c]) == 2
    # a clean block with no prior is a new anchor, not a gate
    r2d = _bench_rec(1000.0)
    r2d["rebalance"] = _rb_block(2)
    f2d = _write(tmp_path, "BENCH_r02.json", r2d)
    assert TREND.main([f1, f2d]) == 0


def test_rebalance_recovery_latency_lower_is_better(tmp_path):
    """Donor recovery latency gates against the best (lowest) prior
    at the same (entities_moved, platform) shape with a 1-window
    absolute slack; an aborted round (recovery None) and an honest
    skip neither gate nor anchor; a different moved-count is a
    different series."""
    r1 = _bench_rec(1000.0)
    r1["rebalance"] = _rb_block(2)
    r2 = _bench_rec(1000.0)
    r2["rebalance"] = _rb_block(3)  # within 1.3x + 1 window slack
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 0
    # injected regression: headline flat, recovery 4x slower
    r3 = _bench_rec(1000.0)
    r3["rebalance"] = _rb_block(8)
    f3 = _write(tmp_path, "BENCH_r03.json", r3)
    assert TREND.main([f1, f2, f3]) == 2
    # an aborted round carries recovery None: no gate, no anchor
    r3b = _bench_rec(1000.0)
    r3b["rebalance"] = _rb_block(None, moved=12, passed=False)
    r3b["rebalance"]["aborts"] = 1
    f3b = _write(tmp_path, "BENCH_r03.json", r3b)
    assert TREND.main([f1, f2, f3b]) == 0
    # an honest skip neither gates nor anchors
    r3c = _bench_rec(1000.0)
    r3c["rebalance"] = {"skipped": "BENCH_REBALANCE=0"}
    f3c = _write(tmp_path, "BENCH_r03.json", r3c)
    assert TREND.main([f1, f2, f3c]) == 0
    # a different moved-count is a different series
    r3d = _bench_rec(1000.0)
    r3d["rebalance"] = _rb_block(8, moved=48)
    f3d = _write(tmp_path, "BENCH_r03.json", r3d)
    assert TREND.main([f1, f2, f3d]) == 0


def test_rebalance_pass_to_fail_and_shape_change(tmp_path):
    """A verdict flip pass -> fail at the same shape always fails;
    the conservation gate survives a headline-shape change (the early
    headline return must not swallow it)."""
    r1 = _bench_rec(1000.0)
    r1["rebalance"] = _rb_block(2)
    r2 = _bench_rec(1000.0)
    r2["rebalance"] = _rb_block(2, passed=False)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    # headline shape change + a lost entity: still gated
    r2b = _bench_rec(5000.0, entities=4096)
    r2b["rebalance"] = _rb_block(2, lost=1, passed=False)
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 2


def _ra_block(ratio=0.96, on_realloc=0, off_realloc=19, passed=None):
    return {
        "entities": 192,
        "capacity": 1024,
        "windows": 6,
        "ticks_per_window": 24,
        "tick_hz": 30.0,
        "on_ms_per_tick": round(30.0 * ratio, 3),
        "off_ms_per_tick": 30.0,
        "ratio": ratio,
        "on_census": {"samples": 12, "realloc": on_realloc,
                      "aliased": 19 - on_realloc,
                      "skipped_deleted": 0},
        "off_census": {"samples": 12, "realloc": off_realloc,
                       "aliased": 19 - off_realloc,
                       "skipped_deleted": 0},
        "pass": ((on_realloc == 0 and off_realloc >= 1
                  and ratio < 1.0) if passed is None else passed),
    }


def test_resident_ab_on_arm_realloc_always_fails(tmp_path):
    """ISSUE 20: ANY re-allocated carry lane in the donation-on arm's
    census fails unconditionally — the resident runtime's contract is
    zero steady-state allocation and needs no prior round; an off arm
    that also reads zero means the A/B measured nothing."""
    r1 = _bench_rec(1000.0)  # prior round without a resident_ab block
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    r2 = _bench_rec(1000.0)
    r2["resident_ab"] = _ra_block(on_realloc=3, passed=False)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    # a list-typed census realloc (the raw snapshot form) gates too
    r2b = _bench_rec(1000.0)
    r2b["resident_ab"] = _ra_block(passed=False)
    r2b["resident_ab"]["on_census"]["realloc"] = ["pos", "vel"]
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 2
    # an off arm with zero churn measured nothing: flagged
    r2c = _bench_rec(1000.0)
    r2c["resident_ab"] = _ra_block(off_realloc=0, passed=False)
    f2c = _write(tmp_path, "BENCH_r02.json", r2c)
    assert TREND.main([f1, f2c]) == 2
    # a clean block with no prior is a new anchor, not a gate
    r2d = _bench_rec(1000.0)
    r2d["resident_ab"] = _ra_block()
    f2d = _write(tmp_path, "BENCH_r02.json", r2d)
    assert TREND.main([f1, f2d]) == 0


def test_resident_ab_ratio_lower_is_better(tmp_path):
    """The on/off ratio gates against the best (lowest) prior at the
    same (entities, platform) shape — a pure ratio, no absolute
    slack; an honest skip neither gates nor anchors."""
    r1 = _bench_rec(1000.0)
    r1["resident_ab"] = _ra_block(ratio=0.90)
    r2 = _bench_rec(1000.0)
    r2["resident_ab"] = _ra_block(ratio=0.96)  # within 1.3x of 0.90
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 0
    # injected regression: headline flat, on arm now 1.5x the off arm
    r3 = _bench_rec(1000.0)
    r3["resident_ab"] = _ra_block(ratio=1.5, passed=False)
    f3 = _write(tmp_path, "BENCH_r03.json", r3)
    assert TREND.main([f1, f2, f3]) == 2
    # an honest skip neither gates nor anchors
    r3b = _bench_rec(1000.0)
    r3b["resident_ab"] = {"skipped": "BENCH_RESIDENT_AB=0"}
    f3b = _write(tmp_path, "BENCH_r03.json", r3b)
    assert TREND.main([f1, f2, f3b]) == 0
    # a different entity count is a different series
    r3c = _bench_rec(1000.0)
    r3c["resident_ab"] = _ra_block(ratio=1.5, passed=False)
    r3c["resident_ab"]["entities"] = 48
    f3c = _write(tmp_path, "BENCH_r03.json", r3c)
    assert TREND.main([f1, f2, f3c]) == 0


def test_resident_ab_pass_to_fail_and_shape_change(tmp_path):
    """A verdict flip pass -> fail at the same shape always fails;
    the zero-realloc gate survives a headline-shape change (the early
    headline return must not swallow it)."""
    r1 = _bench_rec(1000.0)
    r1["resident_ab"] = _ra_block()
    r2 = _bench_rec(1000.0)
    r2["resident_ab"] = _ra_block(passed=False)
    f1 = _write(tmp_path, "BENCH_r01.json", r1)
    f2 = _write(tmp_path, "BENCH_r02.json", r2)
    assert TREND.main([f1, f2]) == 2
    # headline shape change + an on-arm realloc: still gated
    r2b = _bench_rec(5000.0, entities=4096)
    r2b["resident_ab"] = _ra_block(on_realloc=2, passed=False)
    f2b = _write(tmp_path, "BENCH_r02.json", r2b)
    assert TREND.main([f1, f2b]) == 2
