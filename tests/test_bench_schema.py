"""tools/bench_schema.py — artifact schema validation, in tier-1.

Every artifact of a BENCH_r*/MULTICHIP_r* trajectory must validate (so
a malformed stamp can never land), and the checker must actually catch
malformation (required keys, device-plane blocks since r8, multichip
invariants). The repository keeps no artifacts of its own any more
(the old ones were CPU numbers under device-metric names): the
trajectory these tests walk is synthesised per test.
"""

import importlib.util
import glob
import json
import os

import pytest

pytestmark = pytest.mark.devprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "bench_schema_under_test",
    os.path.join(REPO, "tools", "bench_schema.py"))
SCHEMA = importlib.util.module_from_spec(spec)
spec.loader.exec_module(SCHEMA)


def _trajectory(tmp_path):
    """A small valid trajectory in a directory of its own: two
    single-chip rounds and one measured-mesh round."""
    d = tmp_path / "trajectory"
    d.mkdir()
    for name, rec in (("BENCH_r08.json", _full_rec()),
                      ("BENCH_r09.json", _full_rec(value=120.0)),
                      ("MULTICHIP_r10.json", _multi_rec())):
        (d / name).write_text(json.dumps(rec))
    return str(d)


def test_every_checked_in_artifact_validates(tmp_path):
    d = _trajectory(tmp_path)
    files = sorted(glob.glob(os.path.join(d, "BENCH_r*.json"))
                   + glob.glob(os.path.join(d, "MULTICHIP_r*.json")))
    assert len(files) == 3
    problems = {os.path.basename(f): SCHEMA.validate_file(f)
                for f in files}
    assert all(not errs for errs in problems.values()), problems


def test_cli_passes_on_repo(tmp_path, capsys):
    assert SCHEMA.main(["--dir", _trajectory(tmp_path)]) == 0
    # an empty directory has nothing to violate; a NAMED missing file
    # is a usage error
    empty = tmp_path / "empty"
    empty.mkdir()
    assert SCHEMA.main(["--dir", str(empty)]) == 0
    assert SCHEMA.main([str(empty / "BENCH_r01.json")]) == 1
    # a malformed artifact in the walked directory turns the CLI red
    bad = tmp_path / "trajectory" / "BENCH_r11.json"
    bad.write_text(json.dumps({"value": 1.0}))
    assert SCHEMA.main(["--dir", str(tmp_path / "trajectory")]) != 0


def _full_rec(rno=8, **extra):
    rec = {
        "metric": "entity_ticks_per_sec_per_chip", "value": 100.0,
        "unit": "entity-ticks/s/chip", "vs_baseline": 0.0,
        "entities": 1024, "tick_ms": 5.0, "platform": "cpu",
        "attempts": [],
        "sweep_impl": "ranges", "topk_impl": "sort",
        "sort_impl": "argsort", "skin": 0.0,
        "slo": {"target_ms": 16.0, "p50_ms": 1.0, "p90_ms": 2.0,
                "p99_ms": 3.0, "pass": True, "source": "x"},
        "op_stats": {"tick_ms": {"edges": [], "counts": []}},
        "roofline_audit": {"phases": {}},
    }
    rec.update(extra)
    return rec


def _validate(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return SCHEMA.validate_file(str(p))


def test_valid_r8_record_passes(tmp_path):
    assert _validate(tmp_path, "BENCH_r08.json", _full_rec()) == []


def test_missing_kernel_stamp_caught(tmp_path):
    rec = _full_rec()
    del rec["sweep_impl"]
    errs = _validate(tmp_path, "BENCH_r08.json", rec)
    assert any("sweep_impl" in e for e in errs)


def test_missing_device_plane_blocks_caught_since_r8(tmp_path):
    rec = _full_rec()
    del rec["slo"], rec["roofline_audit"], rec["op_stats"]
    errs = _validate(tmp_path, "BENCH_r08.json", rec)
    assert any("slo" in e for e in errs)
    assert any("roofline_audit" in e for e in errs)
    assert any("op_stats" in e for e in errs)
    # the same record is a VALID r7 artifact (grandfathered)
    assert _validate(tmp_path, "BENCH_r07.json", rec) == []


def test_honest_error_blocks_accepted(tmp_path):
    rec = _full_rec(slo={"error": "telemetry scan failed"},
                    roofline_audit={"error": "no phases"},
                    op_stats={"error": "x"})
    assert _validate(tmp_path, "BENCH_r08.json", rec) == []


def test_deliberate_skip_blocks_accepted(tmp_path):
    """BENCH_DEVPROF=0 / BENCH_SLO=0 / BENCH_PHASES=0 runs stamp
    {"skipped": ...} records — a documented thinner run (e.g. a chip
    run avoiding the extra compiles) must stay schema-valid."""
    rec = _full_rec(slo={"skipped": "BENCH_SLO=0"},
                    roofline_audit={"skipped": "BENCH_DEVPROF=0"},
                    op_stats={"skipped": "BENCH_SLO=0"})
    assert _validate(tmp_path, "BENCH_r08.json", rec) == []


def test_value_zero_error_record_is_a_failed_round(tmp_path):
    """compose()'s "no stage completed" artifact (value 0.0 + error)
    is a FAILED round, not a headline held to the headline contract —
    the same definition bench_trend/roofline_audit use
    (devprof.artifact_headline)."""
    failed = {"metric": "entity_ticks_per_sec_per_chip", "value": 0.0,
              "unit": "entity-ticks/s/chip", "vs_baseline": 0.0,
              "error": "no stage completed on any backend",
              "attempts": []}
    doc = {"cmd": "x", "rc": 1, "parsed": failed, "tail": ""}
    assert _validate(tmp_path, "BENCH_r09.json", doc) == []
    # ...but an rc that claims success next to no headline is a lie
    doc_lie = dict(doc, rc=0)
    errs = _validate(tmp_path, "BENCH_r09.json", doc_lie)
    assert any("rc == 0" in e for e in errs)


def test_malformed_slo_shape_caught(tmp_path):
    rec = _full_rec(slo={"target_ms": 16.0})  # percentiles missing
    errs = _validate(tmp_path, "BENCH_r08.json", rec)
    assert any("slo" in e and "p99_ms" in e for e in errs)


def test_non_numeric_value_caught(tmp_path):
    errs = _validate(tmp_path, "BENCH_r08.json",
                     _full_rec(value="fast"))
    assert any("not a number" in e for e in errs)


def test_failed_round_requires_nonzero_rc(tmp_path):
    ok = {"cmd": "x", "rc": 1, "parsed": None, "tail": ""}
    assert _validate(tmp_path, "BENCH_r09.json", ok) == []
    lie = {"cmd": "x", "rc": 0, "parsed": None, "tail": ""}
    errs = _validate(tmp_path, "BENCH_r09.json", lie)
    assert any("rc == 0" in e for e in errs)


def test_scenario_blocks_validated(tmp_path):
    rec = _full_rec(scenarios={"hotspot": {"tick_ms": 1.0}})
    errs = _validate(tmp_path, "BENCH_r08.json", rec)
    assert any("hotspot" in e and "value" in e for e in errs)
    rec2 = _full_rec(scenarios={
        "hotspot": {"value": 1.0, "tick_ms": 1.0, "entities": 10},
        "shrink": {"error": "boom"},
    })
    assert _validate(tmp_path, "BENCH_r08.json", rec2) == []


def test_multichip_invariants(tmp_path):
    good = {"n_devices": 8, "rc": 0, "ok": True, "tail": ""}
    assert _validate(tmp_path, "MULTICHIP_r08.json", good) == []
    bad = {"n_devices": 8, "rc": 3, "ok": True, "tail": ""}
    errs = _validate(tmp_path, "MULTICHIP_r08.json", bad)
    assert any("rc=3" in e for e in errs)
    errs = _validate(tmp_path, "MULTICHIP_r08.json",
                     {"rc": 0, "ok": False})
    assert any("n_devices" in e for e in errs)
    assert any("tail" in e for e in errs)


def _multi_rec(**extra):
    """A valid r>=10 MULTICHIP record (the measured-mesh contract)."""
    rec = {
        "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
        "tail": "multichip(8): ...",
        "headline": {
            "entity_ticks_per_sec_mesh": 159907.2,
            "per_chip_efficiency": 0.19,
            "n_entities": 65536, "platform": "cpu",
        },
        "gauges": {"halo_demand_max": 252, "migrate_demand_max": 2,
                   "migrate_dropped_total": 0},
        "cost_report": {"name": "mega_tick_scan"},
        "roofline_audit": {"phases": {"ici_halo": {"model_mb": 0.1}}},
        "phases": {"border_churn": {"tick_ms": 905.0}},
    }
    rec.update(extra)
    return rec


def test_multichip_r10_contract(tmp_path):
    assert _validate(tmp_path, "MULTICHIP_r10.json", _multi_rec()) == []
    # old dryrun-only records stay grandfathered below r10
    old = {"n_devices": 8, "rc": 0, "ok": True, "tail": ""}
    assert _validate(tmp_path, "MULTICHIP_r09.json", old) == []
    # ... but r10+ requires the measured blocks
    errs = _validate(tmp_path, "MULTICHIP_r10.json", old)
    assert any("headline" in e for e in errs)
    assert any("border_churn" in e for e in errs)
    # missing headline keys caught
    rec = _multi_rec()
    del rec["headline"]["per_chip_efficiency"]
    errs = _validate(tmp_path, "MULTICHIP_r10.json", rec)
    assert any("per_chip_efficiency" in e for e in errs)
    # honest error blocks accepted for the device-plane stamps
    rec = _multi_rec(cost_report={"error": "boom"},
                     roofline_audit={"error": "boom"})
    assert _validate(tmp_path, "MULTICHIP_r10.json", rec) == []
    # ok with no mesh number is a lie
    rec = _multi_rec()
    rec["headline"]["entity_ticks_per_sec_mesh"] = 0
    errs = _validate(tmp_path, "MULTICHIP_r10.json", rec)
    assert any("no mesh number" in e for e in errs)
    # failed rounds and skips stay exempt
    failed = {"n_devices": 8, "rc": 2, "ok": False, "tail": "died"}
    assert _validate(tmp_path, "MULTICHIP_r11.json", failed) == []
    skipped = {"n_devices": 8, "rc": 0, "ok": True, "skipped": True,
               "tail": ""}
    assert _validate(tmp_path, "MULTICHIP_r11.json", skipped) == []


def _sig_block():
    return {"sig": "churn=skinless|density=exact|events=quiet",
            "churn": "skinless", "density": "exact",
            "events": "quiet", "recommendation": {}}


def test_workload_signature_required_since_r11(tmp_path):
    # r10 and older: grandfathered without the block
    assert _validate(tmp_path, "BENCH_r10.json", _full_rec()) == []
    # r11+: the block is part of the contract
    errs = _validate(tmp_path, "BENCH_r11.json", _full_rec())
    assert any("workload_signature" in e for e in errs)
    rec = _full_rec(workload_signature=_sig_block())
    assert _validate(tmp_path, "BENCH_r11.json", rec) == []
    # honest error/skip records accepted (device-plane convention)
    for blk in ({"error": "no op_stats"}, {"skipped": "BENCH_SLO=0"}):
        rec = _full_rec(workload_signature=blk)
        assert _validate(tmp_path, "BENCH_r11.json", rec) == []
    # partial signature shapes caught
    rec = _full_rec(workload_signature={"sig": "x"})
    errs = _validate(tmp_path, "BENCH_r11.json", rec)
    assert any("workload_signature missing key" in e for e in errs)
    # MULTICHIP r11+: same rule at the document level
    mc = _multi_rec()
    errs = _validate(tmp_path, "MULTICHIP_r11.json", mc)
    assert any("workload_signature" in e for e in errs)
    mc = _multi_rec(workload_signature=_sig_block())
    assert _validate(tmp_path, "MULTICHIP_r11.json", mc) == []
    assert _validate(tmp_path, "MULTICHIP_r10.json",
                     _multi_rec()) == []


def _prec_blocks():
    return {
        "precision": {"plane": "off", "pos_scale_bits": 0,
                      "quant_step": 0.03125, "sync_delta": False,
                      "sync_keyframe_every": 16},
        "precision_ab": {"n": 131072, "off_ms": 10.0, "q16_ms": 9.0,
                         "model_off_gb_1m": 1.09,
                         "model_q16_gb_1m": 0.61},
    }


def test_precision_stamp_required_since_r12(tmp_path):
    """ISSUE 12 satellite: r>=12 headlines must stamp the resolved
    precision config + the on/off A/B next to the kernel stamps;
    honest error/skip records accepted; r11 grandfathered."""
    rec = _full_rec(workload_signature=_sig_block())
    # r11: grandfathered without the blocks
    assert _validate(tmp_path, "BENCH_r11.json", rec) == []
    # r12: both blocks required
    errs = _validate(tmp_path, "BENCH_r12.json", rec)
    assert any("precision block" in e or "precision" in e
               for e in errs)
    assert any("precision_ab" in e for e in errs)
    rec = _full_rec(workload_signature=_sig_block(), **_prec_blocks())
    assert _validate(tmp_path, "BENCH_r12.json", rec) == []
    # partial precision shapes caught
    bad = _full_rec(workload_signature=_sig_block(), **_prec_blocks())
    del bad["precision"]["pos_scale_bits"]
    errs = _validate(tmp_path, "BENCH_r12.json", bad)
    assert any("precision missing key 'pos_scale_bits'" in e
               for e in errs)
    bad = _full_rec(workload_signature=_sig_block(), **_prec_blocks())
    del bad["precision_ab"]["model_q16_gb_1m"]
    errs = _validate(tmp_path, "BENCH_r12.json", bad)
    assert any("precision_ab missing key" in e for e in errs)
    # honest error/skip records accepted (device-plane convention)
    rec = _full_rec(workload_signature=_sig_block(),
                    precision={"error": "stamp failed"},
                    precision_ab={"skipped": "BENCH_PRECISION_AB=0"})
    assert _validate(tmp_path, "BENCH_r12.json", rec) == []


def _r13_rec(**extra):
    """A valid r13 record: r12's contract + the governor block."""
    rec = _full_rec(
        workload_signature={"sig": "x", "churn": "flock_like",
                            "density": "exact", "events": "quiet",
                            "recommendation": {}},
        precision={"plane": "off", "pos_scale_bits": 0,
                   "sync_keyframe_every": 16},
        precision_ab={"off_ms": 1.0, "q16_ms": 0.9,
                      "model_off_gb_1m": 1.0, "model_q16_gb_1m": 0.6},
        governor={"schedule": ["flock", "teleport", "hotspot"],
                  "phases": [{"scenario": "flock", "chosen": "default",
                              "expected": "default",
                              "swap_latency_ticks": 8}],
                  "throughput": 1000.0,
                  "static_wall_s": {"default": 1.0}},
    )
    rec.update(extra)
    return rec


def test_governor_block_required_since_r13(tmp_path):
    rec = _r13_rec()
    assert _validate(tmp_path, "BENCH_r13.json", rec) == []
    # missing entirely -> caught at r13, grandfathered at r12
    rec2 = _r13_rec()
    del rec2["governor"]
    errs = _validate(tmp_path, "BENCH_r13.json", rec2)
    assert any("governor" in e for e in errs)
    assert _validate(tmp_path, "BENCH_r12.json", rec2) == []
    # honest skip/error records accepted (the --governor-not-requested
    # round and the stage-failed round are both valid artifacts)
    for blk in ({"skipped": "--governor not requested"},
                {"error": "governor stage never completed"}):
        rec3 = _r13_rec(governor=blk)
        assert _validate(tmp_path, "BENCH_r13.json", rec3) == []


def test_governor_block_shape_caught(tmp_path):
    # a present-but-gutted block is malformation, not an honest skip
    rec = _r13_rec(governor={"schedule": ["flock"]})
    errs = _validate(tmp_path, "BENCH_r13.json", rec)
    assert any("governor" in e and "phases" in e for e in errs)
    # malformed phase records inside an otherwise-complete block
    rec2 = _r13_rec()
    rec2["governor"]["phases"] = [{"scenario": "flock"}]
    errs = _validate(tmp_path, "BENCH_r13.json", rec2)
    assert any("governor phase" in e for e in errs)


def test_unreadable_file_reported(tmp_path):
    p = tmp_path / "BENCH_r08.json"
    p.write_text("{not json")
    errs = SCHEMA.validate_file(str(p))
    assert errs and "unreadable" in errs[0]


# =======================================================================
# r>=15: the sync-age block (ISSUE 15)
# =======================================================================
def _sync_age_block(**extra):
    hops = {h: {"samples": 100, "p50_ms": 1.0, "p90_ms": 2.0,
                "p99_ms": 3.0}
            for h in ("device_tick", "drain_decode", "encode",
                      "dispatcher", "gate_flush")}
    blk = {
        "target_ms": 16.0,
        "e2e": {"samples": 100, "p50_ms": 4.0, "p90_ms": 8.0,
                "p99_ms": 12.0},
        "hops": hops,
        "records_per_tick": 2048,
        "clients": 4,
        "pass": True,
        "stamp_overhead_pct_of_budget": 0.05,
    }
    blk.update(extra)
    return blk


def _r15_rec(**extra):
    """A valid r15 record: r13's contract + the sync_age block."""
    rec = _r13_rec(sync_age=_sync_age_block())
    rec.update(extra)
    return rec


def test_sync_age_block_required_since_r15(tmp_path):
    rec = _r15_rec()
    assert _validate(tmp_path, "BENCH_r15.json", rec) == []
    # missing entirely -> caught at r15, grandfathered at r13
    rec2 = _r15_rec()
    del rec2["sync_age"]
    errs = _validate(tmp_path, "BENCH_r15.json", rec2)
    assert any("sync_age" in e for e in errs)
    assert _validate(tmp_path, "BENCH_r13.json", rec2) == []
    # honest skip/error records accepted (the BENCH_SYNC_AGE=0 round
    # and the stage-failed round are both valid artifacts)
    for blk in ({"skipped": "BENCH_SYNC_AGE=0"},
                {"error": "sync_age stage never completed"}):
        rec3 = _r15_rec(sync_age=blk)
        assert _validate(tmp_path, "BENCH_r15.json", rec3) == []


def test_sync_age_block_shape_caught(tmp_path):
    # a present-but-gutted block is malformation, not an honest skip
    rec = _r15_rec(sync_age={"target_ms": 16.0})
    errs = _validate(tmp_path, "BENCH_r15.json", rec)
    assert any("sync_age" in e for e in errs)
    # a missing hop lane inside an otherwise-complete block
    rec2 = _r15_rec()
    del rec2["sync_age"]["hops"]["dispatcher"]
    errs = _validate(tmp_path, "BENCH_r15.json", rec2)
    assert any("dispatcher" in e for e in errs)
    # e2e percentiles must be the full p50/p90/p99 + samples shape
    rec3 = _r15_rec()
    rec3["sync_age"]["e2e"] = {"p99_ms": 3.0}
    errs = _validate(tmp_path, "BENCH_r15.json", rec3)
    assert any("e2e" in e for e in errs)


# =======================================================================
# r>=16: the serve-loop residency block (ISSUE 16)
# =======================================================================
def _residency_block(**extra):
    pt = {"samples": 90, "p50_ms": 0.5, "p90_ms": 1.0, "p99_ms": 2.0}
    blk = {
        "entities": 64,
        "ticks": 90,
        "bubble": dict(pt),
        "tick": {"samples": 90, "p50_ms": 17.0, "p90_ms": 18.0,
                 "p99_ms": 20.0},
        "bubble_budget_ms": 4.0,
        "phases": {p: dict(pt) for p in
                   ("pre_dispatch", "device_wait", "decode_fanout",
                    "host_other", "idle", "bubble")},
        "gc": {"pauses": 2, "total_ms": 1.0, "max_ms": 0.8},
        "alloc": {"unavailable": "memory_stats unavailable"},
        "census": {"samples": 5, "lanes": 19, "realloc": ["pos"],
                   "aliased": [], "opaque": [], "changes": {"pos": 5}},
        "serve_ms_per_tick": 17.0,
        "serve_gap": 1.4,
        "serve_gap_ref": "scan_marginal",
        "serve_gap_ref_ms": 12.1,
        "scan_marginal_ms": 12.1,
        "pass": True,
        "mark_overhead_us_per_tick": 8.0,
        "mark_overhead_pct_of_budget": 0.05,
    }
    blk.update(extra)
    return blk


def _r16_rec(**extra):
    """A valid r16 record: r15's contract + the residency block."""
    rec = _r15_rec(residency=_residency_block())
    rec.update(extra)
    return rec


def test_residency_block_required_since_r16(tmp_path):
    rec = _r16_rec()
    assert _validate(tmp_path, "BENCH_r16.json", rec) == []
    # missing entirely -> caught at r16, grandfathered at r15
    rec2 = _r16_rec()
    del rec2["residency"]
    errs = _validate(tmp_path, "BENCH_r16.json", rec2)
    assert any("residency" in e for e in errs)
    assert _validate(tmp_path, "BENCH_r15.json", rec2) == []
    # honest skip/error records accepted (the BENCH_RESIDENCY=0 round
    # and the stage-failed round are both valid artifacts)
    for blk in ({"skipped": "BENCH_RESIDENCY=0"},
                {"error": "residency stage never completed"}):
        rec3 = _r16_rec(residency=blk)
        assert _validate(tmp_path, "BENCH_r16.json", rec3) == []


def test_residency_block_shape_caught(tmp_path):
    # a present-but-gutted block is malformation, not an honest skip
    rec = _r16_rec(residency={"bubble": {"p99_ms": 1.0}})
    errs = _validate(tmp_path, "BENCH_r16.json", rec)
    assert any("residency" in e for e in errs)
    # bubble percentiles must be the full p50/p90/p99 + samples shape
    rec2 = _r16_rec()
    rec2["residency"]["bubble"] = {"p99_ms": 1.0}
    errs = _validate(tmp_path, "BENCH_r16.json", rec2)
    assert any("bubble" in e for e in errs)
    # the census must carry the donation worklist shape
    rec3 = _r16_rec()
    rec3["residency"]["census"] = {"samples": 5}
    errs = _validate(tmp_path, "BENCH_r16.json", rec3)
    assert any("census" in e for e in errs)
    # alloc must be a dict — measured stats or {"unavailable": ...},
    # never a bare null pretending nothing was supposed to be there
    rec4 = _r16_rec()
    rec4["residency"]["alloc"] = None
    errs = _validate(tmp_path, "BENCH_r16.json", rec4)
    assert any("alloc" in e for e in errs)


# =======================================================================
# r>=18: the hot-standby failover block (ISSUE 18)
# =======================================================================
def _audit_block(**extra):
    blk = {
        "entities": 64,
        "ledger": {"entities": 64, "crc": 1, "created": 70,
                   "destroyed": 6, "migrated_out": 0, "migrated_in": 0},
        "oracle": {"samples": 12, "entities_checked": 700,
                   "mismatches": 0},
        "violations_total": {},
        "conservation": {"ok": True, "live": 64, "in_flight": 0,
                         "created": 70, "destroyed": 6, "problems": []},
        "overhead_pct_of_budget": 0.2,
        "pass": True,
    }
    blk.update(extra)
    return blk


def _failover_block(**extra):
    blk = {
        "entities": 48,
        "ticks": 20,
        "keyframe_every": 8,
        "replication_bytes_per_tick": 5163.3,
        "client_sync_bytes_per_tick": 1214.4,
        "standby_apply_ms_per_tick": 0.9,
        "promotion_latency_ticks": 1,
        "lag_budget_ticks": 16,
        "entities_lost": 0,
        "entities_duplicated": 0,
        "frames_applied": 20,
        "frames_rejected": 0,
        "decision_log_replay_ok": True,
        "pass": True,
    }
    blk.update(extra)
    return blk


def _r18_rec(**extra):
    """A valid r18 record: r17's contract (the audit block) + the
    hot-standby failover block."""
    rec = _r16_rec(audit=_audit_block(), failover=_failover_block())
    rec.update(extra)
    return rec


def test_failover_block_required_since_r18(tmp_path):
    rec = _r18_rec()
    assert _validate(tmp_path, "BENCH_r18.json", rec) == []
    # missing entirely -> caught at r18, grandfathered at r17
    rec2 = _r18_rec()
    del rec2["failover"]
    errs = _validate(tmp_path, "BENCH_r18.json", rec2)
    assert any("failover" in e for e in errs)
    assert _validate(tmp_path, "BENCH_r17.json", rec2) == []
    # honest skip/error records accepted (the BENCH_FAILOVER=0 round
    # and the stage-failed round are both valid artifacts)
    for blk in ({"skipped": "BENCH_FAILOVER=0"},
                {"error": "failover stage never completed"}):
        rec3 = _r18_rec(failover=blk)
        assert _validate(tmp_path, "BENCH_r18.json", rec3) == []


def test_failover_block_shape_caught(tmp_path):
    # a present-but-gutted block is malformation, not an honest skip
    rec = _r18_rec(failover={"promotion_latency_ticks": 1})
    errs = _validate(tmp_path, "BENCH_r18.json", rec)
    assert any("failover missing key" in e for e in errs)
    assert any("entities_lost" in e for e in errs)
    # a non-numeric conservation count is malformation (a bool True
    # would make `if lost` lie, a string would break the trend gate)
    rec2 = _r18_rec()
    rec2["failover"]["entities_lost"] = "none"
    errs = _validate(tmp_path, "BENCH_r18.json", rec2)
    assert any("entities_lost malformed" in e for e in errs)


# =======================================================================
# r>=19: the self-healing rebalance block (ISSUE 19)
# =======================================================================
def _rebalance_block(**extra):
    blk = {
        "donor_p99_before_ms": 12.1,
        "donor_p99_after_ms": 10.4,
        "entities_moved": 24,
        "batch": 24,
        "aborts": 0,
        "donor_recovery_windows": 2,
        "entities_lost": 0,
        "entities_duplicated": 0,
        "decision_log_replay_ok": True,
        "pass": True,
    }
    blk.update(extra)
    return blk


def _r19_rec(**extra):
    """A valid r19 record: r18's contract + the rebalance block."""
    rec = _r18_rec(rebalance=_rebalance_block())
    rec.update(extra)
    return rec


def test_rebalance_block_required_since_r19(tmp_path):
    rec = _r19_rec()
    assert _validate(tmp_path, "BENCH_r19.json", rec) == []
    # missing entirely -> caught at r19, grandfathered at r18
    rec2 = _r19_rec()
    del rec2["rebalance"]
    errs = _validate(tmp_path, "BENCH_r19.json", rec2)
    assert any("rebalance" in e for e in errs)
    assert _validate(tmp_path, "BENCH_r18.json", rec2) == []
    # honest skip/error records accepted
    for blk in ({"skipped": "BENCH_REBALANCE=0"},
                {"error": "rebalance stage never completed"}):
        rec3 = _r19_rec(rebalance=blk)
        assert _validate(tmp_path, "BENCH_r19.json", rec3) == []


def test_rebalance_block_shape_caught(tmp_path):
    # a present-but-gutted block is malformation, not an honest skip
    rec = _r19_rec(rebalance={"entities_moved": 24})
    errs = _validate(tmp_path, "BENCH_r19.json", rec)
    assert any("rebalance missing key" in e for e in errs)
    assert any("entities_lost" in e for e in errs)
    # non-numeric conservation counts are malformation
    rec2 = _r19_rec()
    rec2["rebalance"]["entities_duplicated"] = "zero"
    errs = _validate(tmp_path, "BENCH_r19.json", rec2)
    assert any("entities_duplicated malformed" in e for e in errs)
    # an aborted round's recovery latency is honestly None — accepted
    rec3 = _r19_rec()
    rec3["rebalance"]["donor_recovery_windows"] = None
    rec3["rebalance"]["pass"] = False
    assert _validate(tmp_path, "BENCH_r19.json", rec3) == []
