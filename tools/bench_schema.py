#!/usr/bin/env python
"""Schema checker for the checked-in BENCH_r*.json / MULTICHIP_r*.json.

The round artifacts are the repo's performance memory — trend gating
(tools/bench_trend.py), the roofline audit and the ROADMAP all read
them — so a malformed stamp is corruption that compounds. This
validates every file's shape and is run as a tier-1 test
(tests/test_bench_schema.py), so a malformed stamp can never land
again.

Rules are VERSIONED by round number (the artifact grew stamps over
time; old rounds are grandfathered, new rounds are held to the current
contract):

* every BENCH file: either a headline record with the base contract
  (metric/value/unit/vs_baseline/entities/tick_ms/platform/attempts),
  or an honestly-recorded failed round (no headline, rc != 0);
* rounds >= 6 (the first artifacts produced by the stamp-carrying
  bench): resolved kernel stamps (sweep/topk/sort/skin);
* rounds >= 8 (the device-plane era): ``slo``, ``op_stats`` and
  ``roofline_audit`` blocks with their required inner shape (an
  ``{"error": ...}`` record is an accepted honest failure, a
  ``{"skipped": ...}`` record a documented deliberate skip —
  BENCH_DEVPROF=0/BENCH_SLO=0/BENCH_PHASES=0);
* MULTICHIP files: n_devices/rc/ok/tail, with ok => rc == 0;
* MULTICHIP rounds >= 10 (the measured-mesh era, bench.py --multichip):
  a ``headline`` block ({entity_ticks_per_sec_mesh,
  per_chip_efficiency, n_entities, platform}), ``gauges``,
  ``cost_report``/``roofline_audit`` (``{"error": ...}`` accepted as
  honest failure) and a ``phases.border_churn`` block; failed rounds
  (rc != 0) and ``skipped`` records stay exempt, old dryrun-only
  artifacts are grandfathered;
* rounds >= 11 (the workload-signature era, ISSUE 11): a
  ``workload_signature`` block — the live ``/workload`` grammar
  (sig/churn/density/events/recommendation) stamped by the same
  jax-free reducer — in BENCH headlines and MULTICHIP documents alike
  (``{"error"/"skipped": ...}`` accepted as honest failure);
* rounds >= 12 (the quantized-plane era, ISSUE 12): a ``precision``
  block (resolved plane on/off, pos scale bits, delta-sync keyframe
  cadence) next to the kernel stamps, plus the ``precision_ab``
  on/off A/B record (measured marginal both ways + modeled bytes at
  the shape and at 1M; honest error/skip records accepted);
* rounds >= 13 (the kernel-governor era, ISSUE 13): a ``governor``
  block — the ``bench.py --governor`` phase-switching schedule
  (per-phase chosen config + swap latency, throughput vs best/worst
  static) when it ran, or an honest ``{"skipped": "--governor not
  requested"}`` / ``{"error": ...}`` record otherwise;
* rounds >= 15 (the sync-age era, ISSUE 15): a ``sync_age`` block —
  the end-to-end device-tick-epoch -> gate-delivery age measured
  through the real game->gate loopback (per-hop + e2e p50/p90/p99,
  the verdict vs the 16 ms target, the measured stamp overhead) —
  honest ``{"error"/"skipped": ...}`` records accepted;
* rounds >= 16 (the serve-loop residency era, ISSUE 16): a
  ``residency`` block — the instrumented-World serve-loop plane
  (bubble/tick percentiles, phase lanes, the donation-readiness
  buffer census, alloc churn or its honest absence, serve_gap vs the
  pinned scan-marginal, the measured mark overhead) — honest
  ``{"error"/"skipped": ...}`` records accepted;
* rounds >= 17 (the correctness-audit era, ISSUE 17): an ``audit``
  block — the entity-ownership ledger census + deployment
  conservation verdict, the sampled AOI-oracle progress, by-kind
  violation totals (the zero-violation gate) and the measured A/B
  overhead of the plane vs the 60 Hz tick budget — honest
  ``{"error"/"skipped": ...}`` records accepted;
* rounds >= 18 (the hot-standby era, ISSUE 18): a ``failover`` block
  — the streamed primary->standby replication cost (bytes/tick, next
  to the client-sync bytes/tick the same workload ships), the
  standby's apply cost, the promotion latency in ticks and the
  conservation counts across the arbitrated promotion (zero lost /
  zero duplicated EntityIDs is the gate) — honest
  ``{"error"/"skipped": ...}`` records accepted;
* rounds >= 19 (the self-healing rebalance era, ISSUE 19): a
  ``rebalance`` block — donor tick p99 before/after the automated
  handoff, entities moved vs the batch cap, abort count, the donor
  recovery latency in observation windows (the lower-is-better trend
  series) and the conservation counts across the move (zero lost /
  zero duplicated is the unconditional gate), plus the byte-identical
  DecisionLog replay verdict — honest ``{"error"/"skipped": ...}``
  records accepted;
* rounds >= 20 (the resident-world era, ISSUE 20): a ``resident_ab``
  block — serve-loop ms/tick with carry donation + the
  double-buffered drain on vs off at the same shape (the
  interleaved paced-window protocol), the residency census counts
  for BOTH arms (0 re-allocated lanes on the donated arm is the
  trend gate; >= 1 on the copy arm proves the A/B measured the
  knob) and allocs/tick where the backend serves memory_stats —
  honest ``{"error"/"skipped": ...}`` records accepted.

Exit codes: 0 all valid, 1 usage/missing, 2 schema violations.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# jax-free artifact conventions shared with bench_trend/roofline_audit
from goworld_tpu.utils.devprof import (  # noqa: E402
    artifact_headline,
    artifact_round as round_no,
)

BASE_KEYS = ("metric", "value", "unit", "vs_baseline", "entities",
             "tick_ms", "platform", "attempts")
KERNEL_STAMPS = ("sweep_impl", "topk_impl", "sort_impl", "skin")
SLO_KEYS = ("target_ms", "p50_ms", "p90_ms", "p99_ms", "pass",
            "source")
# round number from which a stamp family is REQUIRED (the stamps
# landed in the r5 SESSION, so the first artifact carrying them is r6)
KERNEL_STAMPS_SINCE = 6
DEVICE_PLANE_SINCE = 8
# MULTICHIP graduates from a dryrun log to a measured mesh headline
# (bench.py --multichip, ISSUE 10): required from r10, old dryrun-only
# artifacts grandfathered
MULTI_HEADLINE_SINCE = 10
# the workload-signature era (ISSUE 11): every BENCH/MULTICHIP round
# stamps the jax-free signature reduction of its drained telemetry
# lanes — the same grammar the live /workload endpoint serves
# ({"error"/"skipped": ...} accepted as honest failure, like every
# device-plane block)
WORKLOAD_SIG_SINCE = 11
WORKLOAD_SIG_KEYS = ("sig", "churn", "density", "events",
                     "recommendation")
# the quantized-plane era (ISSUE 12): every BENCH headline stamps the
# resolved `precision` block (plane on/off, pos scale bits, delta-sync
# keyframe cadence) next to the kernel stamps, plus the precision
# on/off A/B record ({"error"/"skipped": ...} accepted as honest
# failure, the device-plane convention)
PRECISION_SINCE = 12
PRECISION_KEYS = ("plane", "pos_scale_bits", "sync_keyframe_every")
# the kernel-governor era (ISSUE 13): bench.py --governor stamps the
# phase-switching schedule block; rounds that didn't run it must say
# so honestly ({"skipped"/"error": ...} — the device-plane convention)
GOVERNOR_SINCE = 13
GOVERNOR_KEYS = ("schedule", "phases", "throughput", "static_wall_s")
# the sync-age era (ISSUE 15): every BENCH round stamps the
# game->gate loopback's age-at-delivery block — per-hop + e2e
# percentiles, the verdict vs the paper's 16 ms target, and the
# measured overhead of the always-on stamp (the <1% criterion)
SYNC_AGE_SINCE = 15
SYNC_AGE_KEYS = ("target_ms", "e2e", "hops", "records_per_tick",
                 "pass", "stamp_overhead_pct_of_budget")
SYNC_AGE_HOPS = ("device_tick", "drain_decode", "encode",
                 "dispatcher", "gate_flush")
# the serve-loop residency era (ISSUE 16): every BENCH round stamps
# the instrumented serve loop's residency plane — the host bubble vs
# its budget, the phase lanes, the donation-readiness census (the
# donate_argnums worklist), alloc churn (or its honest absence on
# backends without memory_stats), serve_gap vs the pinned
# scan-marginal, and the measured overhead of the always-on marks
RESIDENCY_SINCE = 16
RESIDENCY_KEYS = ("bubble", "tick", "phases", "census", "alloc",
                  "serve_gap", "serve_gap_ref", "scan_marginal_ms",
                  "bubble_budget_ms", "mark_overhead_pct_of_budget")
# the correctness-audit era (ISSUE 17): every BENCH round stamps the
# audit plane's block — ledger census + conservation verdict, AOI
# oracle sample/mismatch counts, the by-kind violation totals (the
# zero-violation gate) and the measured A/B overhead of the plane vs
# the 60 Hz tick budget (the <1% criterion)
AUDIT_SINCE = 17
AUDIT_KEYS = ("ledger", "oracle", "violations_total", "conservation",
              "overhead_pct_of_budget", "pass")
# the hot-standby era (ISSUE 18): every BENCH round stamps the
# failover block — replication stream bytes/tick next to the
# client-sync bytes/tick the same workload ships, the standby's apply
# cost, the promotion latency in ticks and the conservation counts
# across the promotion (zero lost / zero duplicated is the gate)
FAILOVER_SINCE = 18
FAILOVER_KEYS = ("replication_bytes_per_tick",
                 "client_sync_bytes_per_tick",
                 "standby_apply_ms_per_tick",
                 "promotion_latency_ticks", "entities_lost",
                 "entities_duplicated", "frames_applied",
                 "frames_rejected", "decision_log_replay_ok", "pass")
# the self-healing rebalance era (ISSUE 19): every BENCH round stamps
# the rebalance block — donor tick p99 before/after the handoff,
# entities moved vs the batch cap, abort count, donor recovery
# latency in observation windows (the lower-is-better trend series)
# and the conservation counts across the move (zero lost / zero
# duplicated is the unconditional gate)
REBALANCE_SINCE = 19
REBALANCE_KEYS = ("donor_p99_before_ms", "donor_p99_after_ms",
                  "entities_moved", "batch", "aborts",
                  "donor_recovery_windows", "entities_lost",
                  "entities_duplicated", "decision_log_replay_ok",
                  "pass")
# the resident-world era (ISSUE 20): every BENCH round stamps the
# donation + double-buffered-drain A/B — serve-loop ms/tick on vs off
# at the same shape, the residency census counts on BOTH arms (the
# donated arm's 0-realloc verdict is the trend gate) and allocs/tick
# where the backend serves memory_stats
RESIDENT_AB_SINCE = 20
RESIDENT_AB_KEYS = ("on_ms_per_tick", "off_ms_per_tick", "ratio",
                    "on_census", "off_census", "windows",
                    "ticks_per_window", "pass")
MULTI_HEADLINE_KEYS = ("entity_ticks_per_sec_mesh",
                       "per_chip_efficiency", "n_entities", "platform")
MULTI_GAUGE_KEYS = ("halo_demand_max", "migrate_demand_max",
                    "migrate_dropped_total")


def _is_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _check_block(rec: dict, key: str, inner: tuple,
                 errs: list[str]) -> None:
    """A device-plane block: present, a dict, and either an honest
    ``{"error": ...}`` / ``{"skipped": ...}`` record (an exception in
    the stamping, or a documented BENCH_DEVPROF=0/BENCH_SLO=0/
    BENCH_PHASES=0 skip) or the full inner shape."""
    blk = rec.get(key)
    if not isinstance(blk, dict):
        errs.append(f"missing/invalid {key} block")
        return
    if "error" in blk or "skipped" in blk:
        return  # honestly-recorded failure or deliberate skip
    for k in inner:
        if k not in blk:
            errs.append(f"{key} missing key {k!r}")


def validate_bench(path: str, doc: dict) -> list[str]:
    errs: list[str] = []
    rno = round_no(path)
    # the ONE headline definition shared with bench_trend/
    # roofline_audit (devprof.artifact_headline): a value-0 error
    # record (compose()'s "no stage completed" artifact) is a FAILED
    # round, not a headline to hold to the headline contract
    rec = artifact_headline(doc)
    if rec is None:
        # a failed round: honest only when its rc says so
        if doc.get("rc", 1) == 0 and "parsed" in doc:
            errs.append("no headline record but rc == 0")
        return errs
    for k in BASE_KEYS:
        if k not in rec:
            errs.append(f"missing base key {k!r}")
    if "value" in rec and not _is_num(rec["value"]):
        errs.append(f"value is {type(rec['value']).__name__}, "
                    "not a number")
    if _is_num(rec.get("value")) and rec["value"] < 0:
        errs.append("negative headline value")
    if not isinstance(rec.get("attempts", []), list):
        errs.append("attempts is not a list")
    if rno >= KERNEL_STAMPS_SINCE:
        for k in KERNEL_STAMPS:
            if k not in rec:
                errs.append(f"missing kernel stamp {k!r} "
                            f"(required since r{KERNEL_STAMPS_SINCE:02d})")
    if rno >= DEVICE_PLANE_SINCE:
        _check_block(rec, "slo", SLO_KEYS, errs)
        _check_block(rec, "roofline_audit", ("phases",), errs)
        ost = rec.get("op_stats")
        if not isinstance(ost, dict) or not (
                {"error", "skipped"} & set(ost) or "tick_ms" in ost):
            errs.append("missing/invalid op_stats block")
    if rno >= WORKLOAD_SIG_SINCE:
        _check_block(rec, "workload_signature", WORKLOAD_SIG_KEYS,
                     errs)
    if rno >= PRECISION_SINCE:
        _check_block(rec, "precision", PRECISION_KEYS, errs)
        _check_block(rec, "precision_ab",
                     ("off_ms", "q16_ms", "model_off_gb_1m",
                      "model_q16_gb_1m"), errs)
    if rno >= GOVERNOR_SINCE:
        _check_block(rec, "governor", GOVERNOR_KEYS, errs)
        gv = rec.get("governor")
        if isinstance(gv, dict) and "error" not in gv \
                and "skipped" not in gv:
            for ph in gv.get("phases") or []:
                if not isinstance(ph, dict) or not (
                        {"scenario", "chosen", "expected"} <= set(ph)):
                    errs.append(
                        f"governor phase record malformed: {ph!r:.120}")
    if rno >= SYNC_AGE_SINCE:
        _check_block(rec, "sync_age", SYNC_AGE_KEYS, errs)
        sa = rec.get("sync_age")
        if isinstance(sa, dict) and "error" not in sa \
                and "skipped" not in sa:
            e2e = sa.get("e2e")
            if not (isinstance(e2e, dict)
                    and {"p50_ms", "p90_ms", "p99_ms", "samples"}
                    <= set(e2e)):
                errs.append(f"sync_age e2e malformed: {e2e!r:.120}")
            hops = sa.get("hops")
            if isinstance(hops, dict):
                for hop in SYNC_AGE_HOPS:
                    if hop not in hops:
                        errs.append(f"sync_age missing hop {hop!r}")
            else:
                errs.append(f"sync_age hops malformed: {hops!r:.120}")
    if rno >= RESIDENCY_SINCE:
        _check_block(rec, "residency", RESIDENCY_KEYS, errs)
        rs = rec.get("residency")
        if isinstance(rs, dict) and "error" not in rs \
                and "skipped" not in rs:
            bub = rs.get("bubble")
            if not (isinstance(bub, dict)
                    and {"p50_ms", "p90_ms", "p99_ms", "samples"}
                    <= set(bub)):
                errs.append(f"residency bubble malformed: {bub!r:.120}")
            cen = rs.get("census")
            if not (isinstance(cen, dict)
                    and {"samples", "realloc", "aliased"} <= set(cen)):
                errs.append(f"residency census malformed: {cen!r:.120}")
            if not isinstance(rs.get("alloc"), dict):
                # measured stats or {"unavailable": ...} — never absent
                errs.append(
                    f"residency alloc malformed: {rs.get('alloc')!r:.120}")
    if rno >= AUDIT_SINCE:
        _check_block(rec, "audit", AUDIT_KEYS, errs)
        au = rec.get("audit")
        if isinstance(au, dict) and "error" not in au \
                and "skipped" not in au:
            vt = au.get("violations_total")
            if not isinstance(vt, dict):
                errs.append(f"audit violations_total malformed: "
                            f"{vt!r:.120}")
            orc = au.get("oracle")
            if not (isinstance(orc, dict)
                    and {"samples", "entities_checked", "mismatches"}
                    <= set(orc)):
                errs.append(f"audit oracle malformed: {orc!r:.120}")
            con = au.get("conservation")
            if not (isinstance(con, dict) and "ok" in con):
                errs.append(f"audit conservation malformed: "
                            f"{con!r:.120}")
    if rno >= FAILOVER_SINCE:
        _check_block(rec, "failover", FAILOVER_KEYS, errs)
        fo = rec.get("failover")
        if isinstance(fo, dict) and "error" not in fo \
                and "skipped" not in fo:
            for k in ("entities_lost", "entities_duplicated",
                      "promotion_latency_ticks"):
                if k in fo and not _is_num(fo[k]):
                    errs.append(f"failover {k} malformed: "
                                f"{fo.get(k)!r:.120}")
    if rno >= REBALANCE_SINCE:
        _check_block(rec, "rebalance", REBALANCE_KEYS, errs)
        rb = rec.get("rebalance")
        if isinstance(rb, dict) and "error" not in rb \
                and "skipped" not in rb:
            for k in ("entities_lost", "entities_duplicated",
                      "entities_moved", "aborts",
                      "donor_recovery_windows"):
                if k in rb and rb[k] is not None \
                        and not _is_num(rb[k]):
                    errs.append(f"rebalance {k} malformed: "
                                f"{rb.get(k)!r:.120}")
    if rno >= RESIDENT_AB_SINCE:
        _check_block(rec, "resident_ab", RESIDENT_AB_KEYS, errs)
        ra = rec.get("resident_ab")
        if isinstance(ra, dict) and "error" not in ra \
                and "skipped" not in ra:
            for k in ("on_ms_per_tick", "off_ms_per_tick", "ratio"):
                if not _is_num(ra.get(k)):
                    errs.append(f"resident_ab {k} malformed: "
                                f"{ra.get(k)!r:.120}")
            for arm in ("on_census", "off_census"):
                cen = ra.get(arm)
                if not (isinstance(cen, dict)
                        and {"samples", "realloc", "aliased"}
                        <= set(cen)):
                    errs.append(f"resident_ab {arm} malformed: "
                                f"{cen!r:.120}")
    # per-scenario blocks, wherever present: each needs either a
    # headline-style shape or an honest error
    for sc, blk in (rec.get("scenarios") or {}).items():
        if not isinstance(blk, dict):
            errs.append(f"scenario {sc}: not a dict")
            continue
        if "error" in blk:
            continue
        for k in ("value", "tick_ms", "entities"):
            if k not in blk:
                errs.append(f"scenario {sc}: missing {k!r}")
    return errs


def validate_multichip(path: str, doc: dict) -> list[str]:
    errs: list[str] = []
    for k in ("n_devices", "rc", "ok", "tail"):
        if k not in doc:
            errs.append(f"missing key {k!r}")
    if doc.get("ok") and doc.get("rc", 0) != 0:
        errs.append(f"ok but rc={doc.get('rc')}")
    if "n_devices" in doc and (not _is_num(doc["n_devices"])
                               or doc["n_devices"] <= 0):
        errs.append(f"n_devices={doc.get('n_devices')!r}")
    rno = round_no(path)
    if rno < MULTI_HEADLINE_SINCE or doc.get("skipped"):
        return errs
    # the measured-mesh era (r >= 10): a real headline block with the
    # scan-marginal mesh number + efficiency, comms gauges, and the
    # device-plane stamps ({"error": ...} accepted as honest failure).
    # A FAILED round (rc != 0) is exempt like the BENCH contract —
    # its failure is already recorded honestly.
    if doc.get("rc", 1) != 0 and not doc.get("ok"):
        return errs
    hl = doc.get("headline")
    if not isinstance(hl, dict):
        errs.append("missing/invalid headline block "
                    f"(required since r{MULTI_HEADLINE_SINCE:02d})")
    elif "error" not in hl:
        for k in MULTI_HEADLINE_KEYS:
            if k not in hl:
                errs.append(f"headline missing key {k!r}")
        v = hl.get("entity_ticks_per_sec_mesh")
        if v is not None and (not _is_num(v) or v < 0):
            errs.append(f"entity_ticks_per_sec_mesh={v!r}")
        if doc.get("ok") and not hl.get("entity_ticks_per_sec_mesh"):
            errs.append("ok but headline carries no mesh number")
    _check_block(doc, "gauges", MULTI_GAUGE_KEYS, errs)
    _check_block(doc, "cost_report", ("name",), errs)
    _check_block(doc, "roofline_audit", ("phases",), errs)
    if rno >= WORKLOAD_SIG_SINCE:
        _check_block(doc, "workload_signature", WORKLOAD_SIG_KEYS,
                     errs)
    phases = doc.get("phases")
    if not isinstance(phases, dict) \
            or not isinstance(phases.get("border_churn"), dict):
        errs.append("missing phases.border_churn block "
                    f"(required since r{MULTI_HEADLINE_SINCE:02d})")
    return errs


def validate_file(path: str) -> list[str]:
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError) as exc:
        return [f"unreadable: {exc}"]
    if not isinstance(doc, dict):
        return ["top level is not a JSON object"]
    if "MULTICHIP" in os.path.basename(path):
        return validate_multichip(path, doc)
    return validate_bench(path, doc)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="validate checked-in BENCH/MULTICHIP artifacts")
    ap.add_argument("files", nargs="*",
                    help="explicit files (default: repo glob)")
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    args = ap.parse_args(argv)

    files = args.files or sorted(
        glob.glob(os.path.join(args.dir, "BENCH_r*.json"))
        + glob.glob(os.path.join(args.dir, "MULTICHIP_r*.json"))
    )
    if not files:
        # a walked directory with no artifacts has nothing to violate
        # (the repository keeps none of its own); a NAMED file that is
        # missing stays an error below
        print(f"no artifacts under {args.dir}: nothing to validate")
        return 0
    bad = 0
    for path in files:
        if not os.path.exists(path):
            print(f"missing file: {path}", file=sys.stderr)
            return 1
        errs = validate_file(path)
        name = os.path.basename(path)
        if errs:
            bad += 1
            for e in errs:
                print(f"{name}: {e}", file=sys.stderr)
        else:
            print(f"{name}: ok")
    return 2 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
