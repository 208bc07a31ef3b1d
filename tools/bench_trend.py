#!/usr/bin/env python
"""Regression gate over the BENCH_r*.json / MULTICHIP_r*.json trajectory.

The round artifacts were a pile of snapshots; this turns them into an
ENFORCED contract: read the whole checked-in trajectory and exit
non-zero when the LATEST round regresses against its comparable
predecessors. Runs in tier-1 against the checked-in files (jax-free,
milliseconds) and in CI after any new round lands.

Gating policy — the latest round only (historic inter-round swings,
e.g. r02->r03's workload change, are the recorded past, not a
regression introduced by the change under test):

* headline ``value`` (higher is better): latest must be within
  ``--threshold`` of the BEST prior round at the same
  (entities, platform) shape;
* ``tick_ms`` and every shared ``phase_ms`` entry (lower is better):
  latest vs the MOST RECENT comparable prior round — but when the
  same round's headline IMPROVED past the threshold vs that
  predecessor, split regressions demote to informational NOTES (the
  split gate exists to catch a phase rotting UNDER a flat headline;
  a much faster headline with a slower split is a machine/balance
  change the headline could not have hidden);
* per-scenario block ``value``s: same rule, matched by scenario name
  at equal entities;
* ``slo.pass``: a true -> false transition at the same shape fails;
* ``workload_signature``: a class-string drift vs the most recent
  comparable round is an informational NOTE, never a gate (the
  signature describes the workload, not the implementation — but a
  drift next to a perf swing is the first thing to read);
* ``rebalance`` (ISSUE 19): any lost/duplicated entity across the
  automated handoff or a failed DecisionLog byte replay in a real
  latest block is an UNCONDITIONAL failure (conservation needs no
  prior); ``donor_recovery_windows`` is a lower-is-better series
  gated against the best prior at the same (entities_moved,
  platform) shape with +1 window absolute slack;
* ``resident_ab`` (ISSUE 20): any re-allocated carry lane in the
  donation-on arm's census of a real latest block is an UNCONDITIONAL
  failure (the resident runtime's whole contract is zero steady-state
  allocation — no prior needed, like the audit's zero-violation
  gate); the on/off ``ratio`` (serve ms/tick with donation+overlap
  over without, lower is better, a pure ratio so no absolute slack)
  gates against the best prior at the same (entities, platform)
  shape; a pass->fail flip at the same shape is always a problem;
* MULTICHIP: the latest record must keep ``ok`` (when any prior round
  had it) and ``rc == 0``; measured mesh headlines (r >= 10) gate
  ``entity_ticks_per_sec_mesh`` against the best prior at the same
  (entities, platform, n_devices) shape and fail a
  ``per_chip_efficiency`` drop past the threshold.

Exit codes: 0 pass, 1 usage/missing file, 2 regression.

Usage::

    python tools/bench_trend.py                     # repo trajectory
    python tools/bench_trend.py --threshold 0.2
    python tools/bench_trend.py BENCH_r04.json BENCH_r05.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# jax-free artifact conventions shared with bench_schema/roofline_audit
from goworld_tpu.utils.devprof import (  # noqa: E402
    artifact_headline,
    artifact_round as _round_no,
)

DEFAULT_THRESHOLD = 0.30  # fractional regression that fails the gate


def load_headline(path: str) -> dict | None:
    """The stamped artifact dict (driver wrapper or bare); None when
    the round recorded no usable headline (failed rounds are skipped,
    not gated — their failure is already recorded honestly)."""
    with open(path) as fh:
        rec = artifact_headline(json.load(fh))
    if rec is not None and rec.get("timing_suspect"):
        return None  # a flagged headline is not a trustworthy baseline
    return rec


def _shape(rec: dict) -> tuple:
    """(entities, platform, mode): a headline measured under a
    governor schedule (``bench_mode = "governor"``) anchors its OWN
    series — its number includes swap dynamics and a scenario
    schedule, so gating it against a static-workload round (or vice
    versa) would compare different experiments. NOTE: today's
    ``bench.py --governor`` keeps the headline static and stamps the
    schedule as a separate ``governor`` block (gated by its own
    series below) — no current round stamps ``bench_mode``; this
    component is the enforcement hook for a future round whose
    HEADLINE runs governed, kept so such an artifact can never
    silently gate against the static history."""
    return (rec.get("entities"), rec.get("platform"),
            rec.get("bench_mode", "static"))


def _check_governor_series(rounds: list, latest: dict, name: str,
                           threshold: float, problems: list[str],
                           notes: list[str]) -> None:
    """The governor schedule block (ISSUE 13): its throughput is a
    series of its own, gated against the best prior round that ran
    the SAME (n, platform, schedule) shape — never against static
    headlines (and static headlines never gate against it).
    Skipped/error records neither gate nor anchor."""
    def _gov_ok(g) -> bool:
        return (isinstance(g, dict)
                and isinstance(g.get("throughput"), (int, float))
                and g["throughput"] > 0)

    lgov = latest.get("governor")
    if not _gov_ok(lgov):
        return
    gshape = (lgov.get("n"), latest.get("platform"),
              tuple(lgov.get("schedule") or ()))
    gprior = [
        (p, r["governor"]) for p, r in rounds[:-1]
        if _gov_ok(r.get("governor"))
        and (r["governor"].get("n"), r.get("platform"),
             tuple(r["governor"].get("schedule") or ())) == gshape
    ]
    if not gprior:
        notes.append(f"{name}: governor shape {gshape} has no "
                     "prior round — not gated")
        return
    gbest_path, gbest = max(gprior, key=lambda pr: pr[1]["throughput"])
    gfloor = (1.0 - threshold) * gbest["throughput"]
    if lgov["throughput"] < gfloor:
        problems.append(
            f"{name}: governor throughput "
            f"{lgov['throughput']:.0f} < {gfloor:.0f} "
            f"({(1 - threshold) * 100:.0f}% of "
            f"{os.path.basename(gbest_path)}'s "
            f"{gbest['throughput']:.0f})")
    else:
        notes.append(
            f"{name}: governor throughput "
            f"{lgov['throughput']:.0f} vs best prior "
            f"{gbest['throughput']:.0f} — ok")


def _check_sync_age_series(rounds: list, latest: dict, name: str,
                           threshold: float, problems: list[str],
                           notes: list[str]) -> None:
    """The sync-age loopback block (ISSUE 15): its e2e p99 is a
    delivery-latency series of its own, gated LOWER-IS-BETTER against
    the best (lowest-p99) prior round at the SAME (records_per_tick,
    clients, platform) shape. Skipped/error rounds and rounds whose
    p99 never resolved to a number neither gate nor anchor; a
    pass->fail flip at the same shape is always a problem (the slo
    rule)."""
    def _sa_ok(s) -> bool:
        return (isinstance(s, dict) and "error" not in s
                and "skipped" not in s
                and isinstance((s.get("e2e") or {}).get("p99_ms"),
                               (int, float)))

    lsa = latest.get("sync_age")
    if not _sa_ok(lsa):
        return
    sshape = (lsa.get("records_per_tick"), lsa.get("clients"),
              latest.get("platform"))
    sprior = [
        (p, r["sync_age"]) for p, r in rounds[:-1]
        if _sa_ok(r.get("sync_age"))
        and (r["sync_age"].get("records_per_tick"),
             r["sync_age"].get("clients"),
             r.get("platform")) == sshape
    ]
    if not sprior:
        notes.append(f"{name}: sync_age shape {sshape} has no prior "
                     "round — not gated")
        return
    lp99 = lsa["e2e"]["p99_ms"]
    best_path, best = min(sprior,
                          key=lambda pr: pr[1]["e2e"]["p99_ms"])
    ceil = (1.0 + threshold) * best["e2e"]["p99_ms"]
    if lp99 > ceil:
        problems.append(
            f"{name}: sync_age e2e p99 {lp99} ms > "
            f"{(1 + threshold) * 100:.0f}% of "
            f"{os.path.basename(best_path)}'s "
            f"{best['e2e']['p99_ms']} ms")
    else:
        notes.append(
            f"{name}: sync_age e2e p99 {lp99} ms vs best prior "
            f"{best['e2e']['p99_ms']} ms — ok")
    prev_path, prev = sprior[-1]
    if prev.get("pass") and not lsa.get("pass"):
        problems.append(
            f"{name}: sync_age verdict regressed pass -> fail "
            f"(e2e p99 {lp99} vs target {lsa.get('target_ms')} ms, "
            f"prior {os.path.basename(prev_path)})")


def _check_residency_series(rounds: list, latest: dict, name: str,
                            threshold: float, problems: list[str],
                            notes: list[str]) -> None:
    """The serve-loop residency block (ISSUE 16): its bubble p99 and
    serve_gap are lower-is-better series of their own, gated against
    the best (lowest) prior round at the SAME (entities, platform)
    shape. Skipped/error rounds neither gate nor anchor; a bubble p99
    of ``"inf"`` (mass past the last bucket, the ptiles convention) is
    the strongest regression a latest round can stamp but never
    anchors; a pass->fail flip at the same shape is always a problem
    (the slo rule)."""
    def _p99(s) -> float | None:
        v = (s.get("bubble") or {}).get("p99_ms")
        if v == "inf":
            return float("inf")
        return float(v) if isinstance(v, (int, float)) \
            and not isinstance(v, bool) else None

    def _rs_ok(s) -> bool:
        return (isinstance(s, dict) and "error" not in s
                and "skipped" not in s and _p99(s) is not None
                and isinstance(s.get("serve_gap"), (int, float)))

    lrs = latest.get("residency")
    if not _rs_ok(lrs):
        return
    rshape = (lrs.get("entities"), latest.get("platform"))
    rprior = [
        (p, r["residency"]) for p, r in rounds[:-1]
        if _rs_ok(r.get("residency"))
        and (r["residency"].get("entities"),
             r.get("platform")) == rshape
    ]
    if not rprior:
        notes.append(f"{name}: residency shape {rshape} has no prior "
                     "round — not gated")
        return
    # bubble p99 vs the best (lowest) FINITE prior. The +0.25 ms
    # absolute slack is one histogram bucket: a zero-bubble prior must
    # not turn timer noise on an otherwise-healthy round into a gate
    lp99 = _p99(lrs)
    finite = [(p, s) for p, s in rprior
              if _p99(s) != float("inf")]
    if finite:
        best_path, best = min(finite, key=lambda pr: _p99(pr[1]))
        ceil = (1.0 + threshold) * _p99(best) + 0.25
        if lp99 > ceil:
            problems.append(
                f"{name}: residency bubble p99 {lrs['bubble']['p99_ms']}"
                f" ms > {ceil:.3g} ms "
                f"({(1 + threshold) * 100:.0f}% of "
                f"{os.path.basename(best_path)}'s "
                f"{best['bubble']['p99_ms']} ms + 0.25)")
        else:
            notes.append(
                f"{name}: residency bubble p99 "
                f"{lrs['bubble']['p99_ms']} ms vs best prior "
                f"{best['bubble']['p99_ms']} ms — ok")
    # serve_gap (serve ms/tick over the scan-marginal reference):
    # lower is better, a pure ratio so no absolute slack needed
    lgap = lrs["serve_gap"]
    gbest_path, gbest = min(rprior, key=lambda pr: pr[1]["serve_gap"])
    gceil = (1.0 + threshold) * gbest["serve_gap"]
    if lgap > gceil:
        problems.append(
            f"{name}: residency serve_gap {lgap} > {gceil:.3g} "
            f"({(1 + threshold) * 100:.0f}% of "
            f"{os.path.basename(gbest_path)}'s {gbest['serve_gap']})")
    else:
        notes.append(
            f"{name}: residency serve_gap {lgap} vs best prior "
            f"{gbest['serve_gap']} — ok")
    prev_path, prev = rprior[-1]
    if prev.get("pass") and not lrs.get("pass"):
        problems.append(
            f"{name}: residency verdict regressed pass -> fail "
            f"(bubble p99 {lrs['bubble']['p99_ms']} vs budget "
            f"{lrs.get('bubble_budget_ms')} ms, prior "
            f"{os.path.basename(prev_path)})")


def _check_audit_series(rounds: list, latest: dict, name: str,
                        threshold: float, problems: list[str],
                        notes: list[str]) -> None:
    """The correctness-audit block (ISSUE 17): any recorded violation
    in a real latest block is ALWAYS a problem (the zero-violation
    gate needs no prior — a lost entity is a bug, not a trend); the
    measured plane overhead is a lower-is-better series gated against
    the best prior at the same (entities, platform) shape with a
    small absolute slack (timer noise on a sub-percent number); a
    conservation pass->fail flip at the same shape is always a
    problem (the slo rule)."""
    def _au_ok(s) -> bool:
        return (isinstance(s, dict) and "error" not in s
                and "skipped" not in s
                and isinstance(s.get("overhead_pct_of_budget"),
                               (int, float)))

    lau = latest.get("audit")
    if not _au_ok(lau):
        return
    viol = sum((lau.get("violations_total") or {}).values())
    if viol:
        kinds = ", ".join(sorted((lau.get("violations_total")
                                  or {}).keys()))
        problems.append(
            f"{name}: audit recorded {viol} violation(s) ({kinds}) — "
            "the bench soak must be violation-free")
    if not (lau.get("conservation") or {}).get("ok", True):
        problems.append(f"{name}: audit conservation verdict FAILED")
    ashape = (lau.get("entities"), latest.get("platform"))
    aprior = [
        (p, r["audit"]) for p, r in rounds[:-1]
        if _au_ok(r.get("audit"))
        and (r["audit"].get("entities"), r.get("platform")) == ashape
    ]
    if not aprior:
        notes.append(f"{name}: audit shape {ashape} has no prior "
                     "round — overhead not gated")
        return
    # overhead vs the best (lowest) prior; +0.1 pct-point absolute
    # slack keeps timer noise on a ~0.x% number from gating
    lov = lau["overhead_pct_of_budget"]
    best_path, best = min(aprior,
                          key=lambda pr: pr[1]["overhead_pct_of_budget"])
    ceil = (1.0 + threshold) * best["overhead_pct_of_budget"] + 0.1
    if lov > ceil:
        problems.append(
            f"{name}: audit overhead {lov}% of budget > {ceil:.3g}% "
            f"({(1 + threshold) * 100:.0f}% of "
            f"{os.path.basename(best_path)}'s "
            f"{best['overhead_pct_of_budget']}% + 0.1)")
    else:
        notes.append(
            f"{name}: audit overhead {lov}% of budget vs best prior "
            f"{best['overhead_pct_of_budget']}% — ok")
    prev_path, prev = aprior[-1]
    if prev.get("pass") and not lau.get("pass"):
        problems.append(
            f"{name}: audit verdict regressed pass -> fail "
            f"(prior {os.path.basename(prev_path)})")


def _check_failover_series(rounds: list, latest: dict, name: str,
                           threshold: float, problems: list[str],
                           notes: list[str]) -> None:
    """The hot-standby failover block (ISSUE 18): any lost or
    duplicated EntityID across promotion in a real latest block is
    ALWAYS a problem (conservation needs no prior — a lost entity is
    a bug, not a trend), as is any torn frame or a failed decision-log
    replay; the promotion latency is a lower-is-better series gated
    against the best prior at the same (entities, platform) shape
    with a 1-tick absolute slack (the resume tick quantizes it)."""
    def _fo_ok(s) -> bool:
        return (isinstance(s, dict) and "error" not in s
                and "skipped" not in s
                and isinstance(s.get("promotion_latency_ticks"),
                               (int, float)))

    lfo = latest.get("failover")
    if not _fo_ok(lfo):
        return
    lost = lfo.get("entities_lost", 0) or 0
    dup = lfo.get("entities_duplicated", 0) or 0
    if lost or dup:
        problems.append(
            f"{name}: failover lost {lost} / duplicated {dup} "
            "entity id(s) across promotion — conservation must hold")
    if lfo.get("frames_rejected", 0):
        problems.append(
            f"{name}: failover rejected "
            f"{lfo['frames_rejected']} torn frame(s) on a clean "
            "loopback stream")
    if lfo.get("decision_log_replay_ok") is False:
        problems.append(
            f"{name}: failover decision log failed byte replay")
    fshape = (lfo.get("entities"), latest.get("platform"))
    fprior = [
        (p, r["failover"]) for p, r in rounds[:-1]
        if _fo_ok(r.get("failover"))
        and (r["failover"].get("entities"),
             r.get("platform")) == fshape
    ]
    if not fprior:
        notes.append(f"{name}: failover shape {fshape} has no prior "
                     "round — promotion latency not gated")
        return
    # promotion latency vs the best (lowest) prior; +1 tick absolute
    # slack (the +1 resume tick quantizes the number)
    lat = lfo["promotion_latency_ticks"]
    best_path, best = min(
        fprior, key=lambda pr: pr[1]["promotion_latency_ticks"])
    ceil = ((1.0 + threshold) * best["promotion_latency_ticks"]) + 1
    if lat > ceil:
        problems.append(
            f"{name}: failover promotion latency {lat} ticks > "
            f"{ceil:.3g} ({(1 + threshold) * 100:.0f}% of "
            f"{os.path.basename(best_path)}'s "
            f"{best['promotion_latency_ticks']} + 1)")
    else:
        notes.append(
            f"{name}: failover promotion latency {lat} ticks vs best "
            f"prior {best['promotion_latency_ticks']} — ok")
    prev_path, prev = fprior[-1]
    if prev.get("pass") and not lfo.get("pass"):
        problems.append(
            f"{name}: failover verdict regressed pass -> fail "
            f"(prior {os.path.basename(prev_path)})")


def _check_rebalance_series(rounds: list, latest: dict, name: str,
                            threshold: float, problems: list[str],
                            notes: list[str]) -> None:
    """The self-healing rebalance block (ISSUE 19): any lost or
    duplicated entity across the automated handoff in a real latest
    block is ALWAYS a problem (conservation needs no prior), as is a
    failed DecisionLog byte replay; the donor recovery latency (in
    observation windows, None on an aborted round) is a
    lower-is-better series gated against the best prior at the same
    (entities_moved, platform) shape with a 1-window absolute slack
    (the observe cadence quantizes it)."""
    def _rb_ok(s) -> bool:
        return (isinstance(s, dict) and "error" not in s
                and "skipped" not in s)

    lrb = latest.get("rebalance")
    if not _rb_ok(lrb):
        return
    lost = lrb.get("entities_lost", 0) or 0
    dup = lrb.get("entities_duplicated", 0) or 0
    if lost or dup:
        problems.append(
            f"{name}: rebalance lost {lost} / duplicated {dup} "
            "entity id(s) across handoff — conservation must hold")
    if lrb.get("decision_log_replay_ok") is False:
        problems.append(
            f"{name}: rebalance decision log failed byte replay")
    lat = lrb.get("donor_recovery_windows")
    if not isinstance(lat, (int, float)):
        notes.append(f"{name}: rebalance donor recovery latency "
                     "absent (aborted/degenerate round) — not gated")
        return
    rshape = (lrb.get("entities_moved"), latest.get("platform"))
    rprior = [
        (p, r["rebalance"]) for p, r in rounds[:-1]
        if _rb_ok(r.get("rebalance"))
        and isinstance(r["rebalance"].get("donor_recovery_windows"),
                       (int, float))
        and (r["rebalance"].get("entities_moved"),
             r.get("platform")) == rshape
    ]
    if not rprior:
        notes.append(f"{name}: rebalance shape {rshape} has no prior "
                     "round — recovery latency not gated")
        return
    # recovery latency vs the best (lowest) prior; +1 window absolute
    # slack (the observe cadence quantizes the number)
    best_path, best = min(
        rprior, key=lambda pr: pr[1]["donor_recovery_windows"])
    ceil = ((1.0 + threshold) * best["donor_recovery_windows"]) + 1
    if lat > ceil:
        problems.append(
            f"{name}: rebalance donor recovery {lat} windows > "
            f"{ceil:.3g} ({(1 + threshold) * 100:.0f}% of "
            f"{os.path.basename(best_path)}'s "
            f"{best['donor_recovery_windows']} + 1)")
    else:
        notes.append(
            f"{name}: rebalance donor recovery {lat} windows vs best "
            f"prior {best['donor_recovery_windows']} — ok")
    prev_path, prev = rprior[-1]
    if prev.get("pass") and not lrb.get("pass"):
        problems.append(
            f"{name}: rebalance verdict regressed pass -> fail "
            f"(prior {os.path.basename(prev_path)})")


def _check_resident_series(rounds: list, latest: dict, name: str,
                           threshold: float, problems: list[str],
                           notes: list[str]) -> None:
    """The resident-world A/B block (ISSUE 20): a re-allocated carry
    lane in the donation-ON arm's census of a real latest block is
    ALWAYS a problem (the resident runtime's contract is zero
    steady-state allocation — it needs no prior, like the audit's
    zero-violation gate); an OFF arm that ALSO reads zero realloc
    means the A/B measured nothing and is flagged too; the on/off
    ``ratio`` (serve ms/tick with donation+overlap over without,
    lower is better, a pure ratio so no absolute slack) gates against
    the best prior at the same (entities, platform) shape; a
    pass->fail flip at the same shape is always a problem (the slo
    rule). Skipped/error rounds neither gate nor anchor."""
    def _realloc(cen) -> int | None:
        if not isinstance(cen, dict):
            return None
        v = cen.get("realloc")
        # the stamped block stores a count; the raw census snapshot
        # stores the lane list — accept both so a hand-rolled round
        # never slips the gate on a type mismatch
        if isinstance(v, bool):
            return None
        if isinstance(v, int):
            return v
        if isinstance(v, list):
            return len(v)
        return None

    def _ra_ok(s) -> bool:
        return (isinstance(s, dict) and "error" not in s
                and "skipped" not in s
                and _realloc(s.get("on_census")) is not None
                and isinstance(s.get("ratio"), (int, float))
                and not isinstance(s.get("ratio"), bool))

    lra = latest.get("resident_ab")
    if not _ra_ok(lra):
        return
    on_re = _realloc(lra["on_census"])
    if on_re:
        problems.append(
            f"{name}: resident_ab donation-on census re-allocated "
            f"{on_re} carry lane(s) — the resident serve loop must "
            "alias every lane in place (MUST be zero)")
    off_re = _realloc(lra.get("off_census"))
    if off_re == 0:
        problems.append(
            f"{name}: resident_ab donation-off census read 0 "
            "re-allocated lanes — the control arm shows no churn, so "
            "the A/B measured nothing")
    rshape = (lra.get("entities"), latest.get("platform"))
    rprior = [
        (p, r["resident_ab"]) for p, r in rounds[:-1]
        if _ra_ok(r.get("resident_ab"))
        and (r["resident_ab"].get("entities"),
             r.get("platform")) == rshape
    ]
    if not rprior:
        notes.append(f"{name}: resident_ab shape {rshape} has no "
                     "prior round — ratio not gated")
        return
    # on/off ratio vs the best (lowest) prior: lower is better, a
    # pure ratio so no absolute slack needed (the two arms share one
    # box and one window, so machine speed divides out)
    lratio = lra["ratio"]
    best_path, best = min(rprior, key=lambda pr: pr[1]["ratio"])
    ceil = (1.0 + threshold) * best["ratio"]
    if lratio > ceil:
        problems.append(
            f"{name}: resident_ab ratio {lratio} > {ceil:.3g} "
            f"({(1 + threshold) * 100:.0f}% of "
            f"{os.path.basename(best_path)}'s {best['ratio']})")
    else:
        notes.append(
            f"{name}: resident_ab ratio {lratio} vs best prior "
            f"{best['ratio']} — ok")
    prev_path, prev = rprior[-1]
    if prev.get("pass") and not lra.get("pass"):
        problems.append(
            f"{name}: resident_ab verdict regressed pass -> fail "
            f"(prior {os.path.basename(prev_path)})")


def check_bench(files: list[str], threshold: float,
                problems: list[str], notes: list[str]) -> None:
    rounds = []
    for path in sorted(files, key=_round_no):
        rec = load_headline(path)
        if rec is None:
            notes.append(f"{os.path.basename(path)}: no headline "
                         "(failed/suspect round) — skipped")
            continue
        rounds.append((path, rec))
    if len(rounds) < 2:
        notes.append("bench: <2 comparable rounds, nothing to gate")
        return
    latest_path, latest = rounds[-1]
    name = os.path.basename(latest_path)
    # the governor schedule block (ISSUE 13) gates FIRST: its series
    # is keyed by its own (n, platform, schedule) shape, independent
    # of the headline's — a round that changes the headline shape
    # (no headline prior -> early return below) must not silently
    # skip the governor comparison
    _check_governor_series(rounds, latest, name, threshold,
                           problems, notes)
    # the sync-age delivery series (ISSUE 15) likewise gates above the
    # headline-prior early return: its shape is independent of the
    # headline's
    _check_sync_age_series(rounds, latest, name, threshold,
                           problems, notes)
    # the serve-loop residency series (ISSUE 16): same hoisting — its
    # (entities, platform) shape is the BLOCK's, not the headline's
    _check_residency_series(rounds, latest, name, threshold,
                            problems, notes)
    # the correctness-audit series (ISSUE 17): same hoisting — the
    # zero-violation gate must fire even on a headline-shape change
    _check_audit_series(rounds, latest, name, threshold,
                        problems, notes)
    # the hot-standby failover series (ISSUE 18): same hoisting — the
    # conservation gate must fire even on a headline-shape change
    _check_failover_series(rounds, latest, name, threshold,
                           problems, notes)
    # the self-healing rebalance series (ISSUE 19): same hoisting —
    # the zero-loss gate must fire even on a headline-shape change
    _check_rebalance_series(rounds, latest, name, threshold,
                            problems, notes)
    # the resident-world A/B series (ISSUE 20): same hoisting — the
    # zero-realloc gate must fire even on a headline-shape change
    _check_resident_series(rounds, latest, name, threshold,
                           problems, notes)
    prior = [(p, r) for p, r in rounds[:-1]
             if _shape(r) == _shape(latest)]
    if not prior:
        notes.append(f"{name}: shape {_shape(latest)} has no prior "
                     "round — headline not gated")
        return
    # headline value vs the BEST comparable predecessor
    best_path, best = max(prior, key=lambda pr: pr[1]["value"])
    floor = (1.0 - threshold) * best["value"]
    if latest["value"] < floor:
        problems.append(
            f"{name}: headline {latest['value']:.0f} < "
            f"{floor:.0f} ({(1 - threshold) * 100:.0f}% of "
            f"{os.path.basename(best_path)}'s {best['value']:.0f})")
    else:
        notes.append(f"{name}: headline {latest['value']:.0f} vs best "
                     f"prior {best['value']:.0f} — ok")
    # tick_ms + phases vs the MOST RECENT comparable predecessor.
    # The per-phase gate exists to catch a phase silently rotting
    # UNDER a flat headline; when the same round's headline IMPROVED
    # past the threshold vs that same predecessor, a slower phase
    # split is a machine/balance change, not a regression the headline
    # could have hidden (r12 vs r05: 1.9x faster headline on different
    # hardware with a slower collect split) — surfaced as NOTES so the
    # drift is still on the record, never silent
    prev_path, prev = prior[-1]
    pname = os.path.basename(prev_path)
    headline_improved = (
        isinstance(prev.get("value"), (int, float)) and prev["value"] > 0
        and latest["value"] >= (1.0 + threshold) * prev["value"]
    )
    split_sink = notes if headline_improved else problems

    def split_flag(msg: str) -> None:
        split_sink.append(
            msg + (" (headline improved "
                   f"{latest['value'] / prev['value']:.2f}x vs {pname}"
                   " — machine/balance change, not gated)"
                   if headline_improved else ""))

    for key in ("tick_ms",):
        if key in latest and key in prev and prev[key] > 0:
            if latest[key] > (1.0 + threshold) * prev[key]:
                split_flag(
                    f"{name}: {key} {latest[key]} > "
                    f"{(1 + threshold) * 100:.0f}% of {pname}'s "
                    f"{prev[key]}")
    for ph, ms in (latest.get("phase_ms") or {}).items():
        pms = (prev.get("phase_ms") or {}).get(ph)
        if pms and isinstance(ms, (int, float)) and pms > 0:
            if ms > (1.0 + threshold) * pms:
                split_flag(
                    f"{name}: phase {ph} {ms} ms > "
                    f"{(1 + threshold) * 100:.0f}% of {pname}'s "
                    f"{pms} ms")
    # per-scenario headline blocks, matched by name at equal entities
    for sc, blk in (latest.get("scenarios") or {}).items():
        pblk = (prev.get("scenarios") or {}).get(sc)
        if not (isinstance(blk, dict) and isinstance(pblk, dict)):
            continue
        if blk.get("entities") != pblk.get("entities"):
            continue
        v, pv = blk.get("value"), pblk.get("value")
        if isinstance(v, (int, float)) and isinstance(pv, (int, float)) \
                and pv > 0 and v < (1.0 - threshold) * pv:
            problems.append(
                f"{name}: scenario {sc} value {v:.0f} < "
                f"{(1 - threshold) * 100:.0f}% of {pname}'s {pv:.0f}")
    # SLO: a pass that turns into a fail at the same shape regressed
    lslo, pslo = latest.get("slo"), prev.get("slo")
    if isinstance(lslo, dict) and isinstance(pslo, dict):
        if pslo.get("pass") and not lslo.get("pass"):
            problems.append(
                f"{name}: slo pass regressed true -> false "
                f"(p99 {lslo.get('p99_ms')} vs target "
                f"{lslo.get('target_ms')})")
    # workload-signature drift is INFORMATIONAL, never gated: the
    # signature classifies the measured workload, and a class change at
    # the same shape usually means the bench mix changed on purpose —
    # but a silent drift next to a perf swing is the first thing a
    # reader should see, so it's surfaced as a note
    lsig = (latest.get("workload_signature") or {}).get("sig")
    psig = (prev.get("workload_signature") or {}).get("sig")
    if lsig and psig and lsig != psig:
        notes.append(
            f"{name}: workload signature drifted vs {pname}: "
            f"{psig} -> {lsig} (informational, not gated)")
    elif lsig:
        notes.append(f"{name}: workload signature {lsig}")


def _multi_headline(doc: dict) -> dict | None:
    """The measured mesh headline of one MULTICHIP record, or None
    (dryrun-only rounds, failed rounds, error/suspect headlines)."""
    hl = doc.get("headline")
    if not isinstance(hl, dict) or "error" in hl \
            or hl.get("timing_suspect"):
        return None
    v = hl.get("entity_ticks_per_sec_mesh")
    if not isinstance(v, (int, float)) or v <= 0:
        return None
    return hl


def _multi_shape(hl: dict) -> tuple:
    return (hl.get("n_entities"), hl.get("platform"),
            hl.get("n_devices"))


def check_multichip(files: list[str], problems: list[str],
                    notes: list[str],
                    threshold: float = DEFAULT_THRESHOLD) -> None:
    recs = []
    for path in sorted(files, key=_round_no):
        with open(path) as fh:
            recs.append((path, json.load(fh)))
    if not recs:
        return
    latest_path, latest = recs[-1]
    name = os.path.basename(latest_path)
    any_prior_ok = any(r.get("ok") for _p, r in recs[:-1])
    if latest.get("skipped"):
        notes.append(f"{name}: skipped run — not gated")
        return
    if any_prior_ok and not latest.get("ok"):
        problems.append(f"{name}: multichip ok regressed true -> false")
    if latest.get("rc", 0) != 0 and any_prior_ok:
        problems.append(f"{name}: multichip rc={latest.get('rc')}")
    if latest.get("ok"):
        notes.append(f"{name}: multichip ok "
                     f"(n_devices={latest.get('n_devices')})")
    # the measured mesh headline (r >= 10): latest vs the BEST prior
    # at the same (entities, platform, n_devices) shape, plus a
    # dedicated per_chip_efficiency gate — a mesh that keeps its
    # throughput by burning more chips is still a regression
    hl = _multi_headline(latest)
    if hl is None:
        return
    prior = [(p, h) for p, r in recs[:-1]
             if (h := _multi_headline(r)) is not None
             and _multi_shape(h) == _multi_shape(hl)]
    if not prior:
        notes.append(f"{name}: mesh shape {_multi_shape(hl)} has no "
                     "prior headline — not gated")
        return
    best_path, best = max(
        prior, key=lambda pr: pr[1]["entity_ticks_per_sec_mesh"])
    floor = (1.0 - threshold) * best["entity_ticks_per_sec_mesh"]
    v = hl["entity_ticks_per_sec_mesh"]
    if v < floor:
        problems.append(
            f"{name}: mesh headline {v:.0f} < {floor:.0f} "
            f"({(1 - threshold) * 100:.0f}% of "
            f"{os.path.basename(best_path)}'s "
            f"{best['entity_ticks_per_sec_mesh']:.0f})")
    else:
        notes.append(f"{name}: mesh headline {v:.0f} vs best prior "
                     f"{best['entity_ticks_per_sec_mesh']:.0f} — ok")
    eff = hl.get("per_chip_efficiency")
    best_eff = max((h.get("per_chip_efficiency") or 0.0)
                   for _p, h in prior)
    if isinstance(eff, (int, float)) and best_eff > 0 \
            and eff < (1.0 - threshold) * best_eff:
        problems.append(
            f"{name}: per_chip_efficiency {eff:.3f} dropped >"
            f"{threshold * 100:.0f}% vs best prior {best_eff:.3f}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="fail on regressions across the checked-in bench "
                    "trajectory")
    ap.add_argument("files", nargs="*",
                    help="explicit artifact files (default: repo glob "
                         "of BENCH_r*.json + MULTICHIP_r*.json)")
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="repo root to glob (default: this checkout)")
    ap.add_argument("--threshold", type=float,
                    default=DEFAULT_THRESHOLD,
                    help="fractional regression that fails "
                         f"(default {DEFAULT_THRESHOLD})")
    args = ap.parse_args(argv)

    if args.files:
        files = args.files
        for f in files:
            if not os.path.exists(f):
                print(f"missing file: {f}", file=sys.stderr)
                return 1
    else:
        files = sorted(
            glob.glob(os.path.join(args.dir, "BENCH_r*.json"))
            + glob.glob(os.path.join(args.dir, "MULTICHIP_r*.json"))
        )
        if not files:
            # nothing walked, nothing regressed (the repository keeps
            # no artifacts of its own); a NAMED missing file is the
            # usage error above
            print(f"no BENCH_r*/MULTICHIP_r* files under {args.dir}: "
                  "nothing to gate")
            return 0
    bench = [f for f in files
             if "BENCH" in os.path.basename(f)
             and "_interim" not in os.path.basename(f)]
    multi = [f for f in files if "MULTICHIP" in os.path.basename(f)]

    problems: list[str] = []
    notes: list[str] = []
    if bench:
        check_bench(bench, args.threshold, problems, notes)
    if multi:
        check_multichip(multi, problems, notes, args.threshold)
    for n in notes:
        print(f"  {n}")
    if problems:
        print(f"\nREGRESSIONS ({len(problems)}):", file=sys.stderr)
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 2
    print("trend: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
