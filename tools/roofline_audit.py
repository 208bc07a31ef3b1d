#!/usr/bin/env python
"""Roofline audit over the BENCH_r*.json trajectory.

The docs/ROOFLINE.md hand model is machine-readable now
(``goworld_tpu.utils.devprof.roofline_model_bytes``) and every new
bench round stamps a ``roofline_audit`` block (modeled vs XLA-derived
vs measured per phase, with drift %). This tool closes the loop over
the CHECKED-IN trajectory:

* default: print the per-phase drift table of every stamped audit
  (one section per round) so model rot is visible at a glance;
* ``--stamp``: BACKFILL — for rounds that predate the audit (r02-r05),
  recompute the block from the round's own stamped shape + kernel
  config and rewrite the file in place. XLA columns are included when
  jax is importable (the phase probes are re-lowered at the round's
  entities count on the current backend — labeled, since the original
  round's lowering is gone); without jax the block carries the model
  and measured columns only.
* ``--check``: exit non-zero when any round with a headline lacks the
  audit block (CI mode; pair with --stamp to fix).

Usage::

    python tools/roofline_audit.py                  # report
    python tools/roofline_audit.py --stamp          # backfill files
    python tools/roofline_audit.py --check BENCH_r05.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from goworld_tpu.utils import devprof  # noqa: E402
from goworld_tpu.utils.devprof import (  # noqa: E402
    artifact_headline as headline,
)

# bench defaults of the rounds that predate kernel stamps (r02-r04
# shipped before the headline carried sweep/topk/sort/skin); the
# backfill labels the assumption
LEGACY_GRID = {"k": 32, "cell_cap": 12, "sort_impl": "argsort",
               "sweep_impl": "ranges", "skin": 0.0}


def grid_kw_from_headline(rec: dict) -> dict:
    n = int(rec.get("entities", 0) or 0)
    # the bench density formula: extent so ~12 Chebyshev neighbors
    extent = float(int((max(n, 1) * 10000 / 12) ** 0.5))
    kw = dict(LEGACY_GRID, radius=50.0, extent_x=extent,
              extent_z=extent)
    for key in ("sweep_impl", "topk_impl", "sort_impl", "skin",
                "verlet_cap"):
        if key in rec:
            kw[key] = rec[key]
    return kw


def phase_costs_live(rec: dict) -> dict:
    """XLA cost reports of the bench phase probes at this round's
    shape, on the CURRENT backend (backfill is a re-lowering, not the
    round's original artifact — the table labels it)."""
    try:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench_for_audit", os.path.join(REPO, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        n = int(rec["entities"])
        overrides = {k: rec[k] for k in ("sweep_impl", "topk_impl",
                                         "sort_impl", "skin")
                     if k in rec}
        cfg, st, inputs = bench.build(n, 0.01, overrides or None)
        _ms, costs = bench.measure_phases(cfg, st, inputs, ticks=2)
        return costs
    except Exception as exc:
        print(f"  (no XLA columns: {str(exc)[:120]})", file=sys.stderr)
        return {}


def audit_for(rec: dict, live: bool) -> dict:
    n = int(rec.get("entities", 0) or 0)
    costs = phase_costs_live(rec) if live else {}
    block = devprof.roofline_audit(
        rec.get("phase_ms") or {}, costs, n,
        grid_kw_from_headline(rec), platform=rec.get("platform"),
        device_kind=(rec.get("device") or {}).get("kind")
        if isinstance(rec.get("device"), dict) else None,
    )
    if live and costs:
        block["backfilled"] = "xla columns re-lowered on current backend"
    stamped = [k for k in ("sweep_impl", "sort_impl", "skin")
               if k in rec]
    if not stamped:
        block["assumed_config"] = dict(LEGACY_GRID)
    return block


def print_table(path: str, block: dict) -> None:
    print(f"\n== {os.path.basename(path)} "
          f"(n={block.get('n')}, platform={block.get('platform')})")
    # donate MB = donation_applied_mb (bytes aliasing DID reclaim),
    # reclaim MB = donation_reclaimable_mb (bytes it still could)
    hdr = f"{'phase':<12}{'model MB':>10}{'xla MB':>10}" \
          f"{'drift %':>9}{'meas ms':>9}{'peak ms':>8}" \
          f"{'donate MB':>11}{'reclaim MB':>12}"
    print(hdr)
    for name, row in block.get("phases", {}).items():
        print(f"{name:<12}"
              f"{row.get('model_mb', '-'):>10}"
              f"{row.get('xla_mb', '-'):>10}"
              f"{row.get('drift_pct', '-'):>9}"
              f"{row.get('measured_ms', '-'):>9}"
              f"{row.get('model_ms', '-'):>8}"
              f"{row.get('donation_applied_mb', '-'):>11}"
              f"{row.get('donation_reclaimable_mb', '-'):>12}")
    if "total_drift_pct" in block:
        print(f"{'TOTAL':<12}{block['total_model_mb']:>10}"
              f"{block.get('total_xla_mb', '-'):>10}"
              f"{block['total_drift_pct']:>9}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="diff the ROOFLINE.md hand model against XLA cost "
                    "analysis across the BENCH trajectory")
    ap.add_argument("files", nargs="*",
                    help="BENCH_r*.json files (default: repo glob)")
    ap.add_argument("--stamp", action="store_true",
                    help="backfill roofline_audit blocks into files "
                         "that lack one (rewrites in place)")
    ap.add_argument("--force", action="store_true",
                    help="with --stamp: recompute even when a block "
                         "already exists")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when any headline round lacks "
                         "the audit block")
    args = ap.parse_args(argv)

    files = args.files or sorted(
        f for f in glob.glob(os.path.join(REPO, "BENCH_r*.json"))
        if "_interim" not in f
    )
    missing = []
    for path in files:
        if not os.path.exists(path):
            print(f"{path}: missing", file=sys.stderr)
            return 1
        with open(path) as fh:
            doc = json.load(fh)
        rec = headline(doc)
        if rec is None:
            print(f"\n== {os.path.basename(path)}: no headline "
                  "(failed round) — skipped")
            continue
        block = rec.get("roofline_audit")
        if block is None or (args.stamp and args.force):
            if args.stamp:
                block = audit_for(rec, live=True)
                rec["roofline_audit"] = block
                if "parsed" in doc:
                    doc["parsed"] = rec
                with open(path, "w") as fh:
                    json.dump(doc, fh, indent=1)
                    fh.write("\n")
                print(f"stamped {os.path.basename(path)}")
            else:
                missing.append(path)
                block = audit_for(rec, live=False)
                block["unstamped"] = True
        print_table(path, block)
    if args.check and missing:
        print(f"\n{len(missing)} round(s) lack a stamped "
              f"roofline_audit: "
              f"{', '.join(os.path.basename(m) for m in missing)}",
              file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
