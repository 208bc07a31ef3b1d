#!/usr/bin/env python
"""The one-command pre-merge gate (ISSUE 19 satellite).

The repo grew three jax-free drift checks that every PR is expected
to hold green — and holding them green meant three manual
invocations. This chains them, in order, and exits non-zero the
moment any of them reports drift:

1. ``tools/obs_lint.py`` — the docs keep up with the debug plane
   (every endpoint documented, every pytest marker in the README);
2. ``tools/bench_schema.py`` — every checked-in BENCH_r*/MULTICHIP_r*
   artifact still satisfies its round-versioned shape contract;
3. ``tools/bench_trend.py`` — the LATEST round does not regress
   against its comparable predecessors (headline, splits, SLO, and
   the per-plane series: governor, sync-age, residency, audit,
   failover, rebalance, resident_ab — the last with the
   MUST-BE-ZERO gate on the donation-on arm's census realloc).

All three are imported in-process (they are jax-free by contract;
this gate runs in milliseconds on a laptop or a bare CI runner). A
gate that cannot even be imported counts as FAILED, not skipped —
silent skips are how drift lands.

Exit codes: 0 all gates green, 1 usage, 2 at least one gate failed.

Usage::

    python tools/ci_gate.py                  # the pre-merge one-liner
    python tools/ci_gate.py --threshold 0.2  # forwarded to bench_trend
    python tools/ci_gate.py --dir DIR        # walk DIR's artifacts
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))

# gate order is cheapest-first so the common failure (a doc row
# forgotten) reports before the trajectory walk
GATES = ("obs_lint", "bench_schema", "bench_trend")


def run_gates(threshold: float | None = None,
              directory: str | None = None) -> list[tuple[str, int]]:
    """Run every gate; return the (name, rc) list of FAILURES.
    ``directory`` is the artifact trajectory the two bench gates walk
    (their default: the repo root)."""
    if TOOLS not in sys.path:
        sys.path.insert(0, TOOLS)
    failures: list[tuple[str, int]] = []
    for name in GATES:
        print(f"== {name} ==", flush=True)
        try:
            mod = importlib.import_module(name)
        except Exception as exc:  # an unimportable gate is a failure
            print(f"{name}: import failed: {exc}")
            failures.append((name, -1))
            continue
        argv: list[str] = []
        if name != "obs_lint" and directory is not None:
            argv = ["--dir", directory]
        if name == "bench_trend" and threshold is not None:
            argv += ["--threshold", str(threshold)]
        try:
            rc = int(mod.main(argv))
        except SystemExit as exc:  # tolerate argparse-style exits
            rc = int(exc.code or 0)
        if rc != 0:
            failures.append((name, rc))
    return failures


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="chain obs_lint + bench_schema + bench_trend; "
                    "non-zero exit on any drift")
    ap.add_argument("--threshold", type=float, default=None,
                    help="regression threshold forwarded to "
                         "bench_trend (its default otherwise)")
    ap.add_argument("--dir", default=None,
                    help="artifact directory forwarded to bench_schema "
                         "and bench_trend (their default: the repo "
                         "root, which keeps none of its own)")
    args = ap.parse_args(argv)
    failures = run_gates(args.threshold, args.dir)
    if failures:
        print("ci_gate: FAILED — "
              + ", ".join(f"{n} (rc={rc})" for n, rc in failures))
        return 2
    print(f"ci_gate: ok ({len(GATES)} gates green)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
