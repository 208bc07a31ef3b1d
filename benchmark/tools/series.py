#!/usr/bin/env python3
"""Several runs of ``run.py`` in one call (one chip call: the full sets,
the rate sweep, the control), each a process of its own, one after the
other. Every run's whole output goes to ``chiprun_out/<label>/<i>.log``
and its last line, with the seed and what was varied, to
``chiprun_out/<label>.jsonl``; a compact line per run is printed.

    python benchmark/tools/series.py --label sets_tile --workload tile.roam \\
        --seeds 11,12,13 --seconds 40 [--trace 0,1] [--tick-hz 4,5,6] \\
        [--control-faults SPEC] [--cell-file FILE]

With ``--tick-hz`` or ``--trace`` a list, run ``i`` takes entry ``i``
(the sweep).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def nth(listed: str, i: int) -> str:
    """Entry ``i`` (cyclically) of a comma-separated option, "" where
    the option was not given."""
    values = [v for v in listed.split(",") if v]
    return values[i % len(values)] if values else ""


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--label", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="40")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--tick-hz", dest="tick_hz", default="")
    ap.add_argument("--control-faults", dest="control_faults", default="")
    ap.add_argument("--cell-file", dest="cell_file", default="")
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out", a.label)
    os.makedirs(out_dir, exist_ok=True)
    worst = 0
    with open(os.path.join(ROOT, "chiprun_out", a.label + ".jsonl"),
              "a") as jl:
        for i, seed in enumerate(seeds):
            cmd = [sys.executable, os.path.join(ROOT, "benchmark",
                                                "run.py"),
                   "--workload", a.workload, "--seed", str(seed),
                   "--seconds", a.seconds, "--trace", nth(a.trace, i)]
            rate = nth(a.tick_hz, i)
            for flag, value in (("--tick-hz", rate),
                                ("--control-faults", a.control_faults),
                                ("--cell-file", a.cell_file)):
                if value:
                    cmd += [flag, value]
            if a.rehearsal:
                cmd += ["--rehearsal"]
            t0 = time.monotonic()
            r = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                               text=True)
            wall = time.monotonic() - t0
            with open(os.path.join(out_dir, f"{i}.log"), "w") as f:
                f.write(r.stdout + "\n---- stderr ----\n" + r.stderr)
            # every served process's own account (the game's of its
            # frames), without the compile lines
            rd = os.path.join(ROOT, ".bench_work", a.workload, "run")
            for name in (sorted(os.listdir(rd)) if os.path.isdir(rd)
                         else ()):
                if name.endswith(".log"):
                    with open(os.path.join(rd, name),
                              errors="replace") as f, \
                            open(os.path.join(out_dir, f"{i}.{name}"),
                                 "w") as g:
                        g.writelines([ln[:200].rstrip("\n") + "\n"
                                      for ln in f
                                      if "Finished" not in ln][-4000:])
            for name in ("phases.json", "host_phases.json"):
                try:    # a traced run: what the capture held, by name
                    shutil.copy(os.path.join(ROOT, ".bench_work", a.workload,
                                             name),
                                os.path.join(out_dir, f"{i}.{name}"))
                except OSError:
                    pass
            lines = r.stdout.strip().splitlines()
            try:
                last = json.loads(lines[-1])
            except (ValueError, IndexError):
                last = None
            rec = {"i": i, "seed": seed, "rc": r.returncode,
                   "wall_s": wall, "tick_hz": rate,
                   "control": a.control_faults, "trace": a.trace,
                   "line": last,
                   "notes": [ln for ln in lines if ln.startswith(
                       ("[run] window closed", "[run] set-up",
                        "[run] end to end", "[run] clients"))]}
            jl.write(json.dumps(rec) + "\n")
            jl.flush()
            worst = max(worst, r.returncode)
            if last is None:
                print(f"run {i} seed {seed} rate {rate}: rc "
                      f"{r.returncode}, NO RESULT; tail:\n"
                      + "\n".join(lines[-15:]) + r.stderr[-1500:],
                      flush=True)
                continue
            m = {k: round(v["value"], 4)
                 for k, v in last["metrics"].items()}
            bad = {k: c["value"] for k, c in last["checks"].items()
                   if c["value"] > c["limit"]}
            print(f"run {i} seed {seed} rate {rate}: rc {r.returncode} "
                  f"wall {wall:.0f}s correct {last['correct']} failed "
                  f"{last['failed']}/{last['attempted']} compiles "
                  f"{last.get('compiles_in_window')} {m} bad {bad} mem "
                  f"{last['device'].get('memory_peak_bytes')}",
                  flush=True)
    return worst


if __name__ == "__main__":
    sys.exit(main())
