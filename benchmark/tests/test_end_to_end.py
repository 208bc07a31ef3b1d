"""The harness end to end at the rehearsal size on the CPU (each case
starts and stops a whole cluster: about half a minute each).

* the last line is well formed, with --trace 0 and 1;
* told to find a TPU (no --rehearsal), it exits non-zero with no result;
* with the timed path broken underneath (a planted fault in the game
  process), or with the control (the fault plane dropping position
  packets, which breaks the delivery guarantee), ``correct`` comes out
  false — the rest of the run unchanged.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "tile.roam"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*extra, rehearsal=True, seed=5, seconds=8, trace=0):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", WORKLOAD, "--seed", str(seed), "--seconds",
           str(seconds), "--trace", str(trace), *extra]
    if rehearsal:
        cmd.append("--rehearsal")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    lines = r.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1])
    except (ValueError, IndexError):
        last = None
    return r, last


def bad(last):
    return {k for k, c in last["checks"].items()
            if c["value"] > c["limit"]}


def test_rehearsal_last_line_well_formed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    r, last = run(seed=2**31 + 11)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    assert KEYS <= set(last) and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 16 * (40 + 8)
    assert set(last["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert last["device"]["platform"] == "cpu"      # a rehearsal says so
    assert last["compiles_in_window"] == 0
    # every window opens at the same place against the audit plane's
    # cadence: this one holds its sample
    assert last["audit_samples_in_window"] == 1
    # the numbers compared, each beside its limit, end the errors too
    tail = r.stderr.strip().splitlines()
    assert tail[-1] == "correct: True"
    assert tail[-2].startswith("check ") and "(limit " in tail[-2]


def test_rehearsal_traced_reports_what_it_can_read():
    r, last = run(trace=1, seconds=10)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    # no device plane in a CPU capture: the device readers report
    # nothing (never 0); the counters' readers report
    assert {"gen_late_ms.p95", "gate_flush_ms", "dispatcher_ms",
            "frame_ms"} <= set(last["metrics"])
    assert "device_idle_share" not in last["metrics"]
    assert "tick_roofline" not in last["metrics"]


def test_must_find_a_tpu():
    r, last = run(rehearsal=False)
    assert r.returncode != 0
    assert last is None


def test_unknown_workload():
    r, last = run("--workload", "no.such")
    assert r.returncode != 0 and last is None


@pytest.mark.parametrize("plant,caught", [
    ("alter", "pos_wrong"),         # an answer altered where it is made
    ("half", "final_missing"),      # half of the batch left out
    ("freeze", "final_missing"),    # the state returned unchanged
    ("radius", "rows_wrong"),       # the sweep's box smaller than stated
])
def test_planted_fault_reads_not_correct(plant, caught):
    r, last = run("--plant", plant)
    assert last is not None, r.stdout[-3000:] + r.stderr[-2000:]
    assert last["correct"] is False
    assert caught in bad(last), last["checks"]


def test_control_reads_not_correct():
    # nine batches in ten dropped: at this size the clients' last sends
    # ride two or three of the gate's batches, and with half dropped
    # all of them get through in two runs of five (read on the CPU)
    r, last = run("--control-faults",
                  "drop:gate->dispatcher:mt=14:0.9", seconds=10)
    assert last is not None, r.stdout[-3000:] + r.stderr[-2000:]
    assert last["correct"] is False, last["checks"]
