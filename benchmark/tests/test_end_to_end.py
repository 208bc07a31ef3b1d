"""The harness end to end at the rehearsal size on the CPU (each case
starts and stops a whole cluster: about half a minute each).

* the last line is well formed, with --trace 0 and 1;
* told to find a TPU (no --rehearsal), it exits non-zero with no result;
* with the timed path broken underneath (a planted fault in the game
  process), or with the control (the fault plane dropping position
  packets, which breaks the delivery guarantee), ``correct`` comes out
  false — the rest of the run unchanged;
* the cell on four chips is rehearsed on four host devices: its world
  is one megaspace of 2x2 tiles, read back and judged as ONE world, and
  a share of what is judged lies across a tile border; a tile's migrate
  buffer that overflows and a row that goes missing read not
  ``correct`` through the counts only a tiled world has.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "tile.roam"
MEGA = "mega2x2.roam"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*extra, rehearsal=True, seed=5, seconds=8, trace=0,
        workload=WORKLOAD):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed), "--seconds",
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
    # one space: nothing lies across a border, and nothing has to
    assert last["checks"]["border_untested"]["value"] == 0
    assert last["over_border"]["finals_over_border"] == 0
    # rows.npz of a one-space cell holds what it always held: the arrays
    # of PR 24's fixture, recomputed here from the file's own
    with np.load(os.path.join(ROOT, ".bench_work", WORKLOAD,
                              "rows.npz")) as z:
        assert set(z.files) == {"pos", "alive", "rows", "nbr",
                                "avatar_rows", "avatar_eids", "tick"}
        assert z["pos"].shape == (2048, 3) and z["alive"].shape == (2048,)
        assert len(z["avatar_rows"]) == len(z["avatar_eids"]) == 16
        npc_rows = np.setdiff1d(np.nonzero(z["alive"])[0], z["avatar_rows"])
        rng = np.random.default_rng([2**31 + 11, 0x726F7773])
        want = np.concatenate([z["avatar_rows"], rng.choice(
            npc_rows, min(768, len(npc_rows)), replace=False)])
        assert (z["rows"] == want).all()
        assert z["nbr"].shape[0] == len(want)
        assert int(z["alive"].sum()) == 1500


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


def test_rehearsal_of_the_four_chip_cell():
    """One megaspace of 2x2 tiles on four host devices, 2,048 slots a
    tile: `correct`, with every count 0 and a share of what was judged
    across a tile border."""
    r, last = run(workload=MEGA, seed=2**31 + 12, seconds=10)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    assert last["correct"] is True, last["checks"]
    assert last["device"] == dict(last["device"], platform="cpu", count=4)
    assert {"entities_lost", "mesh_dropped", "border_untested"} \
        <= set(last["checks"])
    assert all(c["value"] == 0 for c in last["checks"].values())
    over = last["over_border"]
    assert over["crossings_over_border"] > 0
    assert over["finals_over_border"] > 0
    assert over["rows_near_border"] > 0
    assert last["attempted"] == 64 * (50 + 10)
    with np.load(os.path.join(ROOT, ".bench_work", MEGA, "rows.npz")) as z:
        # the whole world under one row number: four tiles of 2,048
        assert z["pos"].shape == (4 * 2048, 3)
        assert int(z["alive"].sum()) == 4 * 1500
        assert len(z["rows"]) == 64 + 4 * 768
        # every tile holds avatars, by tile * capacity + slot
        assert set((z["avatar_rows"] // 2048).tolist()) == {0, 1, 2, 3}


def test_planted_radius_is_caught_on_border_rows_of_the_tiled_world():
    r, last = run("--plant", "radius", workload=MEGA, seconds=10)
    assert last is not None, r.stdout[-3000:] + r.stderr[-2000:]
    assert last["correct"] is False
    assert "rows_wrong" in bad(last), last["checks"]
    assert last["over_border"]["rows_wrong_near_border"] > 0


@pytest.mark.parametrize("plant,caught", [
    # the program's alarm for a tile's migrate buffer set to go off at
    # any migration once the window opens (its host-side threshold; the
    # compiled tick keeps its buffers): its own overflow lines, from its
    # own logger, are in its own log, and nothing else reads wrong
    ("caps", "mesh_dropped"),
    # one NPC taken out of the world once the window opens, as a
    # migration that drops a row would: the device holds one row too few
    ("lose", "entities_lost"),
])
def test_a_tiled_worlds_own_counts_read_not_correct(plant, caught):
    r, last = run("--plant", plant, workload=MEGA, seconds=10)
    assert last is not None, r.stdout[-3000:] + r.stderr[-2000:]
    assert last["correct"] is False
    assert bad(last) == {caught}, last["checks"]


def test_control_reads_not_correct_on_the_tiled_world():
    r, last = run("--control-faults", "drop:gate->dispatcher:mt=14:0.9",
                  workload=MEGA, seconds=10)
    assert last is not None, r.stdout[-3000:] + r.stderr[-2000:]
    assert last["correct"] is False, last["checks"]
