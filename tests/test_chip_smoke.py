"""chip_smoke.py at its rehearsal size, on the CPU, in a subprocess —
so the smoke's control flow cannot rot between chip runs.

On the CPU every phase must PASS and the device check alone must fail:
the last line says ``"ok": false`` with platform ``cpu`` and the exit
code is non-zero. A phase that really fails says so in its own line.
And the parents that start chip-owning children must stay off jax (a
chip belongs to one process at a time).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(tmp_path, *args, devices: int = 1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearsal", "--out", str(tmp_path / "out"), *args],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=900)
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    phases = {}
    for ln in lines:
        if ln.startswith('{"phase"'):
            d = json.loads(ln)
            phases[d["phase"]] = d
    return r, lines, phases


def test_rehearsal_every_phase_passes_and_only_the_device_check_fails(
        tmp_path):
    r, lines, phases = _smoke(tmp_path)
    tail = r.stdout[-4000:] + r.stderr[-2000:]
    assert set(phases) == {"world", "cluster"}, tail
    for name, p in phases.items():
        assert p["ok"], f"{name} failed on the CPU: {p}\n{tail}"
    last = json.loads(lines[-1])
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert r.returncode != 0
    # what the phases must have shown on the way
    assert phases["world"]["checks"]["oracle"]
    assert phases["world"]["checks"][
        "no_interest_event_dropped_in_steady_ticks"]
    c = phases["cluster"]["checks"]
    for key in ("bots_served", "reload_printed_reloaded",
                "reload_tick_was_a_cache_hit",
                "bots_served_after_reload", "no_process_left",
                "dispatcher_and_gate_hold_no_backend",
                "holds_tick_hz", "holds_tick_hz_after_reload",
                "ladder_stayed_normal",
                "ladder_stayed_normal_after_reload",
                "no_interest_event_dropped_while_serving",
                "no_interest_event_dropped_while_serving_after_reload"):
        assert c[key], (key, phases["cluster"])
    assert phases["cluster"]["game_device"]["platform"] == "cpu"
    # the served rate is the size's fixed one, never derived from the
    # run, and the deployment's rate is stated beside it
    assert phases["cluster"]["tick_hz"] == 2.0
    assert phases["cluster"]["target_hz"] == 60.0
    assert "holds_target_hz" in phases["world"]
    with open(tmp_path / "out" / "server" / "goworld_tpu.ini") as f:
        assert "tick_hz = 2.0\n" in f.read()


def test_a_failing_phase_says_so_and_fails_the_run(tmp_path):
    # more live entities than the world has slots: the world child
    # (and the game's boot) cannot build it
    r, lines, phases = _smoke(tmp_path, "--entities", "5000")
    assert r.returncode != 0
    assert phases["world"]["ok"] is False
    assert phases["cluster"]["ok"] is False
    assert phases["cluster"]["checks"]["start"] is False
    # nothing is left running even when start failed half-way
    assert phases["cluster"]["checks"]["no_process_left"]
    assert json.loads(lines[-1])["ok"] is False


def test_the_real_size_serves_at_one_fixed_rate():
    """No toy shape and no rate computed from the run on the chip path:
    the defaults are the ISSUE's world, served at a stated constant."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.REAL == dict(capacity=131072, entities=100_000, ticks=40,
                            sample=300, bots=16, tick_hz=1.0)
    assert mod.TARGET_HZ == 60.0
    assert 0.5 < mod.HOLD_SHARE <= 1.0 and mod.HOLD_FRAMES >= 30


def test_compile_cache_is_placed_from_outside_or_at_the_checkout(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code. Unset:
    <checkout>/.jax_compile_cache, whatever the cwd."""
    code = ("from goworld_tpu.utils import compile_cache\n"
            "import jax\n"
            "print(compile_cache.setup())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run(env):
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           cwd=str(tmp_path), capture_output=True,
                           text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.split()

    here = os.path.join(REPO, ".jax_compile_cache")
    assert run(env) == [here, here]
    outside = str(tmp_path / "cache")
    assert run(dict(env, JAX_COMPILATION_CACHE_DIR=outside)) \
        == [outside, outside]


def test_mesh_rehearsal_on_four_virtual_devices(tmp_path):
    """--chips 4 runs the cross-chip paths and nothing else."""
    r, lines, phases = _smoke(tmp_path, "--chips", "4", devices=4)
    assert set(phases) == {"mesh"}, r.stdout[-3000:] + r.stderr[-2000:]
    assert phases["mesh"]["ok"], phases["mesh"]
    c = phases["mesh"]["checks"]
    for key in ("mega_each_device_holds_its_quarter", "mega_oracle",
                "mega_entities_migrated", "spaces_oracle",
                "spaces_each_device_holds_its_quarter",
                "spaces_migrants_arrived"):
        assert c[key], (key, phases["mesh"])
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["count"] == 4
    assert r.returncode != 0


@pytest.mark.parametrize("target", [
    "goworld_tpu.cli", "goworld_tpu.__main__:status",
    "goworld_tpu.net.gate", "goworld_tpu.net.dispatcher",
    "bench.py", "chip_smoke.py", "tools/chaos_soak.py",
    "examples/multihost_demo/run_cluster.py",
])
def test_parents_of_chip_owning_children_never_import_jax(target,
                                                          tmp_path):
    if target.endswith(":status"):
        code = ("import runpy, sys\n"
                f"sys.argv = ['goworld_tpu', 'status', {str(tmp_path)!r}]\n"
                "try:\n"
                "    runpy.run_module('goworld_tpu', run_name='__main__')\n"
                "except SystemExit:\n"
                "    pass\n")
    elif target.endswith(".py"):
        code = ("import importlib.util, sys\n"
                "sys.argv = ['x']\n"
                "spec = importlib.util.spec_from_file_location("
                f"'parent_under_test', {os.path.join(REPO, target)!r})\n"
                "mod = importlib.util.module_from_spec(spec)\n"
                "spec.loader.exec_module(mod)\n")
    else:
        code = f"import importlib\nimportlib.import_module({target!r})\n"
    code += ("import sys\n"
             "bad = [m for m in ('jax', 'jaxlib', 'goworld_tpu.api')"
             " if m in sys.modules]\n"
             "assert not bad, bad\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=REPO))
    assert r.returncode == 0, r.stderr[-2000:]
