#!/usr/bin/env python3
"""One run of one benchmark cell: bots at the gate of a served world.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The parent never imports jax. It reads the cell from ``BENCHMARK.json``,
its configuration from ``benchmark/configs/<config>.json`` and its mix
from ``benchmark/traffic/<mix>.json``, writes a server directory
(``benchmark/fixture``), runs ``python -m goworld_tpu start`` (dispatcher
+ game + gate), refuses to go on unless the game says it serves on a TPU
with the cell's chips, lets ``bots.py`` log every client in and place
it, waits for warm frames, times a window of ``--seconds``, scrapes the
game and the gate at both edges, stops the cluster and prints, as the
LAST line of stdout, the one JSON object of the contract.

Set-up (``setup_s``) is everything from the start of this process to
the first instant of the window. ``--rehearsal`` shrinks the sizes for
a CPU run (never a cell) and is the only way past the TPU check; a cell
on several chips is rehearsed on as many host devices
(``--xla_force_host_platform_device_count``, for the served cluster
only).

A cell's world is read from its configuration (``world.py``): one space
on one chip, one megaspace tiled over the cell's chips, or many spaces
on one chip (``game.n_spaces``). Nothing here branches on a cell's or a
configuration's name.
"""
from __future__ import annotations

import argparse
import collections
import importlib.util
import json
import math
import os
import re
import select
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from http.client import HTTPException

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)          # the per-layer readers import scrapes, work
WORK = os.path.join(ROOT, ".bench_work")

from world import Shape, border_untested  # noqa: E402

# Warm-up, after every client is placed: the game has to serve
# WARM_FRAMES consecutive frames (at least WARM_SECONDS of them) on
# time — in no more than WARM_SLACK times their nominal span — with no
# compile logged meanwhile and its ladder at NORMAL.
WARM_FRAMES, WARM_SECONDS, WARM_SLACK = 5, 3.0, 1.1
WARM_TIMEOUT_S = 240.0
READY_TIMEOUT_S = 420.0
# `start` gives a game 120 s to say it has started (cli.py
# _wait_started, no option). A tiled world that compiles its tick in
# that run needs a little more (fill 400,000 rows, then ~92 s of
# compiling: 121 s, my chip run, PR 28), and `start` then reports FAILED
# while the game is alive and comes up a second later. `start` skips
# what already runs, so the operator's remedy is the harness's: wait
# for the game's own word, then `start` once more for the gate.
STARTED_TAG = b"GOWORLD_TPU_PROCESS_STARTED"
SLOW_START_TIMEOUT_S = 900.0
# the runtime's word when another process still holds a chip, and how
# long a run asks again before it gives up
CHIPS_BUSY = b"Device or resource busy"
CHIPS_BUSY_TIMEOUT_S, CHIPS_BUSY_PAUSE_S = 60.0, 5.0
GRACE_FRAMES = 2          # a send not seen this long after the close (and
GRACE_MIN_S = 1.0         # at least this long) is `failed`
WINDOW_LEAD_S = 0.5       # from choosing the window's start to the start
SAMPLE_AT = 0.125         # where in the window the first audit sample falls
# the program's own words when a tile's exchange buffers overflow
# (entity/manager.py: it has no counter for them): lines of the game's
# log since the window opened
MESH_DROPPED = re.compile(
    rb"megaspace (?:migrate demand \d+ exceeds|halo demand \d+ exceeds"
    rb"|dropped \d+ border-crossing)")

# the limit of every number that decides ``correct``: all are counts of
# answers that differ from the reference's, so all are exact (PERF.md)
LIMITS = {"pos_wrong": 0, "order_back": 0, "final_missing": 0,
          "interest_extra": 0, "npc_stray": 0, "npc_cross_missing": 0,
          "rows_wrong": 0, "avatar_row_off": 0, "cross_missed": 0,
          "rpc_wrong": 0, "mirror_errors": 0, "never_seen": 0,
          "shed": 0, "events_undecoded": 0, "world_size_off": 0,
          "entities_lost": 0, "mesh_dropped": 0, "border_untested": 0,
          "space_wrong": 0, "attr_wrong": 0, "hop_unanswered": 0,
          "hops_untested": 0}


def say(msg: str) -> None:
    print(msg, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def env_for_children(host_devices: int = 0) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env["JAX_LOG_COMPILES"] = "1"     # the game logs every compile
    # the megaspace tick is traced in an order that follows Python's
    # string hashes: under a random hash seed every process compiles it
    # anew (89-92 s on the chip in each of 4 starts, my chip runs,
    # PR 28; 5 of 6 starts missed the cache on the CPU, 6 of 6 hit under
    # a fixed seed; PERF.md section 7). One seed for every run, so that
    # only a checkout's first run compiles
    env["PYTHONHASHSEED"] = "0"
    if host_devices > 1:              # a rehearsal of a cell on several chips
        env["XLA_FLAGS"] = " ".join(
            [env.get("XLA_FLAGS", ""),
             f"--xla_force_host_platform_device_count={host_devices}"]
        ).strip()
    return env


def gw(args: list[str], timeout: float,
       host_devices: int = 0) -> tuple[int, str, float]:
    t0 = time.monotonic()
    try:
        r = subprocess.run([sys.executable, "-m", "goworld_tpu"] + args,
                           capture_output=True, text=True,
                           env=env_for_children(host_devices), cwd=ROOT,
                           timeout=timeout)
        rc, out = r.returncode, r.stdout + r.stderr
    except subprocess.TimeoutExpired as e:
        rc, out = 124, f"timed out after {timeout:.0f} s: {e.stdout}"
    return rc, out, time.monotonic() - t0


def http(port: int, path: str, timeout: float = 10.0,
         tries: int = 3) -> str:
    """GET a page of the game's or the gate's debug server. The pages
    read here change nothing, so a request that times out or is cut
    (the machine stands still now and then, for seconds) is asked again;
    the one request that starts something (``/profile``) passes
    ``tries=1``."""
    for left in range(tries - 1, -1, -1):
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/{path}",
                                        timeout=timeout) as r:
                return r.read().decode()
        except (OSError, HTTPException) as e:
            if not left:
                raise
            say(f"[run] GET /{path} failed ({e!r}); asking again")
            time.sleep(0.5)


_PROM = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)$")


def parse_prom(text: str) -> dict[str, float]:
    """Prometheus text -> {"name{labels}": value} (labels as printed)."""
    out = {}
    for line in text.splitlines():
        m = _PROM.match(line)
        if m and not line.startswith("#"):
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                pass
    return out


def procs_in(server_dir: str) -> list[int]:
    """Every live process whose cwd is the server directory."""
    pids = []
    real = os.path.realpath(server_dir)
    for p in os.listdir("/proc"):
        if p.isdigit():
            try:
                if os.path.realpath(f"/proc/{p}/cwd") == real:
                    with open(f"/proc/{p}/stat") as f:
                        if f.read().rsplit(") ", 1)[1][0] != "Z":
                            pids.append(int(p))
            except OSError:
                continue
    return pids


def tail_logs(server_dir: str, chars: int = 3000) -> None:
    rd = os.path.join(server_dir, "run")
    if os.path.isdir(rd):
        for name in sorted(os.listdir(rd)):
            if name.endswith(".log"):
                with open(os.path.join(rd, name), errors="replace") as f:
                    say(f"---- {name} (tail) ----\n{f.read()[-chars:]}")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cluster:
    """The served system of one run: its directory, ports and probes."""

    def __init__(self, sd: str):
        self.sd = sd
        self.ports = {k: free_port() for k in (
            "dispatcher_port", "game_http_port", "gate_port",
            "gate_http_port")}
        self.game_log = os.path.join(sd, "run", "game1.log")

    def frames(self) -> tuple[float, int, dict]:
        """(instant, frames served so far, the ladder's account)."""
        t = time.monotonic()
        ov = json.loads(http(self.ports["game_http_port"], "overload"))
        gov = next(iter(ov.get("governors", {}).values()))
        return t, int(gov["observations"]), {
            "ladder": gov["state"], "transitions": gov["transitions"],
            "shed": ov.get("shed")}

    def compiles(self, lo: int = 0) -> tuple[int, int]:
        """(compile lines in the game's log from byte ``lo``, its size)."""
        with open(self.game_log, "rb") as f:
            f.seek(lo)
            data = f.read()
        return len(re.findall(rb"Compiling \S+", data)), lo + len(data)

    def compiled(self, lo: int, hi: int) -> dict[str, int]:
        """What the game's log says was compiled between two of its
        sizes: {program name: times}."""
        with open(self.game_log, "rb") as f:
            f.seek(lo)
            names = re.findall(rb"Compiling (\S+)", f.read(hi - lo))
        return {n.decode(errors="replace"): k
                for n, k in collections.Counter(names).items()}

    def game_came_up(self, timeout: float) -> bool:
        """After a `start` that gave up on the game: whether the game's
        process is alive and says it has started within ``timeout``."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            try:
                with open(os.path.join(self.sd, "run", "game1.pid")) as f:
                    os.kill(int(f.read().strip()), 0)
                with open(self.game_log, "rb") as f:
                    if STARTED_TAG in f.read():
                        return True
            except (OSError, ValueError):
                return False
            time.sleep(1.0)
        return False

    def chips_were_busy(self) -> bool:
        """Whether the game died because it could not open its chips."""
        try:
            with open(self.game_log, "rb") as f:
                return CHIPS_BUSY in f.read()
        except OSError:
            return False

    def mesh_dropped(self, lo: int) -> int:
        """Lines of the game's log from byte ``lo`` that say a tile's
        migrate or halo buffer overflowed or a border-crosser was
        dropped."""
        with open(self.game_log, "rb") as f:
            f.seek(lo)
            return len(MESH_DROPPED.findall(f.read()))

    def scrape(self) -> dict:
        """Both processes' counters at one edge of the window."""
        for left in (2, 1, 0):       # the edge's instant is that of the
            t = time.monotonic()     # request that was answered
            try:
                game = parse_prom(http(self.ports["game_http_port"],
                                       "metrics", tries=1))
                break
            except (OSError, HTTPException):
                if not left:
                    raise
        t_game = 0.5 * (t + time.monotonic())
        _t, _n, ladder = self.frames()
        frames = int(game["tick_latency_ms_count"])
        gv = json.loads(http(self.ports["game_http_port"], "vars"))
        gate = parse_prom(http(self.ports["gate_http_port"], "metrics"))
        syncage = json.loads(http(self.ports["gate_http_port"],
                                  "syncage"))
        return {"t": t_game, "game": game, "gate": gate,
                "syncage": syncage, "frames": frames, "ladder": ladder,
                "events_undecoded": gv.get("aoi_events_dropped", {}),
                "log_size": os.path.getsize(self.game_log)}


def start_cluster(cl: Cluster, host_devices: int = 0) -> int:
    """`start`, and what an operator would do where it fails for a
    reason that passes. (1) The chips are busy: a game that held four
    chips is gone some ten seconds before they can be opened again —
    every thread of it has ended by then, nothing in ``/proc`` shows who
    holds them — and the next game dies with ``open(/dev/vfio/1):
    Device or resource busy`` (my chip runs, PR 28: 9-15 s after four
    chips, 3 s after one; a run that followed another at once found
    them busy once and free 14 s later). The harness opens no device
    node to find out: it runs `start` again, which skips what runs.
    (2) `start` gave up on a game that compiles its tick (see
    STARTED_TAG above): wait for the game's own word, then `start` once
    more for the gate."""
    rc, out, secs = gw(["start", cl.sd], 1100, host_devices)
    say(f"[run] start: rc {rc} in {secs:.1f} s: "
        + " | ".join(out.strip().split("\n")[:6]))
    busy_end = time.monotonic() + CHIPS_BUSY_TIMEOUT_S
    while rc != 0 and cl.chips_were_busy() \
            and time.monotonic() < busy_end:
        time.sleep(CHIPS_BUSY_PAUSE_S)
        os.replace(cl.game_log, cl.game_log + ".busy")
        rc, out, secs = gw(["start", cl.sd], 1100, host_devices)
        say(f"[run] the chips were busy; `start` again: rc {rc} in "
            f"{secs:.1f} s: " + " | ".join(out.strip().split("\n")[:6]))
    if rc != 0 and "game1: FAILED" in out \
            and cl.game_came_up(SLOW_START_TIMEOUT_S):
        rc, out, secs = gw(["start", cl.sd], 300, host_devices)
        say(f"[run] the game came up after `start` had given up on "
            f"it (it compiles its tick in this run); `start` once "
            f"more: rc {rc} in {secs:.1f} s: "
            + " | ".join(out.strip().split("\n")[:6]))
    return rc


def shed_count(ladder: dict) -> int:
    """Packets shed so far plus ladder transitions so far."""
    shed = ladder.get("shed") or {}
    n = 0
    stack = [shed]
    while stack:
        v = stack.pop()
        if isinstance(v, dict):
            stack.extend(v.values())
        elif isinstance(v, (list, tuple)):
            stack.extend(v)
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            n += int(v)
    return n + len(ladder.get("transitions") or [])


def wait_line(proc, want: str, timeout: float) -> str | None:
    """Next line of the child that starts with ``want`` (or FAILED)."""
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        ready, _, _ = select.select([proc.stdout], [], [], 1.0)
        if not ready:
            if proc.poll() is not None:
                return None
            continue
        line = proc.stdout.readline()
        if not line:
            return None
        say(f"[bots] {line.rstrip()}")
        if line.startswith(want):
            return line
        if line.startswith("FAILED"):
            return None
    return None


def warm_frames(cl: Cluster, hz: float) -> dict:
    """Block until the game has served its warm frames on time; returns
    what was observed."""
    need = max(WARM_FRAMES, math.ceil(WARM_SECONDS * hz))
    poll = min(0.1, 0.25 / hz)
    marks: list[tuple[float, int]] = []       # (instant, frame) boundaries
    marks_compiles: list[int] = []
    last = None
    end = time.monotonic() + WARM_TIMEOUT_S
    seen = 0
    while time.monotonic() < end:
        t, n, ladder = cl.frames()
        if last is not None and n > last:
            seen += n - last
            marks.append((t, n))
            marks_compiles.append(cl.compiles()[0])
            # the newest stretch of `need` frames
            for i in range(len(marks) - 1, -1, -1):
                if marks[-1][1] - marks[i][1] >= need:
                    span = marks[-1][0] - marks[i][0]
                    frames = marks[-1][1] - marks[i][1]
                    if span <= WARM_SLACK * frames / hz + poll \
                            and marks_compiles[-1] == marks_compiles[i] \
                            and ladder["ladder"] == "NORMAL":
                        return {
                            "frames_watched": seen, "warm_frames": frames,
                            "warm_span_s": span,
                            "compiles_so_far": marks_compiles[-1]}
                    break
        last = n
        time.sleep(poll)
    raise RuntimeError(
        f"the game never served {need} consecutive frames on time within "
        f"{WARM_TIMEOUT_S:.0f} s (frames watched: {seen})")


def world_tick(cl: Cluster) -> tuple[int, int]:
    """(the world's tick count now, the audit plane's sample period)."""
    gv = json.loads(http(cl.ports["game_http_port"], "vars"))
    return int(gv.get("bench_tick", 0)), int(gv.get("bench_audit_every", 0))


def open_before_sample(cl: Cluster, hz: float,
                       seconds: float) -> tuple[float, dict]:
    """Every ``audit_sample_every`` ticks (64, the program's default)
    the game's audit plane samples on the logic thread. At 100,000
    entities that adds a tenth of a second to its frame and, in about
    one run in twelve, a frame of 1.7 to 4.4 s follows it (PERF.md
    section 7): the worst stall a player of this deployment meets. A
    window that holds a sample or not by the chance of its set-up reads
    two different things, so every window opens at the same place
    against that cadence: the first sample inside it falls at an eighth
    of the window (clear of the traced run's capture in the middle, and
    reachable from today's set-up without waiting a whole period), and
    every run of a cell holds as many samples. The cadence is the
    program's own, read through the fixture.

    Returns the window's first instant (mid-frame, so that at a whole
    number of frames per window neither edge sits on a frame boundary)
    and what was done."""
    poll = min(0.1, 0.25 / hz)
    _t, n0, _l = cl.frames()
    while True:                      # a fresh frame boundary, and the
        t_b, n, _l = cl.frames()     # world's tick count right after it
        if n > n0:
            break
        time.sleep(poll)
    tick_b, every = world_tick(cl)
    k = math.ceil((time.monotonic() + WINDOW_LEAD_S - t_b) * hz - 0.5)
    info = {"sample_every": every, "tick_when_warm": tick_b}
    if every > 0:
        to_sample = round(SAMPLE_AT * seconds * hz) % every
        k += (-(tick_b + k) - to_sample) % every
    info["waited_frames"] = k
    return t_b + (k + 0.5) / hz, info


def capture(cl: Cluster, seconds: float, logdir: str) -> dict:
    body = json.loads(http(
        cl.ports["game_http_port"],
        f"profile?seconds={seconds}&logdir={logdir}", timeout=60.0,
        tries=1))
    if not body.get("ok"):
        raise RuntimeError(f"/profile refused: {body}")
    return body


def find_xplane(logdir: str) -> str | None:
    best = None
    for base, _dirs, files in os.walk(logdir):
        for name in files:
            if name.endswith(".xplane.pb"):
                p = os.path.join(base, name)
                if best is None or os.path.getmtime(p) > \
                        os.path.getmtime(best):
                    best = p
    return best


def effective(cfg: dict, mix: dict, rehearsal: bool) -> tuple[dict, dict]:
    """The configuration and the mix as they are served. Each states
    under ``rehearsal`` the sizes of a CPU run (a lap of the orbit in
    10 s, not 50, so that a window of 8 s holds crossings of the AOI
    edge and, in a tiled world, of a tile border): ``--rehearsal`` takes
    them, a measurement never does."""
    cfg, mix = json.loads(json.dumps(cfg)), json.loads(json.dumps(mix))
    small_cfg, small_mix = cfg.pop("rehearsal", {}), mix.pop("rehearsal", {})
    if rehearsal:
        for k, v in small_cfg.items():
            cfg[k].update(v)
        mix.update(small_mix)
    return cfg, mix


def run(a) -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if a.cell_file:         # a cell not admitted yet, by hand and in tests
        with open(os.path.join(ROOT, a.cell_file)) as f:
            more = json.load(f)
        bench["configs"].append(more["config"])
        bench["workloads"].append(more["workload"])
        bench["per_layer"] += more.get("per_layer", [])
        for m in bench["per_layer"]:
            if m["name"] in more.get("per_layer_workloads", ()):
                m["workloads"] = m["workloads"] + [more["workload"]["name"]]
    cell = next((w for w in bench["workloads"]
                 if w["name"] == a.workload), None)
    if cell is None:
        say(f"no workload {a.workload!r} in BENCHMARK.json")
        return 2
    if not os.path.isfile(os.path.join(ROOT, "goworld_tpu",
                                       "__init__.py")):
        say("the system under test (goworld_tpu/) is not in this "
            "directory: nothing to measure")
        return 2
    centry = next(c for c in bench["configs"]
                  if c["name"] == cell["config"])
    with open(os.path.join(ROOT, centry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    cfg, mix = effective(cfg, mix, a.rehearsal)
    chips = int(cell["chips"])
    shape = Shape(cfg)
    if shape.tiles not in (1, chips):
        say(f"the configuration tiles {shape.tiles} chips, the cell asks "
            f"for {chips}")
        return 2
    host_devices = chips if a.rehearsal else 0
    hz = float(a.tick_hz or cfg["game"]["tick_hz"])
    cfg["game"]["tick_hz"] = hz
    clients = int(mix["clients"])
    npcs = int(cfg["world"]["live"]) - clients
    traced = bool(a.trace)
    if traced and a.seconds < 8:
        say("--trace 1 needs a window of 8 s or more")
        return 2

    sd = os.path.join(WORK, a.workload)
    shutil.rmtree(sd, ignore_errors=True)
    os.makedirs(sd)
    cl = Cluster(sd)
    shutil.copy(os.path.join(HERE, "fixture", "server.py"),
                os.path.join(sd, "server.py"))
    with open(os.path.join(HERE, "fixture", "goworld_tpu.ini")) as f:
        ini = f.read()
    deployment = f"\n[deployment]\nfaults = {a.control_faults}\n" \
        f"faults_seed = {a.seed % 2**31}\n" if a.control_faults else ""
    served = dict(cfg["game"])
    if a.plant == "radius":     # tests: the sweep's box is not the stated one
        served["aoi_radius"] = float(served["aoi_radius"]) - 2.0
    with open(os.path.join(sd, "goworld_tpu.ini"), "w") as f:
        f.write(ini.format(
            game_keys="\n".join(
                f"{k} = {str(v).lower() if isinstance(v, bool) else v}"
                for k, v in served.items()),
            deployment=deployment, **cl.ports))
    with open(os.path.join(sd, "bench_params.json"), "w") as f:
        json.dump({"npcs": npcs, "seed": a.seed,
                   "megaspace": shape.mega, "borders": shape.borders,
                   "n_spaces": shape.spaces,
                   "extent_x": shape.extent_x, "extent_z": shape.extent_z,
                   "aoi_radius": cfg["game"]["aoi_radius"],
                   "plant": a.plant}, f)
    for name, obj in (("config.json", cfg), ("mix.json", mix)):
        with open(os.path.join(sd, name), "w") as f:
            json.dump(obj, f)
    say(f"[run] {a.workload}: config {cell['config']}, mix "
        f"{cell['traffic']}, {clients} clients, {npcs} NPCs, "
        + (f"a megaspace of {shape.tx}x{shape.tz} tiles, " if shape.mega
           else f"{shape.spaces} spaces, " if shape.spaces > 1
           else "one space, ")
        + f"capacity {cfg['game']['capacity']}"
        + (" a tile" if shape.mega else " a space" if shape.spaces > 1
           else "")
        + f", {hz:g} Hz, seed {a.seed}, window "
        f"{a.seconds:g} s, trace {int(traced)}"
        + (", REHEARSAL" if a.rehearsal else "")
        + (f", CONTROL faults {a.control_faults}" if a.control_faults
           else "") + (f", PLANT {a.plant}" if a.plant else ""))

    bots = None
    result: dict = {}
    problems: list[str] = []
    try:
        rc = start_cluster(cl, host_devices)
        if rc != 0:
            tail_logs(sd)
            return 1
        gv = json.loads(http(cl.ports["game_http_port"], "vars"))
        device = gv.get("device") or {}
        say(f"[run] game1 serves on {device}; first tick "
            f"{gv.get('first_tick_s')} s; compile cache "
            f"{gv.get('compile_cache')}")
        on_chip = device.get("platform") == "tpu" \
            and device.get("count") == chips
        if not on_chip and not a.rehearsal:
            say(f"[run] refused: the cell needs {chips} TPU chip(s), the "
                f"game serves on {device}")
            return 3

        undecoded0 = sum((gv.get("aoi_events_dropped") or {}).values())
        bots = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "bots.py"),
             "--gate-port", str(cl.ports["gate_port"]),
             "--config", os.path.join(sd, "config.json"),
             "--mix", os.path.join(sd, "mix.json"),
             "--seed", str(a.seed),
             "--out", os.path.join(sd, "bots.json")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env_for_children(), cwd=ROOT, start_new_session=True)
        if wait_line(bots, "READY", READY_TIMEOUT_S) is None:
            say("[run] the clients never got ready")
            tail_logs(sd)
            return 1
        t_ready = time.monotonic() - T_START
        warm = warm_frames(cl, hz)
        t0, audit = open_before_sample(cl, hz, a.seconds)
        grace = max(GRACE_FRAMES / hz, GRACE_MIN_S)
        bots.stdin.write(f"WINDOW {t0!r} {a.seconds!r} {grace!r}\n")
        bots.stdin.flush()
        if a.plant:
            with open(os.path.join(sd, "plant.on"), "w"):
                pass
        time.sleep(max(0.0, t0 - time.monotonic()))
        setup_s = time.monotonic() - T_START
        edge0 = cl.scrape()
        tick0, every = world_tick(cl)
        say(f"[run] set-up {setup_s:.1f} s (clients ready at "
            f"{t_ready:.1f} s; warm-up {warm}; audit {audit}); window "
            f"open at world tick {tick0}, ladder {edge0['ladder']}")
        prof = span_open = span_close = None
        if traced:
            # 3.3 frames: three starts of the tick's program, so two
            # whole frames. No longer: the tile's trace is ~60 MB and
            # ~25 s of stop_trace for every second captured.
            span = min(6.0, max(2.0, 3.3 / hz), a.seconds - 4.0)
            # in mid-window; in a world of many spaces over the window's
            # last frames, ending at its close. stop_trace holds the
            # game's interpreter for seconds, and the one such world
            # there is, under its cell's load, never hears from its
            # clients again after standing still that long in mid-window
            # (four traced runs of four, my chip runs, PR 29; PERF.md
            # section 7, fault 11): there the stall has to fall where
            # the traffic has ended
            last_frames = shape.spaces > 1
            time.sleep(max(0.0, t0 - time.monotonic() + (
                a.seconds - span - 0.25 if last_frames
                else 0.5 * (a.seconds - span) - 1.0)))
            # the series the readers take from the scrapes end here,
            # where the capture begins
            span_open = {
                "game": parse_prom(http(cl.ports["game_http_port"],
                                        "metrics")),
                "gate": parse_prom(http(cl.ports["gate_http_port"],
                                        "metrics"))}
            if last_frames:
                time.sleep(max(0.0, t0 + a.seconds - span
                               - time.monotonic()))
            prof = capture(cl, span, os.path.join(sd, "profile"))
            say(f"[run] profiler capture: {prof}")
            if not last_frames:
                # the frames the capture holds, by the game's own
                # histogram: host_ms is read over these
                time.sleep(span)
                span_close = {"game": parse_prom(http(
                    cl.ports["game_http_port"], "metrics"))}
        time.sleep(max(0.0, t0 + a.seconds - time.monotonic()))
        edge1 = cl.scrape()
        tick1, _ = world_tick(cl)
        samples = tick1 // every - tick0 // every if every else 0
        if traced:
            if span_close is None:      # the capture ended at the close
                span_close = {"game": edge1["game"]}
            # the capture has to be on disk before the game is stopped;
            # stop_trace holds /profile's lock meanwhile, so watch the
            # file, not the endpoint
            t_wait = time.monotonic()
            xp, size = None, -1
            while time.monotonic() < t_wait + 240.0:
                xp = find_xplane(os.path.join(sd, "profile"))
                if xp is not None:
                    now = os.path.getsize(xp)
                    if now == size and now > 0:
                        break
                    size = now
                time.sleep(1.0)
            say(f"[run] capture on disk after "
                f"{time.monotonic() - t_wait:.1f} s: {xp} ({size} bytes)")
        in_window = cl.compiles(edge0["log_size"])[0] \
            - cl.compiles(edge1["log_size"])[0]
        say(f"[run] window closed: frames {edge0['frames']} -> "
            f"{edge1['frames']} in {edge1['t'] - edge0['t']:.3f} s; "
            f"compiles logged inside the window: {in_window} "
            f"{cl.compiled(edge0['log_size'], edge1['log_size'])}; audit "
            f"samples inside the window (world ticks {tick0}..{tick1}, "
            f"one every {every}): {samples}; ladder "
            f"at the edges {edge0['ladder']} / {edge1['ladder']}; "
            f"interest events never decoded {edge0['events_undecoded']}"
            f" / {edge1['events_undecoded']}")
        if wait_line(bots, "DONE", a.seconds + 240.0) is None:
            problems.append("the clients did not finish")
        try:
            bots.wait(timeout=30)
        except subprocess.TimeoutExpired:
            problems.append("the clients' process did not exit")
        # window, settle wait and read-back: until the last answer
        dropped = cl.mesh_dropped(edge0["log_size"])
        result = {"edge0": edge0, "edge1": edge1, "setup_s": setup_s,
                  "mesh_dropped": dropped,
                  "compiles_in_window": in_window, "device": device,
                  "audit_samples_in_window": samples,
                  "on_chip": on_chip, "world": gv.get("bench_npcs"),
                  "undecoded_at_start": undecoded0,
                  "prof": prof, "span_open": span_open,
                  "span_close": span_close}
    except Exception as e:
        import traceback

        traceback.print_exc()
        problems.append(repr(e))
        tail_logs(sd)
    finally:
        if bots is not None:
            if bots.poll() is None:
                os.killpg(bots.pid, signal.SIGKILL)
                bots.wait()
            bots.stdin.close()
            bots.stdout.close()
        rc, out, secs = gw(["stop", sd], 120)
        say(f"[run] stop: rc {rc} in {secs:.1f} s: "
            + " | ".join(out.strip().split("\n")))
        left = procs_in(sd)
        for pid in left:                    # never leave anything running
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        if rc != 0 or left:
            problems.append(f"stop rc {rc}, processes left {left}")
    if problems or not result:
        say(f"[run] no result: {problems}")
        return 1
    return report(a, bench, cell, cfg, mix, cl, result, shape)


def report(a, bench, cell, cfg, mix, cl, res, shape) -> int:
    sd = cl.sd
    with open(os.path.join(sd, "bots.json")) as f:
        bots = json.load(f)
    e0, e1 = res["edge0"], res["edge1"]
    frames = e1["frames"] - e0["frames"]
    seconds = e1["t"] - e0["t"]
    end_to_end = dict(bots["metrics"])
    end_to_end["served_hz"] = frames / seconds
    end_to_end["setup_s"] = res["setup_s"]

    trace = None
    if a.trace:
        xp = find_xplane(os.path.join(sd, "profile"))
        if xp is None:
            say("[run] the profiler left no .xplane.pb")
            return 1
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = os.path.join(sd, "trace.json")
        r = subprocess.run(
            [sys.executable, os.path.join(HERE, "trace_reduce.py"), xp,
             out, "--frame-s", repr(1.0 / float(cfg["game"]["tick_hz"]))],
            env=env, cwd=ROOT, capture_output=True, text=True,
            timeout=300)
        if r.returncode != 0:
            say(f"[run] trace_reduce failed: {r.stdout}{r.stderr}")
            return 1
        with open(out) as f:
            trace = json.load(f)
        say(f"[run] trace: {os.path.getsize(xp)} bytes; planes "
            f"{trace.get('planes')}; device lines {trace.get('lines')}; "
            f"modules {trace.get('modules')}")

    stats = bots.get("stats") or {}
    device = {"platform": res["device"].get("platform"),
              "kind": res["device"].get("kind"),
              "count": res["device"].get("count"),
              "memory_peak_bytes": stats.get("memory_peak_bytes")}
    cellinfo = {"cell": cell, "config": cfg, "mix": mix,
                "device_kind": device["kind"], "served_hz": frames / seconds}
    # a traced run: the window's series end where the capture begins.
    # The tracer stretches the captured frames' host spans, and the
    # frame in which stop_trace runs takes seconds on four chips
    # (2.66 s of `decode_fanout`, my chip run, PR 28): read over the
    # whole window, every span's mean would be the tracer's
    close = dict(e1, **res["span_open"]) if res["span_open"] else e1
    scrapes = {"open": e0, "close": close, "bots": bots,
               "span_open": res["span_open"],
               "span_close": res["span_close"]}
    metrics_out: dict = {}
    if a.trace:
        if trace is None or not trace.get("busy_s"):
            say("[run] no operation ran on a device plane of the trace: "
                "the per-layer metrics that need it are left out")
        else:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
        for m in bench["per_layer"]:
            reader = load_module(os.path.join(
                HERE, "layer_metrics", m["name"] + ".py"),
                "reader_" + re.sub(r"\W", "_", m["name"]))
            v = reader.read(scrapes, trace, cellinfo)
            if v is not None:
                metrics_out[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            metrics_out[m["name"]] = {"value": end_to_end[m["name"]],
                                      "unit": m["unit"]}

    numbers = dict(bots["numbers"])
    # inside the window: packets shed, ladder transitions, and a
    # ladder that is not at NORMAL at either edge
    numbers["shed"] = shed_count(e1["ladder"]) - shed_count(e0["ladder"]) \
        + sum(e["ladder"].get("ladder") != "NORMAL" for e in (e0, e1))
    # since `start` returned (the mass spawn's own overflow is known,
    # PERF.md section 7): logins, placement and the window
    numbers["events_undecoded"] = sum(
        (e1["events_undecoded"] or {}).values()) - res["undecoded_at_start"]
    numbers["world_size_off"] = abs(
        int(res["world"] or 0)
        - (int(cfg["world"]["live"]) - int(mix["clients"])))
    # a tiled world: log lines that say a tile's exchange overflowed;
    # and the checks judged at the clients must have met a seam — a run
    # with no AOI-edge crossing and no final neighbourhood across a tile
    # border has not tested what the cell is for
    over = bots.get("over_border") or {}
    numbers["mesh_dropped"] = res["mesh_dropped"]
    numbers["border_untested"] = border_untested(over, shape.mega)
    checks = {k: {"value": numbers[k], "limit": LIMITS[k]}
              for k in LIMITS}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    say(f"[run] end to end: {json.dumps(end_to_end)}")
    say(f"[run] clients: {json.dumps({k: bots[k] for k in ('sends', 'calls', 'other_calls', 'move_failed', 'rpc_failed', 'other_failed', 'other_ms', 'hops', 'gen_late_ms', 'receipts', 'sync_records', 'npcs_mirrored', 'settled_s_after_close', 'crossings', 'rows_read', 'over_border', 'mirror_errors_first')})}")
    line = {"correct": correct, "attempted": bots["attempted"],
            "failed": bots["failed"], "metrics": metrics_out,
            "device": device}
    if trace is not None and trace.get("breakdown"):
        line["breakdown"] = trace["breakdown"]
    line["compiles_in_window"] = res["compiles_in_window"]
    line["audit_samples_in_window"] = res["audit_samples_in_window"]
    line["over_border"] = over
    line["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    if not res["on_chip"]:
        say(f"[run] REHEARSAL on {res['device']}: no number below is a "
            "device number")
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU allowed, at tiny sizes: never a measurement")
    ap.add_argument("--tick-hz", type=float, dest="tick_hz",
                    help="serve at another rate than the configuration's "
                         "(the sweep that found it; never the driver)")
    ap.add_argument("--cell-file", dest="cell_file", default="",
                    help="a cell that is not in BENCHMARK.json yet: its "
                         "configuration, workload and per-layer entries "
                         "(benchmark/cells/<cell>.json; by hand, in tests)")
    ap.add_argument("--control-faults", dest="control_faults", default="",
                    help="the control: a [deployment] faults spec that "
                         "breaks a guarantee (docs/ROBUSTNESS.md)")
    ap.add_argument("--plant", default="",
                    choices=("", "alter", "half", "freeze", "radius",
                             "caps", "lose", "stay", "hp"),
                    help="tests: break the timed path underneath")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
