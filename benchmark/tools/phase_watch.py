#!/usr/bin/env python3
"""Watch a served game's frames by phase while ``run.py`` drives it: a
by-hand diagnostic for a long frame (PERF.md section 7, fault 2), which
an untraced run shows only as a lost frame.

    python benchmark/tools/phase_watch.py --workload tile.roam \\
        --out chiprun_out/watch.jsonl [--slow-ms 900] &
    python3 benchmark/run.py --workload tile.roam --seed <n> ...

It waits for ``.bench_work/<cell>/goworld_tpu.ini``, reads the game's
``http_port`` from it, and polls ``/metrics`` (every ``--every``
seconds, 0.25 by default) until the game is gone. Every time
``tick_latency_ms_count`` has moved it writes one line: the frames
served since the last poll, their ``tick_latency_ms`` and the
``tick_phase_ms{phase}`` deltas. A poll whose frames took more than
``--slow-ms`` each is printed too, and at the end the game's log lines
about slow ticks are appended (``slo_breach``, ``residency``, ``took``).
The polling costs the game one ``/metrics`` rendering a poll: use it to
find a fault, never beside a measurement.
"""
from __future__ import annotations

import argparse
import configparser
import json
import os
import re
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
_SERIES = re.compile(r'^(tick_latency_ms_(?:sum|count)|'
                     r'tick_phase_ms_sum\{phase="([^"]+)"\})\s+(\S+)$')


def scrape(port: int) -> dict | None:
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5.0) as r:
            text = r.read().decode()
    except OSError:
        return None
    out = {}
    for line in text.splitlines():
        m = _SERIES.match(line)
        if m:
            out[m.group(2) or m.group(1)] = float(m.group(3))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--every", type=float, default=0.25)
    ap.add_argument("--slow-ms", dest="slow_ms", type=float, default=900.0)
    ap.add_argument("--wait", type=float, default=600.0)
    a = ap.parse_args()
    sd = os.path.join(ROOT, ".bench_work", a.workload)
    ini = os.path.join(sd, "goworld_tpu.ini")
    end = time.monotonic() + a.wait
    port = None
    while time.monotonic() < end and port is None:
        if os.path.isfile(ini):
            cp = configparser.ConfigParser()
            cp.read(ini)
            port = cp.getint("game_common", "http_port", fallback=None)
        if port is None or scrape(port) is None:
            port = None
            time.sleep(0.5)
    if port is None:
        print("[watch] no game to watch", file=sys.stderr)
        return 1
    last, misses, slow = scrape(port), 0, 0
    t_last = time.monotonic()
    with open(a.out, "a") as f:
        while misses < 20:
            time.sleep(a.every)
            now = scrape(port)
            if now is None:
                misses += 1
                continue
            misses = 0
            n = now.get("tick_latency_ms_count", 0) \
                - last.get("tick_latency_ms_count", 0)
            if n <= 0:
                continue
            t = time.monotonic()
            row = {"frames": n, "poll_s": round(t - t_last, 3),
                   "at_frame": now["tick_latency_ms_count"],
                   "frame_ms": (now["tick_latency_ms_sum"]
                                - last["tick_latency_ms_sum"]) / n,
                   "phase_ms": {
                       k: round(v - last.get(k, 0.0), 3)
                       for k, v in now.items()
                       if not k.startswith("tick_latency")
                       and v != last.get(k, 0.0)}}
            f.write(json.dumps(row) + "\n")
            if row["frame_ms"] > a.slow_ms:
                slow += 1
                print(f"[watch] SLOW {json.dumps(row)}", flush=True)
            last, t_last = now, t
        log = os.path.join(sd, "run", "game1.log")
        if os.path.isfile(log):
            with open(log, errors="replace") as g:
                for line in g:
                    if re.search(r"slo_breach|residency_regression|"
                                 r"took \d{4,}", line):
                        f.write(json.dumps({"log": line.strip()[:400]})
                                + "\n")
    print(f"[watch] done: {slow} slow poll(s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
