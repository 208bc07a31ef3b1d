"""Poll the gate's and the game's /metrics while benchmark runs are up
(the window's means are printed by a traced run only); append the
relay and hold series, with the instant, to a log. Arguments: the log,
then the ini of each served directory to watch."""
import configparser
import json
import sys
import time
import urllib.request

out, inis = sys.argv[1], sys.argv[2:]
KEEP = ("gate_relay_ms_sum", "gate_relay_ms_count",
        "gate_downstream_batch_records_sum",
        "gate_downstream_batch_records_count", "tick_latency_ms_count")


def scrape(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=2) as r:
        text = r.read().decode()
    got = {}
    for ln in text.splitlines():
        name, _, val = ln.rpartition(" ")
        base = name.partition("{")[0]
        if base in KEEP or ('hop="gate_flush"' in name
                            and base.startswith("sync_age_hop_ms_")
                            and not base.endswith("_bucket")):
            got[base] = float(val)
    return got


while True:
    for ini in inis:
        try:
            cp = configparser.ConfigParser()
            if not cp.read(ini):
                continue
            row = {"t": time.time(), "ini": ini,
                   "gate_port": cp["gate1"]["http_port"]}
            row.update(scrape(cp["gate1"]["http_port"]))
            row.update(scrape(cp["game_common"]["http_port"]))
            with open(out, "a") as f:
                f.write(json.dumps(row) + "\n")
        except Exception:
            pass
    time.sleep(2)
