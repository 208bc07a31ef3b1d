"""Poll a served game's /metrics and /vars for the megaspace ghost-row
series while a benchmark run is up; append what is read to a log."""
import configparser
import json
import sys
import time
import urllib.request

ini, out = sys.argv[1], sys.argv[2]
while True:
    try:
        cp = configparser.ConfigParser()
        cp.read(ini)
        port = (cp["game1"].get("http_port")
                or cp["game_common"]["http_port"])
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2) as r:
            lines = [ln for ln in r.read().decode().splitlines()
                     if ln.startswith("mega_ghost")]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/vars", timeout=2) as r:
            v = json.loads(r.read())
        with open(out, "a") as f:
            f.write(json.dumps({"t": time.time(), "metrics": lines,
                                "vars": {k: v.get(k) for k in (
                                    "mega_ghost_rows",
                                    "mega_ghost_full_path_total")}})
                    + "\n")
    except Exception:
        pass
    time.sleep(5)
